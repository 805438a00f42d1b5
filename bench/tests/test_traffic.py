"""The traffic generator: deterministic per seed, block-aligned, the same
sizes for every seed, and each mix on its side of the partial budget."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from traffic.closed_loop import make

BENCH = Path(__file__).resolve().parents[1]
BUDGET = (1 + 32 + 2) * 128          # partial-verification budget, tokens
TREE = 61                             # verify tree + root


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def requests(name, seed, per_client=6, vocab=151936):
    g = make(mix(name)["params"], seed, vocab)
    return g, [g.next(c) for _ in range(per_client) for c in range(g.clients)]


@pytest.mark.parametrize("name", ["docqa", "longctx", "chat"])
def test_deterministic_per_seed(name):
    _, a = requests(name, 2**31 + 7)
    _, b = requests(name, 2**31 + 7)
    _, c = requests(name, 12)
    assert all(np.array_equal(x.prompt, y.prompt) and
               x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["docqa", "longctx", "chat"])
def test_block_aligned(name):
    g, reqs = requests(name, 3)
    assert all(len(r.prompt) % g.block == 0 for r in reqs)


@pytest.mark.parametrize("name", ["docqa", "longctx", "chat"])
def test_same_sizes_every_seed(name):
    """A seed permutes one grid of sizes: the sessions of a whole grid
    cycle carry the same multiset of lengths for any seed."""
    sizes = []
    for seed in (1, 99, 2**31 + 5):
        g = make(mix(name)["params"], seed, 1000)
        n = len(g._ctx.values)
        plans = [g._session_plan(k % g.clients, k // g.clients)
                 for k in range(n)]
        sizes.append(Counter(p[0] for p in plans))
    assert sizes[0] == sizes[1] == sizes[2]


@pytest.mark.parametrize("name", ["docqa", "longctx"])
def test_long_mixes_exceed_budget(name):
    _, reqs = requests(name, 5)
    assert min(len(r.prompt) for r in reqs) > BUDGET


def test_chat_stays_under_budget():
    g, reqs = requests("chat", 5, per_client=20)
    assert all(len(r.prompt) + r.max_new_tokens + TREE < BUDGET
               for r in reqs)
    longest, new = g.longest()
    assert longest + new + TREE < BUDGET


def test_docqa_questions_share_the_document():
    g, _ = requests("docqa", 9, per_client=0)
    turns = [g.next(0) for _ in range(3)]
    doc = turns[0].prompt[: len(turns[0].prompt) - 128]
    ctx = min(len(t.prompt) for t in turns) - 384
    assert all(np.array_equal(t.prompt[:ctx], turns[0].prompt[:ctx])
               for t in turns)
    assert len(doc) >= 12288
    nxt = g.next(0)                      # a new session: a new document
    assert not np.array_equal(nxt.prompt[:ctx], turns[0].prompt[:ctx])
