"""Closed-loop traffic: a fixed number of clients, each sending its next
request the moment its previous answer completes.

A client runs *sessions*.  A session draws one context (a document, a
file, a chat history) and then sends ``turns`` requests about it in
turn; each request's prompt is the context followed by that turn's own
suffix (a question), so every turn after the first shares the context
as a block-aligned prompt prefix.  ``turns: 1`` with no suffix is plain
single-turn traffic.

Every length is a whole number of ``block`` tokens.  The work a seed
gives is fixed: each length is taken from an evenly spaced grid over its
range, and the seed only permutes the grid (``grid`` values, reshuffled
each time it is used up).  So two seeds send the same sizes in another
order, and token ids are the only other thing the seed draws.

Parameters (a traffic file's ``params``):

    clients        concurrent clients (= batch slots of the cell)
    block          token granularity of every length
    context        [lo, hi] context tokens per session
    suffix         [lo, hi] suffix tokens per turn ([0, 0]: none)
    turns          requests per session
    new_tokens     [lo, hi] tokens generated per request
    new_step       granularity of new_tokens (default 1)
    grid           grid points per length range (default 16)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np


def _grid(lo: int, hi: int, n: int, step: int) -> np.ndarray:
    """``n`` evenly spaced values over ``[lo, hi]``, on multiples of
    ``step`` (duplicates kept, so every grid has ``n`` points)."""
    if hi < lo:
        raise ValueError(f"empty range [{lo}, {hi}]")
    pts = np.linspace(lo, hi, n) if n > 1 else np.asarray([lo], float)
    return (np.round(pts / step) * step).astype(np.int64)


class _Deck:
    """An endless stream of grid values: the grid reshuffled by the seed
    each time it runs out."""

    def __init__(self, values: np.ndarray, rng: np.random.Generator):
        self.values, self.rng, self.left = values, rng, []

    def draw(self) -> int:
        if not self.left:
            self.left = list(self.rng.permutation(self.values))
        return int(self.left.pop())


@dataclass
class Req:
    client: int
    index: int          # request number of this client
    session: int        # session number of this client
    turn: int
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int

    @property
    def request_id(self) -> str:
        return f"c{self.client}.r{self.index}"


class ClosedLoop:
    def __init__(self, params: Dict, seed: int, vocab: int):
        p = dict(params)
        self.clients = int(p["clients"])
        self.block = int(p.get("block", 128))
        self.turns = int(p.get("turns", 1))
        n = int(p.get("grid", 16))
        b = self.block
        ctx, suf = p["context"], p.get("suffix", [0, 0])
        new = p["new_tokens"]
        self.vocab = vocab
        self.seed = int(seed) % (1 << 63)
        root = np.random.SeedSequence([self.seed, 0x7AFF1C])
        r_ctx, r_suf, r_new = (np.random.default_rng(s)
                               for s in root.spawn(3))
        # one deck per quantity, shared by all clients in the order
        # (client, session/turn) below — fixed per seed, not per timing
        self._ctx = _Deck(_grid(ctx[0], ctx[1], n, b), r_ctx)
        self._suf = _Deck(_grid(suf[0], suf[1], n, b), r_suf)
        self._new = _Deck(_grid(new[0], new[1], n,
                                int(p.get("new_step", 1))), r_new)
        self._plan: Dict[Tuple[int, int], Tuple[int, List[int], List[int]]] = {}
        self._next_session = 0
        self._state = [dict(index=0, session=-1, turn=self.turns, ctx=None)
                       for _ in range(self.clients)]

    def _session_plan(self, client: int, session: int):
        """Lengths of one session, drawn in a fixed global order so they
        depend on the seed alone: sessions are numbered client-major
        within rounds (round k holds every client's k-th session)."""
        while (client, session) not in self._plan:
            k = self._next_session
            c, s = k % self.clients, k // self.clients
            self._plan[(c, s)] = (
                self._ctx.draw(),
                [self._suf.draw() for _ in range(self.turns)],
                [self._new.draw() for _ in range(self.turns)])
            self._next_session += 1
        return self._plan[(client, session)]

    def _tokens(self, n: int, *key: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, *key])
        return rng.integers(0, self.vocab, (n,), dtype=np.int64).astype(
            np.int32)

    def next(self, client: int) -> Req:
        """The client's next request (a new session when the last one has
        used up its turns)."""
        st = self._state[client]
        if st["turn"] >= self.turns:
            st["session"] += 1
            st["turn"] = 0
            ctx_len, _, _ = self._session_plan(client, st["session"])
            st["ctx"] = self._tokens(ctx_len, client, st["session"], 0)
        ctx_len, sufs, news = self._session_plan(client, st["session"])
        t = st["turn"]
        suffix = self._tokens(sufs[t], client, st["session"], 1 + t)
        req = Req(client=client, index=st["index"], session=st["session"],
                  turn=t, prompt=np.concatenate([st["ctx"], suffix]),
                  max_new_tokens=news[t])
        st["turn"] += 1
        st["index"] += 1
        return req

    def longest(self) -> Tuple[int, int]:
        """(longest prompt, most new tokens) any request can have."""
        return (int(self._ctx.values.max() + self._suf.values.max()),
                int(self._new.values.max()))


def make(params: Dict, seed: int, vocab: int) -> ClosedLoop:
    return ClosedLoop(params, seed, vocab)
