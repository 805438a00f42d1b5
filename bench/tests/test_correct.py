"""What decides ``correct``: the step plan, the control (the reference in
float8 in the program's place) failing, and the harness driven with its
timed path broken underneath reading ``correct`` false.

The runs use the tiny cell of ``tiny.py`` on the CPU: the harness skips
its look for a chip and drives the rest of a run."""
import numpy as np
import pytest

import correct as cor
import tiny


def test_plan_places_partial_runs_after_their_refresh():
    # prompt 100 tokens; first token from prefill at position 100
    steps = [(1, 1),            # refresh, root 100: commits [100, 101)
             (2, 1), (2, 2),    # partial, roots 101 and 102 (path 103)
             (1, 1),            # refresh, root 104: pending 101..104
             (2, 1)]            # partial, root 105
    runs = cor.plan(100, 8, steps)
    assert runs[0] == {"queries": [100], "length": 101, "start": 101,
                       "end": 104}
    assert runs[1] == {"queries": [101, 102, 103, 104], "length": 105,
                       "start": 105, "end": 106}
    assert cor.classes({"steps": steps, "tokens": np.zeros(8)}) == [
        "prefill", "refresh", "partial", "partial", "partial", "refresh",
        "partial"]


def test_full_steps_plan_nothing():
    assert cor.plan(50, 5, [(0, 1)] * 4) == []


def test_judge_needs_every_limit():
    assert cor.judge({"gap_exact": 0.1}, {"gap_exact": 0.2})[0]
    assert not cor.judge({"gap_exact": 0.3}, {"gap_exact": 0.2})[0]
    assert not cor.judge({"gap_exact": 0.1}, {})[0]
    assert not cor.judge({}, {"gap_exact": 0.2})[0]
    assert not cor.judge({"gap_exact": 0.1}, {})[0]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_program_passes_and_control_fails(root):
    """Sound runs read under every limit; the float8 control reads over
    the exact-token limit on the same sample."""
    import control
    for seed in (3, 2**31 + 11):
        keep = {}
        out = tiny.run_tiny(root, seed, keep=keep)
        assert out["correct"], out["compared"]
        got = control.readings(keep)
        assert got["tokens"] >= 100
        assert got["program"]["gap_exact"] < tiny.LIMITS["gap_exact"]
        assert got["control"]["gap_exact"] > tiny.LIMITS["gap_exact"]


def test_full_only_cell_compares_exact_tokens_only(root):
    """A cell whose contexts never pass the partial budget limits only
    its exact tokens (its own limits file), and a sound run is correct."""
    out = tiny.run_tiny(root, 8, cell=tiny.SHORT)
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == {"gap_exact"}


def test_altered_token_is_caught(root):
    """A served token altered where the fused step produces it."""
    def tamper(engine):
        step = engine.step_fused
        calls = [0]

        def broken(st, rows, modes):
            st, so = step(st, rows, modes)
            calls[0] += 1
            if calls[0] % 5 == 0:
                i = int(np.nonzero(rows)[0][0])
                so.tokens = np.array(so.tokens)
                so.tokens[i, 0] = (so.tokens[i, 0] + 1) % \
                    engine.cfg.vocab_size
            return st, so

        engine.step_fused = broken

    out = tiny.run_tiny(root, 5, tamper=tamper)
    assert not out["correct"]


def test_unchanged_state_is_caught(root):
    """A decode step that hands back the state it was given: every row
    re-verifies from the same cache and repeats itself."""
    import jax

    def tamper(engine):
        step = engine.step_fused

        def unchanged(st, rows, modes):
            before = jax.tree_util.tree_map(lambda x: x.copy(), st)
            _, so = step(st, rows, modes)
            return before, so

        engine.step_fused = unchanged

    out = tiny.run_tiny(root, 6, tamper=tamper, cell=tiny.SHORT)
    assert not out["correct"]
