"""The window's arithmetic: what counts, and that a stall shows."""
import numpy as np

import window as win


def steady(t0=0.0, t1=10.0, dt=0.05, rids=("a", "b")):
    out = []
    for i, rid in enumerate(rids):
        t = t0 + i * 0.01
        while t <= t1:
            out.append((t, rid, 1))
            t += dt
    return out


def test_only_deliveries_inside_count():
    d = [(0.5, "a", 3), (1.0, "a", 2), (2.0, "a", 4), (3.5, "a", 1)]
    assert win.tokens_in(d, 1.0, 3.0) == 6
    # gaps 0.5 (ends 1.0), 1.0 (ends 2.0), 1.5 (ends 3.5, outside)
    assert sorted(win.gaps_in(d, 1.0, 3.0).tolist()) == [0.5, 1.0]


def test_gaps_are_per_request_and_pooled():
    d = [(1.0, "a", 1), (1.2, "b", 1), (1.5, "a", 1), (2.2, "b", 1)]
    assert np.allclose(sorted(win.gaps_in(d, 0, 5).tolist()), [0.5, 1.0])


def test_ttft_only_for_requests_sent_in_window():
    d = [(1.3, "a", 1), (2.5, "b", 1), (4.0, "c", 1)]
    sent = {"a": 1.0, "b": 2.0, "c": 3.9}
    got = win.ttfts(d, sent, 1.5, 4.0)
    assert np.allclose(sorted(got), [0.1, 0.5])


def test_stall_shows_in_tail_and_rate():
    base = win.end_to_end(steady(), {}, 2.0, 10.0)
    stalled = [(t + (1.0 if t > 5.0 else 0.0), r, n) for t, r, n in steady()]
    hit = win.end_to_end(stalled, {}, 2.0, 10.0)
    assert abs(base["gap_p50_ms"] - 50.0) < 1e-6
    assert hit["output_tok_s"] < base["output_tok_s"] * 0.9
    # one 1.05 s gap per request among ~160: above the 95th percentile
    # only when it is there at least 5% of the time — so read the max
    assert win.gaps_in(stalled, 2.0, 10.0).max() > 1.0
    stalled_often = [(t + 1.0 * int(t // 0.5) if t > 2.0 else t, r, n)
                     for t, r, n in steady()]
    hit2 = win.end_to_end(stalled_often, {}, 2.0, 40.0)
    assert hit2["gap_p95_ms"] > 1000.0 > base["gap_p95_ms"]
