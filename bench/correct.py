"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests the run finished is
drawn from the seed — always the one with the longest prompt, then
others until ``MIN_TOKENS`` served tokens are in it.  The reference runs
once over each prompt followed by its served tokens and gives, at every
served position, the logit of the reference's best token and of the
served one.  A served token's *gap* is how far its reference logit lies
below the best (0 when the reference agrees).

The gaps are split by how the program produced the token:

* ``gap_exact``: tokens verified against the whole context — the first
  token (from the prefill, with prefix-cache pages attached), and every
  token of a FULL or REFRESH verify step;
* ``gap_partial``: tokens of PARTIAL verify steps, which by design
  attend only the blocks the last refresh selected (SpecPV's
  approximation), so the full-context reference bounds them less
  tightly.

Per class, the widest gap over the sample (``gap_<class>``), the mean
gap (``mean_gap_<class>``) and the share of tokens the reference puts
second or lower (``miss_<class>``) are read; the cell's limits file
(``bench/limits/<cell>.json``) names those compared and their limits.  The control (``control_gaps``)
puts the reference computed in float8 in the program's place.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from model import load_module

BENCH = Path(__file__).resolve().parent
MIN_TOKENS = 320        # served tokens a sample holds at least
PAD = 1024              # reference sequences pad to whole PADs (compiles)
EXACT = ("prefill", "full", "refresh")


def reference(name: str, root: Path = BENCH):
    """The plain reference ``refs/<name>.py``."""
    return load_module(root / "refs" / f"{name}.py", f"ref_{name}")


def sample(finished: Sequence[dict], seed: int) -> List[dict]:
    """The longest-prompt request, then others drawn from the seed, until
    ``MIN_TOKENS`` served tokens are covered."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r["prompt"]), r["rid"]))
    out, rest = [order[0]], order[1:]
    rng = np.random.default_rng([int(seed) % (1 << 63), 0xC0FFEE])
    for i in rng.permutation(len(rest)):
        if sum(len(r["tokens"]) for r in out) >= MIN_TOKENS:
            break
        out.append(rest[i])
    return out


FULL, REFRESH, PARTIAL = 0, 1, 2
CLASS = {FULL: "full", REFRESH: "refresh", PARTIAL: "partial"}


def classes(req: dict) -> List[str]:
    """How each served token was produced: ``prefill`` for the first,
    then the mode of the verify step that emitted it."""
    out = ["prefill"]
    for mode, n in req["steps"]:
        out.extend([CLASS[mode]] * n)
    return out[: len(req["tokens"])]


def plan(n0: int, m: int, steps) -> List[dict]:
    """The refreshes of a request whose PARTIAL steps follow them: for
    each, the refresh's query positions (its pending run and accepted
    path), the committed length after it, and the run of positions
    ``[start, end)`` the following PARTIAL steps verified.

    Token ``j`` (0-based) of the ``m`` served sits at position ``n0 + j``;
    the first comes from the prefill.  A step whose root is the last
    token so far emits ``c`` tokens.  After a FULL or REFRESH step the
    pending run is its last token; PARTIAL steps extend it."""
    out: List[dict] = []
    emitted, pend, cur = 1, n0, None
    for mode, c in steps:
        root = n0 + emitted - 1
        if mode == PARTIAL:
            if cur is None:
                raise ValueError("PARTIAL step with no refresh before it")
            cur["end"] = root + c
        else:
            cur = None
            if mode == REFRESH:
                cur = {"queries": list(range(pend, root + c)),
                       "length": root + c, "start": root + c,
                       "end": root + c}
                out.append(cur)
            pend = root + c
        emitted += c
        if emitted >= m:
            break
    return [r for r in out if r["end"] > r["start"]]


def served_logits(ref, dm, weights, spec: dict, req: dict,
                  fp8: bool = False) -> np.ndarray:
    """Reference logits [m, V] at the position before each served token,
    as the request's steps verified it: the whole causal context for
    the prefill's token and FULL / REFRESH steps, the refresh's selected
    blocks plus the run itself for PARTIAL steps."""
    prompt = np.asarray(req["prompt"], np.int64)
    toks = np.asarray(req["tokens"], np.int64)
    n0, m = len(prompt), len(toks)
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    padded = np.zeros((-(-len(seq) // PAD) * PAD,), np.int32)
    padded[: len(seq)] = seq
    runs = plan(n0, m, req["steps"])
    qpos = sorted({p for r in runs for p in r["queries"]})
    q_idx = np.zeros((max(128, -(-len(qpos) // 128) * 128),), np.int32)
    q_idx[: len(qpos)] = qpos
    where = {p: i for i, p in enumerate(qpos)}
    h, ks, vs, qs = ref.forward(dm, weights, padded, q_idx, fp8=fp8)
    lg = np.array(ref.head(dm, weights, h[n0 - 1: n0 - 1 + m], fp8=fp8))
    for r in runs:
        rows = np.asarray([where[p] for p in r["queries"]])
        sel = ref.select_layers(dm, spec, ks, [q[rows] for q in qs],
                                r["length"])
        end = min(r["end"], n0 - 1 + m)
        hs = ref.segment(dm, weights, ks, vs, spec, sel, r["length"],
                         seq[r["start"]: end], r["start"], fp8=fp8)
        lg[r["start"] - (n0 - 1): end - (n0 - 1)] = ref.head(
            dm, weights, hs, fp8=fp8)
    del ks, vs, qs, h
    return lg


def gaps(ref, dm, weights, spec: dict, req: dict) -> np.ndarray:
    """Per served token: reference best logit - reference logit of it."""
    toks = np.asarray(req["tokens"], np.int64)
    lg = served_logits(ref, dm, weights, spec, req)
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


def control_gaps(ref, dm, weights, spec: dict, req: dict) -> np.ndarray:
    """Per position of the same prompt and served tokens: the gap of the
    token the float8 reference puts first."""
    toks = np.asarray(req["tokens"], np.int64)
    lg = served_logits(ref, dm, weights, spec, req)
    pick = served_logits(ref, dm, weights, spec, req, fp8=True).argmax(-1)
    return lg.max(-1) - lg[np.arange(len(toks)), pick]


def widest(gap: np.ndarray, classes: Sequence[str]) -> Dict[str, float]:
    """Per class of served token (``exact``, ``partial``): the widest gap
    (``gap_<class>``), and two steadier readings beside it, the mean gap
    (``mean_gap_<class>``) and the share of tokens the reference puts
    second or lower (``miss_<class>``, in %).  A class with no token in
    the sample is left out."""
    cls = np.asarray(list(classes)[: len(gap)])
    exact = np.isin(cls, EXACT)
    out = {}
    for name, sel in (("exact", exact), ("partial", ~exact)):
        if sel.any():
            g = gap[sel]
            out[f"gap_{name}"] = float(g.max())
            out[f"mean_gap_{name}"] = float(g.mean())
            out[f"miss_{name}"] = 100.0 * float(np.mean(g > 0))
    return out


def merge(parts: Sequence[Dict[str, float]],
          weights: Sequence[int]) -> Dict[str, float]:
    """Widest gaps by their maximum, means and shares weighted by the
    tokens of each part."""
    out: Dict[str, float] = {}
    keys = {k for p in parts for k in p}
    for k in keys:
        vals = [(p[k], w) for p, w in zip(parts, weights) if k in p]
        if k.startswith("gap_"):
            out[k] = max(v for v, _ in vals)
        else:
            out[k] = sum(v * w for v, w in vals) / sum(w for _, w in vals)
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> (bool, Dict[str, Dict[str, float]]):
    """The numbers the cell limits, each within its limit; a limited
    number the sample cannot give (no token of its class), or a cell
    that limits nothing, fails."""
    compared = {k: {"value": values.get(k), "limit": lim}
                for k, lim in sorted(limits.items())}
    ok = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    return ok, compared


def check(ref, dm, weights, spec: dict, reqs: List[dict],
          limits: Dict[str, float]) -> (bool, Dict, Dict[str, float]):
    parts = [widest(gaps(ref, dm, weights, spec, r), classes(r))
             for r in reqs]
    vals = merge(parts, [len(r["tokens"]) for r in reqs])
    ok, compared = judge(vals, limits)
    return ok, compared, vals
