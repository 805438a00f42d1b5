"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per executed program and the ``XLA Ops`` line one event
per operation, named by its HLO text (``%fusion.64 = bf16[..] fusion(..)``).
Control-flow ops (``while``, calls) hold the ops they run, so op events
nest; a Pallas kernel is one ``custom-call`` op with
``custom_call_target="tpu_custom_call"``, named after the jitted function
around it.  Host planes hold the harness's ``TraceAnnotation`` spans
(``bench.*``) on the threads that ran them.  All timestamps share the
trace's clock.

``reduce`` returns, for the traced window:

* ``busy_s``: the union of the device's op intervals (averaged over the
  devices), ``window_s`` the window's length, ``idle_s`` the rest;
* ``modules``: per program name, [calls, device seconds];
* ``ops``: per ``<program>/<op>``, the op's self time (its duration less
  the ops nested in it) in seconds;
* ``kernels``: per program name, per Pallas kernel op, [calls, device
  seconds, the op's result type];
* ``idle_gaps``: idle device seconds by the innermost host span open at
  each gap's midpoint (``host`` when none is);
* ``breakdown``: the ten ops with the most self time and the ten largest
  idle-gap totals, as the result line carries them.
"""
from __future__ import annotations

import glob
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_ID = re.compile(r"[.(]\d+\)?$")


def base_name(name: str) -> str:
    """A program or op name without its trailing numeric id
    (``fusion.123`` -> ``fusion``, ``jit_run(7)`` -> ``jit_run``)."""
    prev = None
    while prev != name:
        prev, name = name, _ID.sub("", name)
    return name


def op_parts(text: str) -> Tuple[str, str]:
    """(op name, result type) of an op event's HLO text: ``%fusion.64 =
    bf16[8]{0} fusion(..)`` -> (``fusion.64``, ``bf16[8]{0}``)."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    if rest.startswith("("):               # a tuple: up to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return name, rest[: i + 1]
    return name, rest.split(" ", 1)[0]


def self_times(ops: List[Tuple[str, float, float]]) -> List[float]:
    """Each op's duration less the durations of the ops nested directly
    in it (events of one line nest, they never partly overlap)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(f"{root}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def planes_of(pd):
    dev = [p for p in pd.planes if p.name.startswith("/device:TPU")
           and not p.name.endswith("SparseCore")]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    return dev, host


def _events(plane, line_name: str):
    for ln in plane.lines:
        if ln.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events]
    return []


def host_spans(host_planes, prefix: str = "bench.") -> List[Tuple]:
    out = []
    for p in host_planes:
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return sorted(out)


def reduce(pd, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    """See the module docstring.  ``window`` (ns, trace clock) defaults
    to the ``bench.traced`` host span, else the device events' extent."""
    dev, host = planes_of(pd)
    spans = host_spans(host)
    if window is None:
        w = [(s, e) for s, e, n in spans if n == "bench.traced"]
        window = w[0] if w else None
    per_dev = []
    for p in dev:
        ops = _events(p, "XLA Ops")
        mods = _events(p, "XLA Modules")
        if not ops and not mods:
            continue
        per_dev.append((ops, mods))
    if not per_dev:
        raise ValueError("trace holds no device operations")
    if window is None:
        allev = [x for ops, mods in per_dev for x in ops + mods]
        window = (min(s for _, s, _ in allev), max(e for _, _, e in allev))
    lo, hi = window
    busy_ns = []
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops_t: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, Dict[str, list]] = defaultdict(dict)
    gaps_by: Dict[str, float] = defaultdict(float)
    for d_i, (ops, mods) in enumerate(per_dev):
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        mods = sorted((s, e, n) for n, s, e in mods if e > lo and s < hi)
        busy = _merge(_clip([(s, e) for _, s, e in (ops or
                                                    [(n, s, e) for s, e, n
                                                     in mods])], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        if d_i:
            continue                  # names and gaps from the first device
        for s, e, n in mods:
            m = modules[base_name(n)]
            m[0] += 1
            m[1] += (min(e, hi) - max(s, lo)) / 1e9
        starts = [s for s, _, _ in mods]
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in ops]
        for (n, s, e), own in zip(clipped, self_times(clipped)):
            i = bisect_right(starts, s) - 1
            mod = (base_name(mods[i][2]) if i >= 0 and mods[i][1] >= e
                   else "none")
            name, out = op_parts(n)
            ops_t[f"{mod}/{name}"] += own / 1e9
            if 'custom_call_target="tpu_custom_call"' in n:
                k = kernels[mod].setdefault(name, [0, 0.0, out])
                k[0] += 1
                k[1] += (e - s) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        inner_spans = [x for x in spans if x[2] != "bench.traced"]
        span_starts = [s for s, _, _ in inner_spans]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            # spans nest (a tick holds its steps), so the innermost one
            # open at ``mid`` is among the last few that started before it
            j = bisect_right(span_starts, mid)
            inner = [(se - ss, n) for ss, se, n in inner_spans[max(0, j - 8):j]
                     if se >= mid]
            gaps_by[min(inner)[1] if inner else "host"] += (b - a) / 1e9
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    top_ops = sorted(ops_t.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_s": window_s - busy_s,
            "window_ns": (lo, hi),
            "modules": {k: list(v) for k, v in modules.items()},
            "ops": dict(ops_t),
            "kernels": {k: dict(v) for k, v in kernels.items()},
            "idle_gaps": dict(gaps_by),
            "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                          "idle_gaps": [[n, s] for n, s in top_gaps]}}


def load(root: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(root))
