"""The system under test, built and driven the way a deployment runs it.

One ``SpecPVEngine`` (paged KV, zero-copy partial verification, prefix
cache, Pallas kernels, as the configuration file says) under one
``ContinuousScheduler`` with chunked prefill interleaved with decode.
The harness calls ``tick()`` itself, with its own clock, and runs the
closed-loop clients between ticks: a client whose answer completed in a
tick submits its next request before the next tick.

``Recorder`` wraps the engine's two dispatching entry points
(``step_fused``, ``prefill_step_fused``) on the instance — the program's
code is untouched — to log what each tick ran: rows, modes, context
lengths, prefill chunk shapes and host times.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

MODE_CLASS = {0: "full", 1: "refresh", 2: "partial"}


def request_blocks(gen, spec, dcfg) -> int:
    """Blocks the cell's largest request holds end to end: longest prompt
    + new tokens + the verify buffer and tree margin the engine reserves
    (``request_token_need``)."""
    from repro.core.engine import request_token_need
    from repro.core.tree import TreeSpec
    longest, new = gen.longest()
    tree = TreeSpec.from_branch(dcfg.tree_branch[: dcfg.tree_depth])
    need = request_token_need(longest, new, spec.buffer_size, tree.max_path)
    return -(-need // spec.block_size)


def build_engine(configs, sv: dict, gen, params, dparams):
    """The engine from the architecture's ``program_configs`` and the
    configuration file's ``serving`` settings ``sv``: ``max_len`` the
    largest request rounded to whole blocks, and a page pool that holds
    every client's largest request at once plus the null page, so
    admission never stalls for pages."""
    from repro.core.engine import SpecPVEngine
    cfg, spec, dcfg = configs
    nb = request_blocks(gen, spec, dcfg)
    return SpecPVEngine(
        cfg, spec, dcfg, params, dparams, batch=gen.clients,
        max_len=nb * spec.block_size, partial_verification=True,
        paged=sv["paged_kv"], num_pages=gen.clients * nb + 1,
        prefix_cache=sv["prefix_cache"], zero_copy=sv["zero_copy_partial"])


# ---------------------------------------------------------------------------
# warm-up: exactly the cell's shapes
# ---------------------------------------------------------------------------

def decode_variants(gen, spec) -> List[Tuple[bool, bool, bool]]:
    """(has_full, has_partial, has_refresh) step variants the cell's mode
    mix can need: only FULL when no context ever exceeds the partial
    budget, otherwise every mix of FULL / REFRESH / PARTIAL rows."""
    longest, new = gen.longest()
    if longest + new <= spec.partial_budget_tokens:
        return [(True, False, False)]
    return [(True, False, False), (False, True, False), (True, False, True),
            (True, True, False), (True, True, True)]


def prefill_variants(gen, chunk: int, budget: int) -> List[Tuple[int, int]]:
    """(rows, longest chunk) of the fused prefill dispatches the cell can
    make: chunks are whole blocks up to ``chunk``, and a dispatch packs
    rows oldest-first while their chunks fit ``budget`` together."""
    b = gen.block
    sizes = list(range(b, chunk + 1, b))
    out = []
    for k in range(1, gen.clients + 1):
        for t in sizes:
            if k == 1 or t + (k - 1) * b <= budget:
                out.append((k, t))
    return out


def warm(engine, gen, chunk: int, budget: int) -> Dict[str, int]:
    """Compile and run once every decode and prefill variant of the cell
    on a scratch state that is dropped afterwards."""
    import jax
    import jax.numpy as jnp
    st = engine.empty_state()
    b = engine.batch
    off = jnp.zeros((b,), bool)
    decode = decode_variants(gen, engine.spec)
    for hf, hp, hr in decode:
        # no live row: the variant runs whole and leaves every row as it
        # was (the in-jit row merge), so only compilation happens here
        fn = engine._fused_fn(hf, hp, hr, False, False)
        with engine._on_mesh():
            st, out = fn(engine.params, engine.dparams, st, off,
                         jnp.zeros((b,), jnp.int8), off)
        jax.block_until_ready(out)
    prefill = prefill_variants(gen, chunk, budget)
    rng = np.random.default_rng(0)
    for k, t in prefill:
        curs = []
        for i in range(k):
            n = t if i == 0 else gen.block
            prompt = rng.integers(0, engine.cfg.vocab_size, (n,)).astype(
                np.int32)
            st, cur = engine.prefill_begin_slot(st, i, prompt, chunk=chunk,
                                                max_new_tokens=1)
            curs.append(cur)
        st, _ = engine.prefill_step_fused(st, curs)
        for cur in curs:
            st, _ = engine.prefill_finalize_slot(st, cur)
        for i in range(k):
            st = engine.reset_slot(st, i)
    jax.block_until_ready(st)
    del st
    return {"decode_variants": len(decode), "prefill_variants": len(prefill)}


# ---------------------------------------------------------------------------
# the record of a run
# ---------------------------------------------------------------------------

@dataclass
class Tick:
    t0: float
    t1: float = 0.0
    prefill_tokens: int = 0
    prefill_shapes: List[Tuple[int, int]] = field(default_factory=list)
    prefill_s: float = 0.0
    # decode rows: (request id, mode id, context length, pending tokens)
    rows: List[Tuple[str, int, int, int]] = field(default_factory=list)
    decode_s: float = 0.0


class Recorder:
    """Logs every tick's work by wrapping the engine's dispatch calls on
    the instance; ``spans`` adds profiler annotations (traced runs)."""

    def __init__(self, engine, sched, clock, spans: bool = False):
        self.engine, self.sched, self.clock = engine, sched, clock
        self.ticks: List[Tick] = []
        # rid -> [(mode id, tokens emitted)] of every decode step it took
        self.steps: Dict[str, List[Tuple[int, int]]] = {}
        self.since_refresh: Dict[str, int] = {}
        self.spans = spans
        self._step = engine.step_fused
        self._prefill = engine.prefill_step_fused
        engine.step_fused = self._step_fused
        engine.prefill_step_fused = self._prefill_step_fused

    def _span(self, name: str):
        import contextlib
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin(self) -> Tick:
        t = Tick(t0=self.clock())
        self.ticks.append(t)
        return t

    def _step_fused(self, st, rows, modes):
        tick = self.ticks[-1]
        info = []
        for i in np.nonzero(rows)[0]:
            s = self.sched.slots[i]
            rid = s.req.request_id
            info.append((i, rid, int(modes[i]),
                         len(s.req.prompt) + len(s.tokens),
                         self.since_refresh.get(rid, 1)))
        t0 = self.clock()
        with self._span("bench.decode_step"):
            st, so = self._step(st, rows, modes)
        tick.decode_s += self.clock() - t0
        for i, rid, m, ctx, pend in info:
            n = int(so.counts[i])
            tick.rows.append((rid, m, ctx, pend))
            self.steps.setdefault(rid, []).append((m, n))
            # pending run a later refresh re-verifies: one token after a
            # full or refresh step, growing through partial steps
            self.since_refresh[rid] = pend + n if m == 2 else 1
        return st, so

    def _prefill_step_fused(self, st, cursors):
        tick = self.ticks[-1]
        live = [c for c in cursors if not c.done]
        sizes = [min(len(c.prompt), (c.off // c.chunk + 1) * c.chunk) - c.off
                 for c in live]
        t0 = self.clock()
        with self._span("bench.prefill_step"):
            st, n = self._prefill(st, cursors)
        tick.prefill_s += self.clock() - t0
        tick.prefill_tokens += n
        tick.prefill_shapes.append((len(live), max(sizes)))
        return st, n

    def tick(self) -> Tick:
        t = self.begin()
        with self._span("bench.tick"):
            self.sched.tick()
        t.t1 = self.clock()
        return t


# ---------------------------------------------------------------------------
# closed-loop clients
# ---------------------------------------------------------------------------

class Clients:
    """The traffic's clients: each has one request in flight; when it
    completes, the client submits its next one."""

    def __init__(self, gen, sched, clock):
        self.gen, self.sched, self.clock = gen, sched, clock
        self.submitted: Dict[str, float] = {}
        self.requests: Dict[str, object] = {}       # rid -> generator Req
        self.deliveries: List[Tuple[float, str, int]] = []
        self.seen: Dict[str, int] = {}
        self.done: Dict[str, float] = {}
        self.failed: Dict[str, str] = {}
        self.open = True
        self._current: Dict[int, str] = {}
        self._outputs_seen = 0

    def submit(self, client: int) -> None:
        from repro.serving import Request
        r = self.gen.next(client)
        now = self.clock()
        req = Request(request_id=r.request_id, prompt=r.prompt,
                      max_new_tokens=r.max_new_tokens, eos_id=-1,
                      arrival_s=now)
        self.requests[r.request_id] = r
        self.submitted[r.request_id] = now
        self.seen[r.request_id] = 0
        self._current[client] = r.request_id
        self.sched.submit(req)

    def start(self) -> None:
        for c in range(self.gen.clients):
            self.submit(c)

    def after_tick(self, t: float) -> None:
        """Deliver the tick's new tokens, close completed requests, and
        let their clients send the next one."""
        for s in self.sched.slots:
            if s is None:
                continue
            rid = s.req.request_id
            n = min(len(s.tokens), s.req.max_new_tokens)
            if n > self.seen[rid]:
                self.deliveries.append((t, rid, n - self.seen[rid]))
                self.seen[rid] = n
        outs = self.sched.done_order[self._outputs_seen:]
        self._outputs_seen = len(self.sched.done_order)
        for o in outs:
            if not o.finished and o.request_id not in self.done:
                self.failed[o.request_id] = o.finish_reason
        for c, rid in list(self._current.items()):
            r = self.requests[rid]
            if rid in self.failed or self.seen[rid] >= r.max_new_tokens:
                if rid not in self.failed:
                    self.done[rid] = t
                del self._current[c]
                if self.open:
                    self.submit(c)

    def completed_clients(self) -> int:
        return len({self.requests[r].client for r in self.done})

    def tokens_of(self, rid: str) -> Optional[np.ndarray]:
        out = self.sched.outputs.get(rid)
        if out is not None:
            return np.asarray(out.tokens)
        for s in self.sched.slots:
            if s is not None and s.req.request_id == rid:
                return np.asarray(s.tokens[: s.req.max_new_tokens])
        return None
