"""Chip smoke: the continuous SpecPV server, once, on a TPU at Qwen3-8B widths.

Drives the serving path through the entry points a user calls —
``ServingEngine`` with the continuous scheduler, the paged KV cache, the
fused decode step, fused prefill interleaved with decode, the prefix
cache, zero-copy partial verification and the Pallas kernels — at the
published widths of ``qwen3-8b`` (d_model 4096, 32 query / 8 KV heads,
head_dim 128, d_ff 12288, vocab 151936, bf16).  Only the depth is cut,
to 12 layers, so weights, draft and KV fit one 16 GB v5e chip; weights
are random from ``--seed`` and nothing is read from disk.

Phases, all in this one process (any failure raises, exit code != 0):

* kernels — every Pallas kernel of the serving path against its
  ``kernels/ref.py`` oracle at the real widths;
* logits  — the model's prefill logits through the paged kernel route
  against the XLA route over a contiguous cache, on the same prompt;
* serve   — four requests through ``ServingEngine``: 8192- and
  6144-token prompts (past the 4,480-token partial budget), a
  1024-token prompt (FULL mode only), then a prompt sharing the first
  4096 tokens of the 8192-token one (prefix-cache hit).  Each generates
  64 tokens; the prefill budget interleaves prefill with decode.  Checks
  that every request finished with its tokens, that partial
  verification and a refresh ran, that the prefix cache hit, and that
  the compiled fused decode step calls the Pallas kernels.

``--chips 4`` runs only the sharded engine (``mesh_shape=(1, 4)``): the
12-layer model sharded against the same model unsharded on device 0
(greedy tokens and first-step logits), then the 36-layer model, whose
weights are created directly in their shardings, serving the same four
requests.

Run from the repository root of a machine with a TPU:

    python chip_smoke.py [--chips 4] [--seed 0]

It exits non-zero without a result when JAX finds no TPU.  The last line
of stdout is one JSON object: {"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}; the lines before it report each phase.
Wall times printed there are information, not speed results.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-8b"
LAYERS = 12          # depth cut: weights + draft + KV fit one 16 GB chip
BATCH = 4
MAX_LEN = 10240      # longest prompt + 64 new tokens + tree, block-rounded
CHUNK = 512          # prefill chunk, also the per-tick prefill budget
MAX_NEW = 64
PROMPT_LENS = (8192, 1024, 6144)    # first wave: long, short, long
SHARED = 4096        # leading tokens the second wave shares with request 0
# bf16 tolerance, as a fraction of the reference's largest magnitude:
# inputs and stored activations are bf16 (2^-8 relative rounding), so a
# kernel or route that matches the reference's arithmetic lands well
# inside 1e-2 while a wrong mask, block or head lands at O(1)
TOL = 1e-2
LOGIT_TOL = 5e-2     # 12 bf16 layers between the two routes' outputs


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds spent in XLA backend compiles, from ``jax.monitoring``
    events (a persistent-cache hit skips the compile and adds nothing)."""

    def __init__(self):
        import jax
        self._secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self._secs += secs

    def take(self) -> float:
        """Compile seconds since the previous ``take``."""
        secs, self._secs = self._secs, 0.0
        return secs


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found "
                 f"{devs[0].platform!r} devices; nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {len(devs)}")
    return devs


def close(name: str, got, want, tol: float = TOL) -> float:
    """Max |got - want| over the reference's largest magnitude; fails
    above ``tol`` or on any non-finite value."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                   f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite values")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    log(f"  {name}: rel err {err:.3e} (tol {tol:.0e})")
    check(err <= tol, f"{name}: rel err {err:.3e} > {tol:.0e}")
    return err


def model_configs(layers: int):
    from repro.configs import DraftConfig, SpecPVConfig, get_config
    cfg = get_config(ARCH).replace(num_layers=layers)
    # the Pallas kernels on; every other SpecPV knob at its default
    # (block 128, 1 sink + 32 retrieved + 2 local blocks = 4480 tokens)
    return cfg, SpecPVConfig(use_pallas=True), DraftConfig()


def build_params(cfg, dcfg, seed: int, mesh=None):
    """Random target + draft weights made on the device(s) from ``seed``:
    with a mesh, each leaf is created directly in its sharding."""
    import jax
    from repro.core.draft import init_draft_params
    from repro.models import api
    k_t, k_d = jax.random.split(jax.random.PRNGKey(seed))
    out = []
    for init, key in ((functools.partial(api.init_params, cfg), k_t),
                      (functools.partial(init_draft_params, cfg, dcfg), k_d)):
        sh = None
        if mesh is not None:
            from repro.distributed.sharding import (ShardingRules,
                                                    param_shardings)
            sh = param_shardings(ShardingRules(mesh), jax.eval_shape(init, key))
        compiled = jax.jit(init, out_shardings=sh).lower(key).compile()
        out.append(compiled(key))
        log(f"  init {init.func.__name__}: "
            f"{compiled.memory_analysis().output_size_in_bytes / 2**30:.2f} "
            f"GiB of weights")
    return out


def traffic(vocab: int, seed: int):
    """Two waves of requests from ``seed``: the first three arrive
    together; the fourth shares the first ``SHARED`` tokens of request 0
    and arrives once its blocks are in the prefix cache."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, vocab, (n,)).astype(np.int32)

    first = [toks(n) for n in PROMPT_LENS]
    second = [np.concatenate([first[0][:SHARED],
                              toks(len(first[0]) - SHARED)])]
    names = [f"prompt-{len(p)}" for p in first] + [
        f"shared-prefix-{len(second[0])}"]
    return [[Request(request_id=names[i + off], prompt=p,
                     max_new_tokens=MAX_NEW, eos_id=-1)
             for i, p in enumerate(wave)]
            for off, wave in ((0, first), (len(first), second))]


def serve(cfg, spec, dcfg, params, dparams, waves, mesh_shape=None):
    from repro.serving import ServingConfig, ServingEngine
    srv = ServingEngine(cfg, spec, dcfg, params, dparams, ServingConfig(
        batch=BATCH, max_len=MAX_LEN, prefill_chunk=CHUNK,
        prefill_budget=CHUNK, paged_kv=True, zero_copy_partial=True,
        prefix_cache=True, mesh_shape=mesh_shape))
    outs = {}
    t0 = time.perf_counter()
    for wave in waves:
        for r in wave:
            srv.submit(r)
        outs.update({o.request_id: o for o in srv.run()})
    log(f"  served {len(outs)} requests in {time.perf_counter() - t0:.1f} s "
        f"wall, compiles included")
    return srv, outs


def check_served(srv, outs, waves, vocab: int) -> None:
    want = [r.request_id for wave in waves for r in wave]
    done = [rid for rid in want if rid in outs and outs[rid].finished]
    log(f"  requests finished: {len(done)}/{len(want)}")
    check(len(done) == len(want), f"unfinished requests: "
                                  f"{sorted(set(want) - set(done))}")
    for rid in want:
        toks = np.asarray(outs[rid].tokens)
        log(f"  {rid}: {toks.size} tokens, first {toks[:6].tolist()}, "
            f"finish {outs[rid].finish_reason}")
        check(toks.size == MAX_NEW, f"{rid}: {toks.size} tokens")
        check(((toks >= 0) & (toks < vocab)).all(), f"{rid}: bad token id")
    st = srv.stats
    rows = {m: int(st[f"mode_rows_{m}"]) for m in ("full", "refresh",
                                                   "partial")}
    ps = srv.prefix_stats()
    log(f"  mode rows {rows}, decode steps {int(st['steps'])}, "
        f"prefill tokens {int(st['prefill_tokens'])} in "
        f"{int(st['prefill_dispatches'])} dispatches")
    log(f"  prefix cache: {ps['blocks_matched']} blocks matched, "
        f"{ps['prefill_tokens_skipped']} prompt tokens skipped")
    check(rows["partial"] > 0, "no partial verification ran")
    check(rows["refresh"] >= 1, "no refresh ran")
    check(ps["blocks_matched"] > 0, "no prefix-cache hit")


def check_kernel_route(srv) -> None:
    """Every fused decode-step variant the run compiled calls the Pallas
    kernels (``tpu_custom_call``) — the serving path took the kernel
    route and not the gathered XLA fallback."""
    import jax.numpy as jnp
    eng = srv._engine_for(srv.scfg.batch, paged=True)
    st = srv._continuous.st
    b = eng.batch
    for key, fn in sorted(eng._fused_jits.items()):
        text = fn.lower(eng.params, eng.dparams, st, jnp.ones((b,), bool),
                        jnp.zeros((b,), jnp.int8),
                        jnp.zeros((b,), bool)).as_text()
        n = text.count("tpu_custom_call")
        flags = dict(zip(("full", "partial", "refresh", "sampled", "chain"),
                         key))
        log(f"  fused step {({k for k, v in flags.items() if v})}: "
            f"{n} tpu_custom_call")
        check(n > 0, f"fused step {key} has no Pallas kernel")


def prefill_logits(cfg, spec, params, prompt, *, paged: bool, mesh=None):
    """Last-token logits of ``prompt`` through ``api.prefill`` in
    CHUNK-token chunks: the paged kernel route, or the XLA route over a
    contiguous cache.  With a mesh, params are sharded over it and the
    steps run under it (as the sharded engine runs its steps)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from repro.models import api
    from repro.utils import cdiv
    nb = cdiv(len(prompt), spec.block_size)
    cache = api.init_cache(cfg, 1, nb * spec.block_size, spec, paged=paged,
                           num_pages=nb + 1)
    if paged:
        cache["page_table"] = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, t, c: api.prefill(cfg, p, t, c, spec=spec),
                   donate_argnums=(2,))
    logits = None
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        for off in range(0, len(prompt), CHUNK):
            logits, _, cache = step(
                params, jnp.asarray(prompt[None, off: off + CHUNK]), cache)
    return np.asarray(logits)


def peak_bytes(devs) -> str:
    stats = [d.memory_stats() or {} for d in devs]
    return ", ".join(f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                     for s in stats) + " GiB"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(cfg, spec, dcfg, seed: int) -> None:
    """Each kernel of the serving path against its ref.py oracle at the
    served shapes: the batch, the verify tree plus its root token, the
    prefill chunk, the model's heads and the cache's blocks."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    b, tp = BATCH, CHUNK
    t = dcfg.tree_size + 1
    h, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    bs = spec.block_size
    nb = MAX_LEN // bs
    npages = b * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 8)

    def normal(k, shape, dtype=jnp.bfloat16):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)

    q = normal(ks[0], (b, t, h, dh))
    qp = normal(ks[1], (b, tp, h, dh))
    # two layers of the stored lane-dense pool; the kernels read layer 1
    pool_k = normal(ks[2], (2, npages, bs, hk * dh))
    pool_v = normal(ks[3], (2, npages, bs, hk * dh))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(np.arange(1, npages))
                        .reshape(b, nb).astype(np.int32))
    length = jnp.asarray([*PROMPT_LENS, 37], jnp.int32)
    ns = spec.partial_budget_tokens // bs
    sel = jnp.asarray(rng.integers(1, npages, (b, hk, ns)), jnp.int32)
    sel_len = jnp.asarray(rng.integers(0, bs + 1, (b, hk, ns)), jnp.int32)
    sel_len = sel_len.at[:, :, 0].set(bs)        # every head reads a block
    pf_len = jnp.asarray([0, SHARED, nb * bs - tp, tp], jnp.int32)
    pf_valid = jnp.asarray([tp, tp, tp * 3 // 5, tp], jnp.int32)

    def attn(parts):
        m, l, acc = (np.asarray(x, np.float32) for x in parts)
        return m, acc / l[..., None]

    def both(fn, *args):
        got = jax.block_until_ready(fn(*args))
        with jax.default_matmul_precision("highest"):
            want = fn(*args, use_pallas=False)
        return got, want

    got, want = both(ops.paged_verify_attention, q, pool_k, pool_v, 1, table,
                     length)
    for name, g, w in zip(("max", "out"), attn(got), attn(want)):
        close(f"paged_verify_attention {name}", g, w)
    got, want = both(ops.routed_partial_attention, q, pool_k, pool_v, 1, sel,
                     sel_len)
    for name, g, w in zip(("max", "out"), attn(got), attn(want)):
        close(f"routed_partial_attention {name}", g, w)
    got, want = both(ops.paged_prefill_attention, qp, pool_k, pool_v, 1,
                     table, pf_len, pf_valid)
    valid = np.arange(tp)[None, :] < np.asarray(pf_valid)[:, None]
    close("paged_prefill_attention", np.asarray(got, np.float32)[valid],
          np.asarray(want, np.float32)[valid])
    k_ctx = normal(ks[4], (b, MAX_LEN, hk, dh))
    got, want = both(ops.block_summaries, k_ctx, length)
    for name, g, w in zip(("kmax", "kmin"), got, want):
        close(f"block_summaries {name}", g, w)
    qw = (jnp.arange(t)[None] < jnp.asarray([t, t // 2, 1, t])[:, None]
          ).astype(jnp.float32)
    got, want = both(ops.retrieval_scores, q, want[0], want[1], qw)
    close("retrieval_scores", got, want)


def phase_logits(cfg, spec, params, prompt) -> None:
    got = prefill_logits(cfg, spec, params, prompt, paged=True)
    want = prefill_logits(cfg, spec.replace(use_pallas=False), params,
                          prompt, paged=False)
    check(got.shape == (1, cfg.vocab_size), f"logits shape {got.shape}")
    close("prefill logits, kernel vs XLA route", got, want, LOGIT_TOL)


def run_one_chip(seed: int, devs, clock: CompileClock) -> None:
    from repro.configs import get_config
    cfg, spec, dcfg = model_configs(LAYERS)
    log(f"model: {ARCH} at published widths (d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim {cfg.head_dim_}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}); depth cut "
        f"{get_config(ARCH).num_layers} -> {cfg.num_layers} layers; random "
        f"weights from seed {seed}")
    t0 = time.perf_counter()
    log("phase kernels: Pallas vs kernels/ref.py at real widths")
    phase_kernels(cfg, spec, dcfg, seed)
    log(f"  ok ({time.perf_counter() - t0:.1f} s wall, "
        f"{clock.take():.1f} s compiling)")
    t0 = time.perf_counter()
    log("phase logits: prefill through the kernel route vs the XLA route")
    params, dparams = build_params(cfg, dcfg, seed)
    waves = traffic(cfg.vocab_size, seed)
    phase_logits(cfg, spec, params, waves[0][1].prompt)
    log(f"  ok ({time.perf_counter() - t0:.1f} s wall, "
        f"{clock.take():.1f} s compiling, weight init included)")
    log(f"phase serve: ServingEngine, batch {BATCH}, max_len {MAX_LEN}, "
        f"prefill chunk = budget = {CHUNK}, {MAX_NEW} new tokens each")
    srv, outs = serve(cfg, spec, dcfg, params, dparams, waves)
    log(f"  {clock.take():.1f} s compiling")
    check_served(srv, outs, waves, cfg.vocab_size)
    check_kernel_route(srv)
    log(f"  peak HBM in use: {peak_bytes(devs[:1])}")


def run_four_chips(seed: int, devs, clock: CompileClock) -> None:
    from repro.launch.mesh import make_mesh
    mesh_shape = (1, 4)
    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devs[:4])
    cfg, spec, dcfg = model_configs(LAYERS)
    waves = traffic(cfg.vocab_size, seed)
    short = [[waves[0][1]]]
    rid, prompt = short[0][0].request_id, short[0][0].prompt
    log(f"phase sharded-vs-unsharded: {ARCH} {LAYERS} layers, "
        f"mesh_shape {mesh_shape} vs device 0")
    res = {}
    for name, m, shape in (("unsharded", None, None),
                           ("sharded", mesh, mesh_shape)):
        params, dparams = build_params(cfg, dcfg, seed, m)
        logits = prefill_logits(cfg, spec, params, prompt, paged=True,
                                mesh=m)
        srv, outs = serve(cfg, spec, dcfg, params, dparams, short, shape)
        toks = np.asarray(outs[rid].tokens)
        res[name] = (logits, toks)
        log(f"  {name}: tokens {toks[:12].tolist()}, {clock.take():.1f} s "
            f"compiling, peak HBM per device {peak_bytes(devs[:4])}")
        del srv, params, dparams
        gc.collect()
    got, want = res["sharded"][0][0], res["unsharded"][0][0]
    close("first-step logits, sharded vs unsharded", got, want, LOGIT_TOL)
    # greedy tokens: a flip is a bug only where the reference's top-2
    # margin exceeds what the two reduction orders can move a logit;
    # with random weights later near-ties are common, so the served
    # streams' agreement is reported, not required
    top2 = np.sort(want)[-2:]
    margin, err = float(top2[1] - top2[0]), float(np.abs(got - want).max())
    log(f"  first-step top-2 margin {margin:.4f}, max logit diff {err:.4f}")
    if margin > 2 * err:
        check(np.argmax(got) == np.argmax(want),
              "sharded and unsharded first greedy token differ")
        log("  first greedy token agrees")
    a, b = res["sharded"][1], res["unsharded"][1]
    agree = int(np.argmax(a != b)) if (a != b).any() else len(a)
    log(f"  served greedy tokens agree for the first {agree}/{len(a)} steps")
    cfg36, spec, dcfg = model_configs(36)
    log(f"phase 36 layers on mesh_shape {mesh_shape}: weights created "
        f"in their shardings")
    params, dparams = build_params(cfg36, dcfg, seed, mesh)
    log("  HBM in use per device after init: " + ", ".join(
        f"{(d.memory_stats() or {}).get('bytes_in_use', 0) / 2**30:.2f}"
        for d in devs[:4]) + " GiB")
    srv, outs = serve(cfg36, spec, dcfg, params, dparams, waves, mesh_shape)
    log(f"  {clock.take():.1f} s compiling, weight init included")
    check_served(srv, outs, waves, cfg36.vocab_size)
    log(f"  peak HBM per device: {peak_bytes(devs[:4])}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-engine path on a 2x2 host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    clock = CompileClock()
    from repro.utils.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devs[0].device_kind} x {len(devs)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, devs, clock)
    else:
        run_one_chip(args.seed, devs, clock)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
