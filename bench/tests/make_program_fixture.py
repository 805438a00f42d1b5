"""Record the chip trace ``test_program_metrics.py`` reads.

    python3 bench/tests/make_program_fixture.py   # on a machine with a TPU

The serving program's named Pallas kernels (``src/repro/kernels/ops.py``)
at Qwen3-8B head widths, small pools, in programs named as the engine's
are: ``jit_run`` (paged full/refresh verification, zero-copy partial
verification and the retrieval scores, as a fused decode step with a
refresh row holds them) and ``jit__prefill_chunk_fused`` (paged prefill
attention), plus the block summaries.  Three rounds under the harness's
``bench.traced`` span, each inside program spans (``sched.tick``,
``engine.step.wait``).  The ``.xplane.pb`` is written to
``bench/tests/data/program.xplane.pb``.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

B, T, TP, H, HK, DH, BS = 2, 61, 256, 32, 8, 128, 128
NB = 16                         # pages per row
NS = 8                          # selected blocks per partial row


def programs():
    """(decode step, prefill chunk, block summaries): jitted, with the
    engine's program names."""
    import jax
    from repro.kernels import ops

    def run(q, pool_k, pool_v, table, length, sel, sel_len, kmax, kmin,
            qw):
        full = ops.paged_verify_attention(q, pool_k, pool_v, 0, table,
                                          length)
        part = ops.routed_partial_attention(q, pool_k, pool_v, 0, sel,
                                            sel_len)
        return full, part, ops.retrieval_scores(q, kmax, kmin, qw)

    def _prefill_chunk_fused(q, pool_k, pool_v, table, length, t_valid):
        return ops.paged_prefill_attention(q, pool_k, pool_v, 0, table,
                                           length, t_valid)

    return (jax.jit(run), jax.jit(_prefill_chunk_fused),
            lambda k, n: ops.block_summaries(k, n, BS))


def operands():
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    np_ = B * NB + 1
    # one layer of the stored lane-dense pool stack [L, NP, block, Hk*Dh]
    pool = jnp.asarray(rng.standard_normal((np_, BS, HK, DH)), bf).reshape(
        1, np_, BS, HK * DH)
    q = jnp.asarray(rng.standard_normal((B, T, H, DH)), bf)
    table = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    length = jnp.asarray([NB * BS, NB * BS // 2], jnp.int32)
    sel = jnp.asarray(1 + rng.integers(0, B * NB, (B, HK, NS)), jnp.int32)
    sel_len = jnp.full((B, HK, NS), BS, jnp.int32)
    kmax = jnp.asarray(rng.standard_normal((B, NB, HK, DH)), jnp.float32)
    qw = jnp.ones((B, T), jnp.float32)
    decode = (q, pool, pool, table, length, sel, sel_len, kmax, -kmax, qw)
    prefill = (jnp.asarray(rng.standard_normal((B, TP, H, DH)), bf), pool,
               pool, table, jnp.asarray([0, BS], jnp.int32),
               jnp.asarray([TP, TP // 2], jnp.int32))
    keys = (jnp.asarray(rng.standard_normal((B, NB * BS, HK, DH)), bf),
            jnp.asarray([NB * BS, 3 * BS], jnp.int32))
    return decode, prefill, keys


def record(dst: Path) -> None:
    import jax
    from repro.utils.spans import span
    step, prefill, summaries = programs()
    decode_args, prefill_args, keys = operands()
    jax.block_until_ready((step(*decode_args), prefill(*prefill_args),
                           summaries(*keys)))
    stats = defaultdict(float)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with span(stats, "sched.tick"):
                out = prefill(*prefill_args)
                out = (out, step(*decode_args), summaries(*keys))
                with span(stats, "engine.step.wait"):
                    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{dst}: {dst.stat().st_size} bytes")


def main() -> None:
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    record(HERE / "data" / "program.xplane.pb")


if __name__ == "__main__":
    main()
