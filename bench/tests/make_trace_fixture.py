"""Record the small chip trace ``test_trace.py`` reads.

    python3 bench/tests/make_trace_fixture.py   # on a machine with a TPU

Two programs run three times each under ``bench.tick`` host spans, with
host sleeps between them that leave the device idle: ``jit_matmul``
(one XLA fusion) and ``jit_attend`` (the block-attention Pallas kernel
of ``kernels/sparse_attention.py``).  The ``.xplane.pb`` is written to
``bench/tests/data/small.xplane.pb``.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.sparse_attention import block_attention_pallas
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    def matmul(x):
        return x @ x

    def attend(q, k, v, idx, vlen):
        return block_attention_pallas(q, k, v, 0, idx, vlen, interpret=False)

    mm = jax.jit(matmul)
    at = jax.jit(attend)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((61, 32, 128), jnp.bfloat16)
    kv = jnp.ones((1, 64, 128, 8 * 128), jnp.bfloat16)    # [L, NP, bs, Hk*Dh]
    idx = jnp.arange(64, dtype=jnp.int32).reshape(8, 8) % 64
    vlen = jnp.full((8, 8), 128, jnp.int32)
    jax.block_until_ready((mm(x), at(q, kv, kv, idx, vlen)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                jax.block_until_ready(mm(x))
                time.sleep(0.002)
                jax.block_until_ready(at(q, kv, kv, idx, vlen))
            time.sleep(0.003)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
    dst = HERE / "data" / "small.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{dst}: {dst.stat().st_size} bytes")


if __name__ == "__main__":
    main()
