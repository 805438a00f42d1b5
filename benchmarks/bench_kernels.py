"""Kernel micro-bench: Pallas (interpret) vs pure-jnp reference wall time
and agreement at representative SpecPV shapes.  On TPU the same harness
times the compiled kernels; in this container it validates numerics and
reports interpret-mode timings (not meaningful as absolute perf).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, time_fn, write_rows  # noqa

from repro.kernels import ops, ref  # noqa


def main(quick: bool = False):
    b, s, hk, dh, bs_, h, t = 1, 1024, 2, 64, 128, 8, 8
    if quick:
        s = 512
    k = jax.random.normal(jax.random.PRNGKey(0), (b, s, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, dh))
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, dh))
    length = jnp.full((b,), s, jnp.int32)
    qw = jnp.ones((b, t))
    nsel = 4
    idx = jax.random.randint(jax.random.PRNGKey(3), (b, hk, nsel), 0,
                             s // bs_)
    vlen = jnp.full((b, hk, nsel), bs_, jnp.int32)

    rows = []
    for name, pall, refc in [
        ("block_summary",
         lambda: ops.block_summaries(k, length, bs_),
         lambda: ops.block_summaries(k, length, bs_, use_pallas=False)),
        ("retrieval_score",
         lambda: ops.retrieval_scores(
             q, *ops.block_summaries(k, length, bs_, use_pallas=False), qw),
         lambda: ops.retrieval_scores(
             q, *ops.block_summaries(k, length, bs_, use_pallas=False), qw,
             use_pallas=False)),
        ("sparse_verify_attn",
         lambda: ops.sparse_verify_attention(q, k, v, idx, vlen, bs_),
         lambda: ops.sparse_verify_attention(q, k, v, idx, vlen, bs_,
                                             use_pallas=False)),
    ]:
        tp = time_fn(pall, iters=2)
        tr = time_fn(refc, iters=2)
        a = jax.tree_util.tree_leaves(pall())
        r = jax.tree_util.tree_leaves(refc())
        err = max(float(jnp.abs(x - y).max()) for x, y in zip(a, r))
        rows.append([name, f"{tp*1e6:.0f}", f"{tr*1e6:.0f}",
                     f"{err:.2e}"])
        print(f"kernel/{name},{tp*1e6:.0f},ref_us={tr*1e6:.0f};err={err:.1e}")
    header = ["kernel", "pallas_interp_us", "ref_us", "max_abs_err"]
    print_table("Kernels (interpret-mode validation)", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "kernels.csv"), header, rows)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
