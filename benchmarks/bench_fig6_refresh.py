"""Fig. 6: refresh-interval sweep — larger buffers (rarer full
verification) trade similarity for speed — plus the modelled refresh
HBM traffic of the two rebuild contracts (gathered copy vs zero-copy
page routing), derived from ``SpecPVConfig`` through the same billing
functions the engine's ``TrafficMeter`` uses (no magic constants).

Both refresh styles score the per-block kmax/kmin summaries to pick
the top-k blocks; that read is common, so it is reported as a context
column.  The *rebuild* differs: a gathered refresh copies the selected
blocks' bytes into the dense partial buffer
(``kvcache.offload.partial_step_bytes``), a routed refresh writes the
selected block indices and resets the small tail buffer
(``kvcache.offload.routed_refresh_bytes`` minus the common summaries
term).  The headline is the rebuild-only ratio at paper scale (8B-class
trunk at 60K context, bf16 KV, the default ``SpecPVConfig`` budget) —
the acceptance bar is >= 10x — with the bench-dims ratio reported
alongside each measured sweep row.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, rouge_l, write_rows  # noqa

from repro.artifacts import get_trained_pair, corpus_for  # noqa
from repro.configs import SpecPVConfig  # noqa
from repro.core import SpecPVEngine, autoregressive_generate  # noqa
from repro.data import continuation_task  # noqa
from repro.kvcache.offload import (  # noqa
    partial_step_bytes, routed_refresh_bytes)


def refresh_rebuild_model(spec, *, num_layers, hk, dh, itemsize, ctx_len):
    """Modelled per-refresh rebuild HBM bytes for one row, both
    contracts, every term derived from ``spec``: gathered copies
    ``spec.partial_budget_tokens`` of K+V; routed writes
    ``partial_budget_tokens // block_size`` block indices and resets
    the ``spec.buffer_size``-token tail.  The summary read (common to
    both — it is how either refresh *selects*) is isolated by zeroing
    the routed-only terms in ``routed_refresh_bytes``."""
    nb = -(-ctx_len // spec.block_size)
    ns = spec.partial_budget_tokens // spec.block_size
    gathered = partial_step_bytes(num_layers, 1, spec.partial_budget_tokens,
                                  hk, dh, itemsize)
    routed_total = routed_refresh_bytes(num_layers, 1, nb, ns,
                                        spec.buffer_size, hk, dh, itemsize)
    summaries = routed_refresh_bytes(num_layers, 1, nb, 0, 0,
                                     hk, dh, itemsize)
    routed = routed_total - summaries
    return dict(gathered_rebuild=gathered, routed_rebuild=routed,
                summaries_read=summaries,
                ratio=gathered / max(routed, 1))


def main(quick: bool = False):
    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    ctx, max_new = (256, 32) if quick else (512, 64)
    prompt, _ = continuation_task(corpus, batch=1, context_len=ctx, seed=55)
    ref = autoregressive_generate(cfg, params, prompt, max_new,
                                  max_len=ctx + max_new + 256)
    dh = cfg.head_dim or cfg.d_model // cfg.num_heads
    itemsize = np.dtype(cfg.dtype).itemsize
    buffers = [16, 48] if quick else [16, 32, 64, 128]
    rows = []
    for buf in buffers:
        spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                            retrieval_budget_blocks=4,
                            local_window_blocks=2, buffer_size=buf)
        eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=1,
                           max_len=ctx + max_new + 256,
                           partial_verification=True)
        t0 = time.time()
        toks, stats = eng.generate(prompt, max_new)
        dt = time.time() - t0
        rl = rouge_l(toks[0], ref[0])
        n_refresh = stats["modes"].get("refresh", 0)
        m = refresh_rebuild_model(spec, num_layers=cfg.num_layers,
                                  hk=cfg.num_kv_heads, dh=dh,
                                  itemsize=itemsize, ctx_len=ctx)
        rows.append([buf, n_refresh, f"{rl:.3f}",
                     f"{stats['mean_accept']:.2f}", f"{dt:.1f}",
                     f"{m['ratio']:.1f}"])
    header = ["buffer_size", "refresh_steps", "rougeL_vs_full", "tau",
              "wall_s", "rebuild_bytes_ratio"]
    print_table("Fig.6 — refresh interval sweep", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "fig6_refresh.csv"), header, rows)
    for r in rows:
        print(f"fig6/buf{r[0]},0.0,rougeL={r[2]};refreshes={r[1]}")

    # modelled refresh rebuild traffic at paper scale: 8B-class trunk
    # (32 layers, 8 KV heads, head dim 128), bf16 KV, 60K context, the
    # default SpecPVConfig retrieval budget.  The gathered rebuild moves
    # the whole selected body; the routed rebuild is index writes + the
    # tail-buffer reset.  >= 10x is the zero-copy acceptance bar.
    paper_spec = SpecPVConfig()
    paper = refresh_rebuild_model(paper_spec, num_layers=32, hk=8, dh=128,
                                  itemsize=2, ctx_len=60_000)
    bench = refresh_rebuild_model(
        SpecPVConfig(block_size=16, num_sink_blocks=1,
                     retrieval_budget_blocks=4, local_window_blocks=2,
                     buffer_size=48),
        num_layers=cfg.num_layers, hk=cfg.num_kv_heads, dh=dh,
        itemsize=itemsize, ctx_len=ctx)
    assert paper["ratio"] >= 10.0, paper
    print(f"modelled refresh rebuild HBM bytes (paper scale, "
          f"{paper_spec.partial_budget_tokens}-token budget at 60K ctx): "
          f"gathered {paper['gathered_rebuild'] / 2**20:.1f} MiB vs "
          f"routed {paper['routed_rebuild'] / 2**20:.2f} MiB -> "
          f"{paper['ratio']:.1f}x smaller "
          f"(summaries read, common to both: "
          f"{paper['summaries_read'] / 2**20:.1f} MiB; "
          f"bench dims: {bench['ratio']:.1f}x)")
    hdr = ["scale", "gathered_rebuild_bytes", "routed_rebuild_bytes",
           "summaries_read_bytes", "rebuild_ratio"]
    write_rows(os.path.join(RESULTS_DIR, "fig6_refresh_traffic.csv"), hdr,
               [["paper-60k", paper["gathered_rebuild"],
                 paper["routed_rebuild"], paper["summaries_read"],
                 f"{paper['ratio']:.2f}"],
                ["bench", bench["gathered_rebuild"],
                 bench["routed_rebuild"], bench["summaries_read"],
                 f"{bench['ratio']:.2f}"]])


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main("--quick" in sys.argv)
