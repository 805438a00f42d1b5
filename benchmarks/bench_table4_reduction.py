"""Tab. 4: retrieval-score reduction strategy ablation (mean / max / last)
— similarity to full verification and accept length.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, rouge_l, write_rows  # noqa

from repro.artifacts import get_trained_pair, corpus_for  # noqa
from repro.configs import SpecPVConfig  # noqa
from repro.core import SpecPVEngine, autoregressive_generate  # noqa
from repro.data import continuation_task  # noqa


def main(quick: bool = False):
    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    ctx, max_new = (256, 32) if quick else (512, 48)
    prompt, _ = continuation_task(corpus, batch=2, context_len=ctx, seed=77)
    ref = autoregressive_generate(cfg, params, prompt, max_new,
                                  max_len=ctx + max_new + 160)
    rows = []
    for red in ["mean", "max", "last"]:
        spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                            retrieval_budget_blocks=4,
                            local_window_blocks=2, buffer_size=48,
                            reduction=red)
        eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=2,
                           max_len=ctx + max_new + 160,
                           partial_verification=True)
        toks, stats = eng.generate(prompt, max_new)
        rl = np.mean([rouge_l(toks[i], ref[i]) for i in range(2)])
        rows.append([red, f"{rl:.3f}", f"{stats['mean_accept']:.2f}"])
    header = ["reduction", "rougeL_vs_full", "tau"]
    print_table("Tab.4 — reduction strategies", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "table4_reduction.csv"), header,
               rows)
    for r in rows:
        print(f"table4/{r[0]},0.0,rougeL={r[1]};tau={r[2]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main("--quick" in sys.argv)
