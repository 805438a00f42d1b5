"""Tab. 2: similarity between SpecPV and full-verification generation
under different retrieval budgets (token-level ROUGE-L + exact agreement;
the full-verification output is the reference, exactly as in the paper).
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
    ctx = 256 if quick else 512
    max_new = 32 if quick else 64
    nprompts = 2 if quick else 4
    budgets = [2, 6] if quick else [2, 6, 14]
    base = SpecPVConfig(block_size=16, num_sink_blocks=1,
                        local_window_blocks=2, buffer_size=48)

    refs = []
    prompts = []
    for i in range(nprompts):
        prompt, _ = continuation_task(corpus, batch=1, context_len=ctx,
                                      seed=31 + i)
        prompts.append(prompt)
        ref = autoregressive_generate(cfg, params, prompt, max_new,
                                      max_len=ctx + max_new + 160)
        refs.append(ref[0])

    rows = [["full-verify", "-", "1.000", "1.000"]]
    for ret in budgets:
        spec = base.replace(retrieval_budget_blocks=ret)
        rl, agree = [], []
        for prompt, ref in zip(prompts, refs):
            eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=1,
                               max_len=ctx + max_new + 160,
                               partial_verification=True)
            toks, _ = eng.generate(prompt, max_new)
            rl.append(rouge_l(toks[0], ref))
            agree.append(float((toks[0] == ref).mean()))
        rows.append([f"budget={16*(ret+3)}tok", ret,
                     f"{np.mean(rl):.3f}", f"{np.mean(agree):.3f}"])
    header = ["method", "ret_blocks", "rougeL_vs_full", "exact_agree"]
    print_table("Tab.2 — SpecPV vs full-verification similarity", header,
                rows)
    write_rows(os.path.join(RESULTS_DIR, "table2_quality.csv"), header,
               rows)
    for r in rows:
        print(f"table2/{r[0]},0.0,rougeL={r[2]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main("--quick" in sys.argv)
