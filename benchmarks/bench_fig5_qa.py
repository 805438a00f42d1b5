"""Fig. 5 (proxy): task accuracy under shrinking partial-KV budgets.

The paper's QA benchmarks need instruction-tuned LLMs; the CPU-scale
analogue is continuation accuracy on the synthetic corpus — the fraction
of reference-continuation tokens exactly reproduced — which exercises the
same mechanism: how much task signal survives KV truncation.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, write_rows  # noqa

from repro.artifacts import get_trained_pair, corpus_for  # noqa
from repro.configs import SpecPVConfig  # noqa
from repro.core import SpecPVEngine, autoregressive_generate  # noqa
from repro.data import continuation_task  # noqa


def main(quick: bool = False):
    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    ctx, max_new = (256, 24) if quick else (512, 32)
    nprompts = 2 if quick else 4
    budgets = [1, 4] if quick else [1, 2, 4, 8]
    rows = []
    accs_ar = []
    data = []
    for i in range(nprompts):
        prompt, ref = continuation_task(corpus, batch=1, context_len=ctx,
                                        seed=91 + i)
        data.append((prompt, ref[:, :max_new]))
        ar = autoregressive_generate(cfg, params, prompt, max_new,
                                     max_len=ctx + max_new + 160)
        accs_ar.append(float((ar[0] == ref[0, :max_new]).mean()))
    rows.append(["full-verify", "-", f"{np.mean(accs_ar):.3f}"])
    for ret in budgets:
        spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                            retrieval_budget_blocks=ret,
                            local_window_blocks=2, buffer_size=48)
        accs = []
        for prompt, ref in data:
            eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=1,
                               max_len=ctx + max_new + 160,
                               partial_verification=True)
            toks, _ = eng.generate(prompt, max_new)
            accs.append(float((toks[0] == ref[0]).mean()))
        rows.append([f"budget={16*(ret+3)}tok", ret,
                     f"{np.mean(accs):.3f}"])
    header = ["method", "ret_blocks", "continuation_acc"]
    print_table("Fig.5 (proxy) — accuracy vs partial budget", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "fig5_qa.csv"), header, rows)
    for r in rows:
        print(f"fig5/{r[0]},0.0,acc={r[2]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main("--quick" in sys.argv)
