"""Tab. 1/3: decoding speedup alpha and accept length tau across context
lengths and partial-KV budgets, vs the autoregressive baseline and vs
full-verification self-speculation (EAGLE3-YARN analogue).

On CPU the wall-clock alpha is measured on the same device as the AR
baseline (and we additionally report the device-independent
target-forward-pass reduction).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, write_rows  # noqa

from repro.artifacts import get_trained_pair, corpus_for  # noqa
from repro.configs import SpecPVConfig  # noqa
from repro.core import SpecPVEngine, autoregressive_generate  # noqa
from repro.data import continuation_task  # noqa


def run_method(cfg, dcfg, params, dparams, spec, prompt, max_new, *,
               partial):
    eng = SpecPVEngine(cfg, spec, dcfg, params, dparams,
                       batch=prompt.shape[0],
                       max_len=prompt.shape[1] + max_new + 160,
                       partial_verification=partial)
    t0 = time.time()
    toks, stats = eng.generate(prompt, max_new)
    dt = time.time() - t0
    return toks, stats, dt


def main(quick: bool = False):
    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    contexts = [192, 384] if quick else [192, 384, 768]
    budgets = {"SpecPV-64": 2, "SpecPV-128": 6} if quick else \
        {"SpecPV-64": 2, "SpecPV-128": 6, "SpecPV-256": 14}
    max_new = 32 if quick else 64
    rows = []
    for ctx in contexts:
        prompt, _ = continuation_task(corpus, batch=1, context_len=ctx)
        t0 = time.time()
        ar = autoregressive_generate(cfg, params, prompt, max_new,
                                     max_len=ctx + max_new + 160)
        t_ar = time.time() - t0

        base_spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                                 retrieval_budget_blocks=4,
                                 local_window_blocks=2, buffer_size=48)
        toks, stats, dt = run_method(cfg, dcfg, params, dparams, base_spec,
                                     prompt, max_new, partial=False)
        rows.append([ctx, "EAGLE3-full", f"{t_ar/dt:.2f}x",
                     f"{max_new/stats['steps']:.2f}x",
                     f"{stats['mean_accept']:.2f}",
                     "lossless" if np.array_equal(toks, ar) else "DIVERGED"])
        for name, ret in budgets.items():
            spec = base_spec.replace(retrieval_budget_blocks=ret)
            toks, stats, dt = run_method(cfg, dcfg, params, dparams, spec,
                                         prompt, max_new, partial=True)
            agree = float((toks == ar).mean())
            rows.append([ctx, name, f"{t_ar/dt:.2f}x",
                         f"{max_new/stats['steps']:.2f}x",
                         f"{stats['mean_accept']:.2f}",
                         f"agree={agree:.3f}"])
    header = ["context", "method", "alpha_wall", "fwd_reduction", "tau",
              "vs_AR"]
    print_table("Tab.1 — speedup & accept length", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "table1_speedup.csv"), header,
               rows)
    for r in rows:
        print(f"table1/{r[0]}/{r[1]},{0.0},alpha={r[2]};tau={r[4]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main("--quick" in sys.argv)
