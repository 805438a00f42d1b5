"""Quickstart: train a tiny target + EAGLE-3-style draft on the synthetic
long-document corpus, then compare

  1. plain autoregressive decoding,
  2. self-speculative decoding with FULL verification (lossless),
  3. SpecPV: partial verification + periodic refresh (the paper),

reporting accept length tau, tokens/step, target-forward-pass reduction
(the CPU-measurable analogue of the paper's alpha) and cache-traffic
bytes (the offload-analogue of Fig. 4).

Run:  PYTHONPATH=src python examples/quickstart.py [--context 192]
"""
import argparse
import time

import numpy as np

from repro.artifacts import get_trained_pair, corpus_for
from repro.configs import SpecPVConfig
from repro.core import SpecPVEngine, autoregressive_generate
from repro.data import continuation_task


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--context", type=int, default=192)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--target-steps", type=int, default=200)
    ap.add_argument("--draft-steps", type=int, default=150)
    args = ap.parse_args()

    cfg, dcfg, params, dparams = get_trained_pair(
        "tiny-dense", target_steps=args.target_steps,
        draft_steps=args.draft_steps)
    corpus = corpus_for(cfg)
    spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                        retrieval_budget_blocks=4, local_window_blocks=2,
                        buffer_size=48)
    prompt, _ = continuation_task(corpus, batch=args.batch,
                                  context_len=args.context)
    max_len = args.context + args.max_new + 128

    t0 = time.time()
    ar = autoregressive_generate(cfg, params, prompt, args.max_new,
                                 max_len=max_len, spec=spec)
    t_ar = time.time() - t0
    print(f"\n[AR      ] {args.max_new} tokens in {t_ar:.1f}s "
          f"({args.max_new} target forwards)")

    for name, partial in [("SpecPV-full", False), ("SpecPV-part", True)]:
        eng = SpecPVEngine(cfg, spec, dcfg, params, dparams,
                           batch=args.batch, max_len=max_len,
                           partial_verification=partial)
        t0 = time.time()
        toks, stats = eng.generate(prompt, args.max_new)
        dt = time.time() - t0
        lossless = np.array_equal(toks, ar)
        agree = float((toks == ar).mean())
        print(f"[{name}] {args.max_new} tokens in {dt:.1f}s | "
              f"steps={stats['steps']} "
              f"(forward-pass reduction {args.max_new / stats['steps']:.2f}x)"
              f" | tau={stats['mean_accept']:.2f} "
              f"tokens/step={stats['tokens_per_step']:.2f} | "
              f"modes={stats['modes']} | "
              + (f"LOSSLESS vs AR" if lossless
                 else f"agreement vs AR: {agree:.3f}"))
        if partial:
            tm = eng.traffic
            print(f"           cache traffic by mode: "
                  f"{ {k: f'{v/2**20:.1f}MiB' for k, v in tm.bytes_by_mode.items()} }")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
