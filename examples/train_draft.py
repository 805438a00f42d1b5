"""Draft-module training with the EAGLE-3 training-time-test loss
(paper eq. (5)) + YARN long-context adaptation (paper App. A, Fig. 8).

Trains two drafts on a trained tiny target: one at base context, one with
YARN scaling for longer contexts, and prints the TTT loss curves (the
CPU-scale analogue of Fig. 8).

Run:  PYTHONPATH=src python examples/train_draft.py --steps 150
"""
import argparse

import numpy as np

from repro.artifacts import get_trained_pair, corpus_for
from repro.configs import DraftConfig
from repro.data import batch_iterator
from repro.train.draft_train import DraftTrainer, DraftTrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--seq-len", type=int, default=192)
    ap.add_argument("--yarn", type=float, default=4.0,
                    help="YARN scaling factor for the long-context draft")
    args = ap.parse_args()

    cfg, dcfg, params, _ = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)

    print("== base-context draft (TTT loss, eq. 5) ==")
    base = DraftTrainer(cfg, dcfg, params,
                        DraftTrainConfig(total_steps=args.steps, warmup=10,
                                         log_every=25))
    rb = base.fit(batch_iterator(corpus, batch=8, seq_len=args.seq_len,
                                 seed=11), steps=args.steps)

    print(f"\n== YARN x{args.yarn} long-context draft (App. A) ==")
    cfg_yarn = cfg.replace(yarn_factor=args.yarn,
                           yarn_orig_len=args.seq_len)
    yarn = DraftTrainer(cfg_yarn, dcfg, params,
                        DraftTrainConfig(total_steps=args.steps, warmup=10,
                                         log_every=25))
    ry = yarn.fit(batch_iterator(corpus, batch=8, seq_len=args.seq_len,
                                 seed=13), steps=args.steps)

    print("\nTTT loss curves (step, L_total, L0):")
    for tag, hist in [("base", rb["history"]), ("yarn", ry["history"])]:
        pts = [(h["step"], round(h["loss"], 3), round(h["ttt_loss_0"], 3))
               for h in hist]
        print(f"  {tag}: {pts}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
