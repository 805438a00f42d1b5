"""End-to-end training driver: train the ~100M-parameter dense target
(``target-100m``: 12L, d=768, 12H, vocab 8K) on the synthetic corpus for a
few hundred steps with AdamW + cosine schedule + grad clipping +
checkpointing.

Run:  PYTHONPATH=src python examples/train_target.py --steps 300
(CPU: ~1-2 s/step at batch 4 x 256.)
"""
import argparse

from repro.configs import get_config
from repro.data import SyntheticCorpus, batch_iterator
from repro.train.trainer import Trainer, TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="target-100m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--ckpt", default="results/artifacts/target100m.npz")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, order=1,
                             branching=4, seed=0)
    tr = Trainer(cfg, TrainConfig(total_steps=args.steps, warmup=20,
                                  log_every=10, ckpt_path=args.ckpt,
                                  ckpt_every=100))
    res = tr.fit(batch_iterator(corpus, batch=args.batch,
                                seq_len=args.seq_len), steps=args.steps)
    print(f"final loss: {res['final_loss']:.4f} "
          f"(checkpoint -> {args.ckpt})")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
