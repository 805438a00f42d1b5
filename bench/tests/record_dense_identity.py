"""Record what the harness computes for dense GQA configurations, so
that ``test_dense_identity.py`` can hold the architecture module
(``bench/archs/dense_gqa.py``) to it.

    python3 bench/tests/record_dense_identity.py --root <checkout of 9f98656>

It reads the harness of the checkout at ``--root`` through the API that
harness had before the dense architecture left ``model.py``,
``weights.py`` and ``flops.py`` (commit 9f98656): ``model.dims`` and
``model.program_configs``, ``weights.shapes`` and ``make_weights``,
``flops.matmul_flops_per_token`` / ``verify_flops`` and the per-layer
``flops.attn_flops`` / ``attn_bytes``, and ``correct.served_logits``
with the reference ``refs/dense_gqa.py``.  On the CPU, for
``qwen3-8b-L12`` and ``qwen3-4b`` at their file widths and for the tiny
configuration of ``tiny.py``:

* the ``(target, draft)`` shape tree, flattened as ``(path, shape,
  fan_in)`` in ``jax.tree_util`` order (the order fixes the PRNG stream,
  so it fixes the weights);
* every field of the ``ModelConfig``, ``SpecPVConfig`` and
  ``DraftConfig`` the program is built from;
* the counts on a grid of ``(t_q, s_kv)`` (``GRID``): ``verify_flops``,
  one layer's ``attn_flops`` / ``attn_bytes`` and their sums over the
  layers, as the attention rooflines form them;
* tiny only: each weight leaf's float64 sum and the sha256 of its bytes
  at seed 0, and the sha256 of the reference's ``served_logits`` over
  one fixed request (``REQUEST``) that refreshes and verifies PARTIAL
  runs.

The JSON goes to ``--out`` (default ``data/dense_identity.json`` beside
this file).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIGS = ("qwen3-8b-L12", "qwen3-4b")
T_Q = (1, 60, 61, 157)
S_KV = (0, 4480, 12288, 22528)
GRID = [(t, s) for t in T_Q for s in S_KV]
WEIGHT_SEED = 0


def request() -> dict:
    """A 160-token prompt (10 blocks of the tiny 16) and 12 served
    tokens: the prefill's, then refresh, PARTIAL, refresh, PARTIAL, FULL,
    refresh, PARTIAL steps (mode id, tokens emitted)."""
    import numpy as np
    rng = np.random.default_rng(20261019)
    steps = [(1, 1), (2, 1), (2, 2), (1, 1), (2, 2), (2, 1), (0, 1),
             (1, 1), (2, 1)]
    return {"rid": "identity", "prompt": rng.integers(0, 256, 160).tolist(),
            "tokens": rng.integers(0, 256, 12).tolist(), "steps": steps}


def to_json(x):
    """Tuples as lists, all the way down."""
    if isinstance(x, dict):
        return {k: to_json(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [to_json(v) for v in x]
    return x


def flat_shapes(tree, is_leaf):
    import jax
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return [[jax.tree_util.keystr(p), list(shape), fan]
            for p, (shape, fan) in leaves]


def leaf_digests(tree):
    import jax
    import numpy as np
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for p, x in leaves:
        a = np.asarray(x)
        out.append([jax.tree_util.keystr(p),
                    float(a.astype(np.float64).sum()),
                    hashlib.sha256(np.ascontiguousarray(a).tobytes())
                    .hexdigest()])
    return out


def logits_digest(lg) -> str:
    import numpy as np
    return hashlib.sha256(np.ascontiguousarray(lg, np.float32).tobytes()
                          ).hexdigest()


def record_config(mdl, weights, flops, conf: dict) -> dict:
    dm = mdl.dims(conf)
    cfg, spec, dcfg = mdl.program_configs(conf)
    counts = []
    for t_q, s_kv in GRID:
        af, ab = flops.attn_flops(dm, t_q, s_kv), flops.attn_bytes(dm, t_q,
                                                                   s_kv)
        counts.append({"t_q": t_q, "s_kv": s_kv,
                       "verify_flops": flops.verify_flops(dm, t_q, s_kv),
                       "attn_flops": af, "attn_bytes": ab,
                       "layers_attn_flops": dm.layers * af,
                       "layers_attn_bytes": dm.layers * ab})
    return {
        "shapes": flat_shapes(weights.shapes(dm), weights._is_leaf),
        "configs": {"model": to_json(dataclasses.asdict(cfg)),
                    "specpv": to_json(dataclasses.asdict(spec)),
                    "draft": to_json(dataclasses.asdict(dcfg))},
        "sizes": {"layer_params": dm.layer_params(),
                  "kv_bytes_per_token": dm.kv_bytes_per_token(),
                  "matmul_flops_per_token": flops.matmul_flops_per_token(dm),
                  "matmul_flops_per_token_no_head":
                      flops.matmul_flops_per_token(dm, lm_head=False)},
        "counts": counts,
    }


def record(root: Path) -> dict:
    bench = root / "bench"
    sys.path[:0] = [str(bench), str(bench / "tests"), str(root / "src")]
    import correct as cor
    import flops
    import model as mdl
    import tiny
    import weights
    out = {"grid": {"t_q": list(T_Q), "s_kv": list(S_KV)}, "configs": {}}
    for name in CONFIGS:
        out["configs"][name] = record_config(mdl, weights, flops,
                                             mdl.load_config(name, bench))
    with tempfile.TemporaryDirectory() as tmp:
        conf = mdl.load_config("tiny", tiny.make_root(Path(tmp)) / "bench")
    rec = record_config(mdl, weights, flops, conf)
    dm = mdl.dims(conf)
    params, dparams = weights.make_weights(dm, WEIGHT_SEED)
    rec["weights"] = {"seed": WEIGHT_SEED,
                      "leaves": leaf_digests((params, dparams))}
    req = request()
    lg = cor.served_logits(cor.reference(conf["reference"]), dm, params,
                           conf["specpv"], req)
    rec["served_logits"] = {"request": req, "shape": list(lg.shape),
                            "sha256": logits_digest(lg)}
    out["configs"]["tiny"] = rec
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=Path, required=True,
                    help="checkout whose harness is recorded")
    ap.add_argument("--out", type=Path,
                    default=HERE / "data" / "dense_identity.json")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    out = record(args.root.resolve())
    args.out.write_text(json.dumps(out, indent=1) + "\n")
    print(f"{args.out}: {args.out.stat().st_size} bytes")


if __name__ == "__main__":
    main()
