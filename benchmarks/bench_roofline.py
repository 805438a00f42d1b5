"""§Roofline: three-term roofline per (arch x shape x mesh) from the
dry-run artifacts (results/dryrun/*.json).

  compute term    = FLOPs / (chips x 197e12)          [bf16 peak, v5e]
  memory term     = bytes  / (chips x 819e9)          [HBM]
  collective term = collective bytes / 50e9           [per-chip ICI link]

Caveat recorded in EXPERIMENTS.md: XLA's CPU cost-analysis counts each
while-loop (lax.scan) body ONCE, so `flops`/`bytes accessed` from the
compiled artifact undercount by the trip count (layers, KV chunks).  We
therefore derive the compute/memory terms from an analytic model of the
step (documented below, cross-checked against the HLO numbers and trip
counts) and report the raw HLO figures alongside.  Collective bytes are
parsed from post-SPMD HLO (per-device shard shapes) and corrected by the
scan trip count where the collective sits inside the layer loop.
"""
from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from common import RESULTS_DIR, print_table, write_rows  # noqa: E402

from repro.configs import get_config, INPUT_SHAPES  # noqa: E402
from repro.models.dense import (attn_layer_count,  # noqa: E402
                                superblock_decomp)

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / link

MESH_CHIPS = {"single": 256, "multipod": 512}


def _dtype_bytes(cfg) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def analytic_model(arch: str, shape: str, mesh: str) -> dict:
    """Per-STEP global FLOPs and HBM bytes for the lowered function."""
    cfg = get_config(arch)
    info = INPUT_SHAPES[shape]
    kind, seq, batch = info["kind"], info["seq_len"], info["global_batch"]
    n = cfg.param_count()
    na = cfg.active_param_count()
    l_attn = attn_layer_count(cfg.layer_kinds())
    hk, dh, h = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    dt = _dtype_bytes(cfg)
    kv_token_bytes = 2 * l_attn * hk * dh * dt

    if kind == "train":
        tokens = batch * seq
        # fwd + bwd = 3x matmul passes; remat adds ~1 more fwd
        flops = 6 * na * tokens * (4 / 3)
        # causal attention FLOPs (fwd 2 matmuls + bwd ~2.5x)
        flops += 3.5 * 2 * 2 * l_attn * h * dh * seq * seq / 2 * batch
        # params (bf16) + grads + adam moments traffic + activations r/w
        bytes_ = n * dt * 2 + n * 4 * 3 + tokens * cfg.d_model * dt * \
            cfg.num_layers * 6
        model_flops = 6 * na * tokens
    elif kind == "prefill":
        tokens = batch * seq
        flops = 2 * na * tokens
        flops += 2 * 2 * l_attn * h * dh * seq * seq / 2 * batch
        bytes_ = n * dt + tokens * kv_token_bytes + \
            tokens * cfg.d_model * dt * cfg.num_layers * 2
        model_flops = 2 * na * tokens
    else:  # decode (one token per sequence)
        tokens = batch
        flops = 2 * na * tokens
        if cfg.is_attention_arch:
            if shape == "long_500k":
                # SpecPV partial path: attention touches only the partial
                # cache (~4.6K tokens), not seq
                touched = 4480 + 96
            else:
                touched = seq
            flops += 2 * 2 * l_attn * h * dh * touched * batch
            bytes_ = n * dt + batch * touched * kv_token_bytes
        else:
            bytes_ = n * dt + batch * 4 * cfg.num_layers * cfg.d_model * 4
        model_flops = 2 * na * tokens
    return dict(flops=flops, bytes=bytes_, model_flops=model_flops,
                tokens=tokens)


def scan_trip_count(arch: str) -> int:
    cfg = get_config(arch)
    _, n_super, _ = superblock_decomp(cfg.layer_kinds())
    return n_super


def analytic_collectives(arch: str, shape: str, mesh: str) -> float:
    """Per-chip collective bytes per step from the sharding design:

    train:  FSDP param all-gather (fwd+bwd) + grad reduce-scatter over the
            data axes + per-layer TP all-reduce of activations
    prefill:per-layer TP all-reduce of activations
    decode: per-layer TP all-reduce ([B_loc, 1, d]) + context-parallel
            softmax psum over the seq-sharded KV
    long_500k adds the distributed retrieval gather of the partial cache.
    """
    cfg = get_config(arch)
    info = INPUT_SHAPES[shape]
    kind, seq, batch = info["kind"], info["seq_len"], info["global_batch"]
    chips = MESH_CHIPS[mesh]
    model = 16
    data = chips // model
    n = cfg.param_count()
    dt = _dtype_bytes(cfg)
    L = cfg.num_layers
    d = cfg.d_model
    l_attn = attn_layer_count(cfg.layer_kinds())
    hk, dh, h = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads

    if kind == "train":
        tokens_chip = batch * seq // chips
        ag = 2 * n * dt / model                 # fsdp gather, fwd+bwd
        rs = n * 4 / model                      # grad reduce-scatter (f32)
        tp = 4 * L * tokens_chip * d * dt       # 2 all-reduce / layer, bwd 2x
        return ag + rs + tp
    if kind == "prefill":
        tokens_chip = batch * seq // chips
        tp = 2 * L * tokens_chip * d * dt
        kv_write = 0.0                          # writes are shard-local
        return tp + kv_write
    # decode
    b_loc = max(batch // data, 1)
    tp = 2 * L * b_loc * d * dt
    # context-parallel softmax combine: (m, l, acc) per head per layer
    cp = l_attn * b_loc * h * (dh + 2) * 4
    if shape == "long_500k" and cfg.is_attention_arch:
        # retrieval gather of selected blocks across seq shards (amortised:
        # a refresh every ~20 steps re-materialises the 4.5K-token body)
        pbody = 4480
        cp += l_attn * b_loc * hk * pbody * dh * dt * 2 / 20
    return tp + cp


def analyse(results_dir=None):
    results_dir = results_dir or os.path.join(RESULTS_DIR, "dryrun")
    rows = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        r = json.load(open(path))
        if r.get("skipped"):
            rows.append([r["arch"], r["shape"], r["mesh"], "SKIP",
                         "-", "-", "-", "-", "-", r["reason"][:40]])
            continue
        if not r.get("ok"):
            rows.append([r["arch"], r["shape"], r["mesh"], "FAIL",
                         "-", "-", "-", "-", "-", r.get("error", "")[:40]])
            continue
        chips = MESH_CHIPS[r["mesh"]]
        am = analytic_model(r["arch"], r["shape"], r["mesh"])
        t_comp = am["flops"] / (chips * PEAK_FLOPS)
        t_mem = am["bytes"] / (chips * HBM_BW)
        coll_bytes = analytic_collectives(r["arch"], r["shape"], r["mesh"])
        t_coll = coll_bytes / LINK_BW
        coll = r["collectives"]
        parsed = sum(v for k, v in coll.items() if k != "counts")
        terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
        dom = max(terms, key=terms.get)
        ratio = am["model_flops"] / max(am["flops"], 1)
        mem_gib = r["memory"]["per_device_total"] / 2**30
        rows.append([
            r["arch"], r["shape"], r["mesh"], dom,
            f"{t_comp*1e3:.3f}", f"{t_mem*1e3:.3f}", f"{t_coll*1e3:.3f}",
            f"{ratio:.2f}", f"{mem_gib:.1f}",
            f"hlo_flops={r['flops']:.2e};hlo_coll={parsed:.2e}"])
    header = ["arch", "shape", "mesh", "bottleneck", "t_compute_ms",
              "t_memory_ms", "t_collective_ms", "useful_flops_ratio",
              "mem_GiB/chip", "notes"]
    return header, rows


def main():
    header, rows = analyse()
    print_table("Roofline (per step, per mesh)", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "roofline.csv"), header, rows)
    # benchmark-harness CSV contract: name,us_per_call,derived
    for r in rows:
        if r[3] not in ("SKIP", "FAIL"):
            dom_ms = max(float(r[4]), float(r[5]), float(r[6]))
            print(f"roofline/{r[0]}/{r[1]}/{r[2]},{dom_ms*1e3:.1f},"
                  f"bottleneck={r[3]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
