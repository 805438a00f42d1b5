"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers and compiles on the production meshes, and extract the
memory / FLOP / collective numbers the roofline analysis (EXPERIMENTS.md
§Roofline) reads.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results land in results/dryrun/<arch>__<shape>__<mesh>.json (idempotent:
existing results are skipped unless --force).
"""
# The dry-run needs 512 placeholder devices BEFORE jax initialises.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_XLA_EXTRA", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (get_config, ASSIGNED_ARCHS, INPUT_SHAPES,
                           SpecPVConfig)
from repro.configs.base import ModelConfig
from repro.models import api
from repro.models import common as cm
from repro.models.dense import attn_layer_count
from repro.distributed.sharding import (ShardingRules, param_shardings,
                                        cache_shardings, batch_spec,
                                        pkv_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as st
from repro.train.optimizer import adamw_init

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")

# long_500k requires sub-quadratic decode: dense/moe/vlm go through the
# SpecPV block-sparse partial path; ssm/hybrid decode natively.  whisper
# (enc-dec audio) has no 500K-token decode story -> skipped (DESIGN.md).
SKIPS = {("whisper-small", "long_500k"):
         "enc-dec audio decoder is bounded at 448 positions; no "
         "500K-token decode exists for this family (DESIGN.md)"}

from repro.launch.hlo_analysis import parse_collective_bytes, COLLECTIVES


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shard_tree(rules, tree_shapes, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), tree_shapes, shardings)


def _extra_specs(cfg: ModelConfig, batch: int, rules):
    mesh = rules.mesh
    bspec = batch_spec(rules, batch)
    bax = bspec[0] if len(bspec) else None
    out = {}
    if cfg.arch_type == "vlm":
        out["image_embeds"] = _sds(
            (batch, cfg.num_image_tokens, cfg.vision_dim), cm.dt(cfg.dtype),
            NamedSharding(mesh, P(bax, None, None)))
    if cfg.has_encoder:
        out["frame_embeds"] = _sds(
            (batch, cfg.num_audio_frames, cfg.d_model), cm.dt(cfg.dtype),
            NamedSharding(mesh, P(bax, None, None)))
    return out or None


def build_case(arch: str, shape_name: str, mesh, spec: SpecPVConfig):
    """Returns (fn, args, donate_argnums) ready for jit().lower(*args)."""
    cfg = get_config(arch)
    info = INPUT_SHAPES[shape_name]
    kind = info["kind"]
    seq, batch = info["seq_len"], info["global_batch"]
    rules = ShardingRules(mesh, fsdp=(kind == "train"))
    mesh_axes = tuple(mesh.axis_names)

    params_shape = jax.eval_shape(
        lambda k: api.init_params(cfg, k), jax.random.PRNGKey(0))
    pshard = param_shardings(rules, params_shape)
    pargs = _shard_tree(rules, params_shape, pshard)
    bspec = batch_spec(rules, batch)
    bax = bspec[0] if len(bspec) else None

    if kind == "train":
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        oshard = type(opt_shape)(
            step=NamedSharding(mesh, P()),
            mu=param_shardings(rules, opt_shape.mu),
            nu=param_shardings(rules, opt_shape.nu))
        oargs = _shard_tree(rules, opt_shape, oshard)
        tokens = _sds((batch, seq + 1), jnp.int32,
                      NamedSharding(mesh, P(bax)))
        extra = _extra_specs(cfg, batch, rules)
        fn = st.make_train_step(cfg, grad_shardings=pshard)
        return fn, (pargs, oargs, tokens, extra), (0, 1)

    # round the cache up so both the token dim (S) and the block dim (NB)
    # divide the axes they are sharded over
    seq_shards = (int(np.prod([mesh.shape[a] for a in mesh_axes]))
                  if (shape_name == "long_500k" and cfg.is_attention_arch)
                  else mesh.shape["model"])
    nb = -(-(seq + 2 * spec.block_size) // spec.block_size)
    nb = -(-nb // seq_shards) * seq_shards
    max_len = nb * spec.block_size
    cache_shape = jax.eval_shape(
        lambda: api.init_cache(cfg, batch, max_len, spec))
    cshard = cache_shardings(
        rules, cfg, cache_shape,
        shard_seq_over_all=(shape_name == "long_500k"
                            and cfg.is_attention_arch))
    cargs = {k: _sds(v.shape, v.dtype, cshard[k])
             for k, v in cache_shape.items()}

    if kind == "prefill":
        tokens = _sds((batch, seq), jnp.int32, NamedSharding(mesh, P(bax)))
        extra = _extra_specs(cfg, batch, rules)
        fn = st.make_prefill_step(cfg, spec)
        return fn, (pargs, cargs, tokens, extra), (1,)

    # decode
    token = _sds((batch,), jnp.int32, NamedSharding(mesh, P(bax)))
    partial = (shape_name == "long_500k") and cfg.is_attention_arch
    fn = st.make_decode_step(cfg, spec, partial=partial)
    if not partial:
        return fn, (pargs, cargs, token), (1,)

    l_attn = attn_layer_count(cfg.layer_kinds())
    p_slots = spec.partial_budget_tokens + spec.buffer_size
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    pkv_shapes = (jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots, dh),
                                       cm.dt(cfg.dtype)),) * 2 + (
        jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots), jnp.int32),)
    pksh = pkv_shardings(rules, pkv_shapes)
    pkv_args = tuple(_sds(s.shape, s.dtype, sh)
                     for s, sh in zip(pkv_shapes, pksh))
    buf_len = _sds((batch,), jnp.int32, NamedSharding(mesh, P()))
    return fn, (pargs, cargs, *pkv_args, buf_len, token), (1, 2, 3, 4)


def run_case(arch: str, shape_name: str, mesh_name: str,
             spec: Optional[SpecPVConfig] = None,
             spec_desc: str = "default") -> Dict:
    res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "spec": spec_desc, "ok": False}
    if (arch, shape_name) in SKIPS:
        res.update(skipped=True, reason=SKIPS[(arch, shape_name)])
        return res
    spec = spec or SpecPVConfig()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
        t0 = time.time()
        fn, args, donate = build_case(arch, shape_name, mesh, spec)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        res["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t0, 2)
        ma = compiled.memory_analysis()
        res["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            per_device_total=int(ma.argument_size_in_bytes
                                 + ma.output_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 - ma.alias_size_in_bytes))
        ca = compiled.cost_analysis() or {}
        res["flops"] = float(ca.get("flops", 0.0))
        res["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        txt = compiled.as_text()
        res["collectives"] = parse_collective_bytes(txt)
        res["ok"] = True
    except Exception as e:  # noqa: BLE001 — recorded, the sweep continues
        res["error"] = f"{type(e).__name__}: {e}"
        res["traceback"] = traceback.format_exc()[-2000:]
    return res


def result_path(arch, shape, mesh_name, spec_desc="default"):
    tag = "" if spec_desc == "default" else f"__{spec_desc}"
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}{tag}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = (["single", "multipod"] if args.mesh == "both"
              else [args.mesh])

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                path = result_path(arch, shape, mesh_name)
                if os.path.exists(path) and not args.force:
                    print(f"[skip-existing] {arch} {shape} {mesh_name}")
                    continue
                print(f"[dryrun] {arch} {shape} {mesh_name} ...",
                      flush=True)
                r = run_case(arch, shape, mesh_name)
                with open(path, "w") as f:
                    json.dump(r, f, indent=1)
                if r.get("skipped"):
                    n_skip += 1
                    print(f"  -> SKIP ({r['reason'][:60]}...)")
                elif r["ok"]:
                    n_ok += 1
                    mem = r["memory"]["per_device_total"] / 2**30
                    print(f"  -> OK lower={r['lower_s']}s "
                          f"compile={r['compile_s']}s "
                          f"mem/device={mem:.2f}GiB "
                          f"flops={r['flops']:.3g}")
                else:
                    n_fail += 1
                    print(f"  -> FAIL {r['error'][:200]}")
    print(f"done: ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
