"""§Perf hillclimbing cases: lower + compile optimization variants of the
three chosen (arch x shape) pairs and extract their roofline inputs
(EXPERIMENTS.md §Perf records the hypothesis -> change -> before/after).

  A. granite-3-2b, 32K SpecPV verify step   (paper-representative pair)
     A0 full-verification tree step (the EAGLE-3 baseline)
     A1 partial verification (the paper)
     A2 partial verification + int8 partial cache (beyond paper)
  B. qwen1.5-32b, decode_32k                (worst memory-per-chip pair)
     B0 baseline bf16 KV (from the main dry-run)
     B1 int8 KV cache + tile-local dequant (beyond paper)
  C. deepseek-7b, long_500k                 (most collective-bound pair)
     C0 baseline partial decode (from the main dry-run)
     C1 int8 partial cache (halves refresh-gather + buffer traffic)
     C2 refresh interval 20 -> 40 (config; analytic + quality-checked)

Run:  PYTHONPATH=src python -m repro.launch.hillclimb [--case A1]
Writes results/hillclimb/<case>.json.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_XLA_EXTRA", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, INPUT_SHAPES, SpecPVConfig
from repro.core import tree as tr
from repro.core import verify as vf
from repro.models import api
from repro.models import common as cm
from repro.models.dense import attn_layer_count
from repro.distributed.sharding import (ShardingRules, param_shardings,
                                        cache_shardings, batch_spec,
                                        pkv_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import parse_collective_bytes
from repro.launch.dryrun import _sds, _shard_tree

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "hillclimb")

TREE = tr.TreeSpec.from_branch((4, 2, 2, 1, 1))   # 60 nodes, EAGLE-scale


# ---------------------------------------------------------------------------
# verify steps (family A)
# ---------------------------------------------------------------------------

def make_verify_step(cfg, spec, tree, *, partial: bool, int8: bool = False):
    """One SpecPV verification step: tree forward + greedy acceptance +
    commit (full cache or partial buffer)."""

    def common_part(params, cache, pending, tree_tokens, pkv=None):
        b = pending.shape[0]
        plen = jnp.ones((b,), jnp.int32)
        seq_len = cache["length"] + 1
        vin = vf.build_verify_inputs(tree, pending[:, None], plen,
                                     tree_tokens, seq_len)
        out = api.decode(cfg, params, vin["tokens"], vin["positions"],
                         cache, mode=("partial" if partial else "full"),
                         self_mask=vin["self_mask"], pkv=pkv, spec=spec)
        path, acc, bonus, _ = tr.greedy_tree_accept(
            tree, tree_tokens, out.logits, vin["root_slot"],
            vin["node_slots"])
        slots, valid = vf.commit_slots(tree, vin["pend_valid"], path, 1)
        ck, cv = vf.gather_new_kv(out.new_kv, slots, valid)
        count = 1 + acc
        return vin, ck, cv, count, bonus

    if not partial:
        def step_full(params, cache, pending, tree_tokens):
            vin, ck, cv, count, bonus = common_part(params, cache, pending,
                                                    tree_tokens)
            cache = vf.append_full_cache(cache, ck, cv, count, spec)
            return bonus, cache
        return step_full

    def step_partial(params, cache, pkv_args, buf_len, pending,
                     tree_tokens):
        vin, ck, cv, count, bonus = common_part(params, cache, pending,
                                                tree_tokens, pkv=pkv_args)
        cpos = jnp.take_along_axis(
            vin["positions"],
            vf.commit_slots(tree, vin["pend_valid"],
                            jnp.full_like(tree_tokens[:, :tree.depth], -1),
                            1)[0], axis=1)
        body = spec.partial_budget_tokens
        if int8:
            from repro.kvcache.quant import quantize_kv
            pk, pv, ppos, pks, pvs = pkv_args
            ckq, cks = quantize_kv(ck)
            cvq, cvs = quantize_kv(cv)
            pk, pv, ppos, buf_len = vf.append_buffer(
                pk, pv, ppos, body, buf_len, ckq, cvq, cpos, count)
            # scales follow the same buffer layout
            cks_h = jnp.moveaxis(cks, 3, 2)
            cvs_h = jnp.moveaxis(cvs, 3, 2)
            off = body + buf_len - count

            def wr(buf, new, o):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (0, o))
            pks = jax.vmap(lambda bl, nl: jax.vmap(wr)(bl, nl, off))(pks,
                                                                     cks_h)
            pvs = jax.vmap(lambda bl, nl: jax.vmap(wr)(bl, nl, off))(pvs,
                                                                     cvs_h)
            return bonus, cache, (pk, pv, ppos, pks, pvs), buf_len
        pk, pv, ppos = pkv_args
        pk, pv, ppos, buf_len = vf.append_buffer(
            pk, pv, ppos, body, buf_len, ck, cv, cpos, count)
        return bonus, cache, (pk, pv, ppos), buf_len
    return step_partial


def build_verify_case(arch: str, *, partial: bool, int8: bool, mesh):
    cfg = get_config(arch)
    spec = SpecPVConfig()
    batch, seq = 8, 32768
    rules = ShardingRules(mesh)
    params_shape = jax.eval_shape(lambda k: api.init_params(cfg, k),
                                  jax.random.PRNGKey(0))
    pargs = _shard_tree(rules, params_shape,
                        param_shardings(rules, params_shape))
    nb = -(-(seq + 2 * 128) // 128)
    nb = -(-nb // 16) * 16
    max_len = nb * 128
    cache_shape = jax.eval_shape(
        lambda: api.init_cache(cfg, batch, max_len, spec))
    cshard = cache_shardings(rules, cfg, cache_shape)
    cargs = {k: _sds(v.shape, v.dtype, cshard[k])
             for k, v in cache_shape.items()}
    bspec = batch_spec(rules, batch)
    bax = bspec[0] if len(bspec) else None
    pending = _sds((batch,), jnp.int32, NamedSharding(mesh, P(bax)))
    tree_tokens = _sds((batch, TREE.size), jnp.int32,
                       NamedSharding(mesh, P(bax, None)))
    fn = make_verify_step(cfg, spec, TREE, partial=partial, int8=int8)
    if not partial:
        return fn, (pargs, cargs, pending, tree_tokens), (1,)

    l_attn = attn_layer_count(cfg.layer_kinds())
    p_slots = spec.partial_budget_tokens + spec.buffer_size
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    kdt = jnp.int8 if int8 else cm.dt(cfg.dtype)
    shapes = [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots, dh), kdt)
              ] * 2 + [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots),
                                            jnp.int32)]
    if int8:
        shapes += [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots),
                                        jnp.bfloat16)] * 2
    pksh = pkv_shardings(rules, shapes[:3])
    shard5 = list(pksh) + [pksh[2], pksh[2]]
    pkv_args = tuple(_sds(s.shape, s.dtype, sh)
                     for s, sh in zip(shapes, shard5))
    buf_len = _sds((batch,), jnp.int32, NamedSharding(mesh, P()))
    return fn, (pargs, cargs, pkv_args, buf_len, pending, tree_tokens), (2,)


# ---------------------------------------------------------------------------
# int8 decode steps (families B, C)
# ---------------------------------------------------------------------------

def make_decode_step_int8(cfg, spec, *, partial: bool):
    from repro.kvcache.quant import quantize_kv

    def step_full(params, cache, token):
        b = token.shape[0]
        pos = cache["length"][:, None]
        out = api.decode(cfg, params, token[:, None], pos, cache,
                         mode="full", spec=spec)
        nxt = jnp.argmax(out.logits[:, 0], axis=-1).astype(jnp.int32)
        kq, ks = quantize_kv(out.new_kv[0])     # [L,B,1,Hk,Dh]
        vq, vs = quantize_kv(out.new_kv[1])

        def wr4(buf, new, off):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (off, 0, 0))

        def wr3(buf, new, off):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (off, 0))
        length = cache["length"]
        cache = dict(cache)
        cache["k"] = jax.vmap(lambda bl, nl: jax.vmap(wr4)(bl, nl, length)
                              )(cache["k"], kq)
        cache["v"] = jax.vmap(lambda bl, nl: jax.vmap(wr4)(bl, nl, length)
                              )(cache["v"], vq)
        cache["k_scale"] = jax.vmap(
            lambda bl, nl: jax.vmap(wr3)(bl, nl, length)
        )(cache["k_scale"], ks)
        cache["v_scale"] = jax.vmap(
            lambda bl, nl: jax.vmap(wr3)(bl, nl, length)
        )(cache["v_scale"], vs)
        cache["length"] = length + 1
        return nxt, cache

    def step_partial(params, cache, pkv_args, buf_len, token):
        b = token.shape[0]
        pos = (cache["length"] + buf_len)[:, None]
        out = api.decode(cfg, params, token[:, None], pos, cache,
                         mode="partial", pkv=pkv_args, spec=spec)
        nxt = jnp.argmax(out.logits[:, 0], axis=-1).astype(jnp.int32)
        pk, pv, ppos, pks, pvs = pkv_args
        kq, ks = quantize_kv(out.new_kv[0])
        vq, vs = quantize_kv(out.new_kv[1])
        ones = jnp.ones((b,), jnp.int32)
        body = spec.partial_budget_tokens
        pk, pv, ppos, buf_len = vf.append_buffer(pk, pv, ppos, body,
                                                 buf_len, kq, vq, pos, ones)
        off = body + buf_len - 1

        def wr(buf, new, o):
            return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                                (0, o))
        ksh = jnp.moveaxis(ks, 3, 2)
        vsh = jnp.moveaxis(vs, 3, 2)
        pks = jax.vmap(lambda bl, nl: jax.vmap(wr)(bl, nl, off))(pks, ksh)
        pvs = jax.vmap(lambda bl, nl: jax.vmap(wr)(bl, nl, off))(pvs, vsh)
        return nxt, cache, (pk, pv, ppos, pks, pvs), buf_len

    return step_partial if partial else step_full


def build_int8_decode_case(arch: str, shape: str, mesh):
    cfg = get_config(arch)
    spec = SpecPVConfig()
    info = INPUT_SHAPES[shape]
    seq, batch = info["seq_len"], info["global_batch"]
    partial = shape == "long_500k"
    rules = ShardingRules(mesh)
    params_shape = jax.eval_shape(lambda k: api.init_params(cfg, k),
                                  jax.random.PRNGKey(0))
    pargs = _shard_tree(rules, params_shape,
                        param_shardings(rules, params_shape))
    seq_shards = (int(np.prod(list(mesh.shape.values())))
                  if partial else 16)
    nb = -(-(seq + 2 * 128) // 128)
    nb = -(-nb // seq_shards) * seq_shards
    max_len = nb * 128
    cache_shape = jax.eval_shape(
        lambda: api.init_cache(cfg, batch, max_len, spec))
    # re-type k/v to int8 + add scales
    l_attn = attn_layer_count(cfg.layer_kinds())
    hk, dh = cfg.num_kv_heads, cfg.head_dim_
    cache_shape["k"] = jax.ShapeDtypeStruct(cache_shape["k"].shape, jnp.int8)
    cache_shape["v"] = jax.ShapeDtypeStruct(cache_shape["v"].shape, jnp.int8)
    cache_shape["k_scale"] = jax.ShapeDtypeStruct(
        (l_attn, batch, max_len, hk), jnp.bfloat16)
    cache_shape["v_scale"] = jax.ShapeDtypeStruct(
        (l_attn, batch, max_len, hk), jnp.bfloat16)
    cshard = cache_shardings(rules, cfg, cache_shape,
                             shard_seq_over_all=partial)
    seq_spec = cshard["k"].spec[2]
    bspec = batch_spec(rules, batch)
    bax = bspec[0] if len(bspec) else None
    for s_ in ("k_scale", "v_scale"):
        cshard[s_] = NamedSharding(mesh, P(None, cshard["k"].spec[1],
                                           seq_spec, None))
    cargs = {k: _sds(v.shape, v.dtype, cshard[k])
             for k, v in cache_shape.items()}
    token = _sds((batch,), jnp.int32, NamedSharding(mesh, P(bax)))
    fn = make_decode_step_int8(cfg, spec, partial=partial)
    if not partial:
        return fn, (pargs, cargs, token), (1,)
    p_slots = spec.partial_budget_tokens + spec.buffer_size
    shapes = [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots, dh),
                                   jnp.int8)] * 2 + \
        [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots), jnp.int32)] + \
        [jax.ShapeDtypeStruct((l_attn, batch, hk, p_slots),
                              jnp.bfloat16)] * 2
    pksh = pkv_shardings(rules, shapes[:3])
    shard5 = list(pksh) + [pksh[2], pksh[2]]
    pkv_args = tuple(_sds(s.shape, s.dtype, sh)
                     for s, sh in zip(shapes, shard5))
    buf_len = _sds((batch,), jnp.int32, NamedSharding(mesh, P()))
    return fn, (pargs, cargs, pkv_args, buf_len, token), (1, 2)


def build_cp_retrieval_case(arch: str, mesh):
    """Case D: shard_map context-parallel retrieval + partial attention —
    selected blocks stay shard-local; only softmax partials cross ICI."""
    from repro.distributed.cp_retrieval import cp_partial_verify_attention
    cfg = get_config(arch)
    spec = SpecPVConfig()
    b, t = 1, 8
    seq = 524288
    hk, dh, h = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    nb = seq // spec.block_size
    rules = ShardingRules(mesh)
    seq_sh = NamedSharding(mesh, P(None, "model", None, None))
    q = _sds((b, t, h, dh), cm.dt(cfg.dtype), NamedSharding(mesh, P()))
    k = _sds((b, seq, hk, dh), cm.dt(cfg.dtype), seq_sh)
    v = _sds((b, seq, hk, dh), cm.dt(cfg.dtype), seq_sh)
    km = _sds((b, nb, hk, dh), jnp.float32, seq_sh)
    kn = _sds((b, nb, hk, dh), jnp.float32, seq_sh)
    ln = _sds((b,), jnp.int32, NamedSharding(mesh, P()))

    def fn(q, k, v, km, kn, ln):
        return cp_partial_verify_attention(
            mesh, "model", spec, spec.retrieval_budget_blocks,
            q, k, v, km, kn, ln)

    return fn, (q, k, v, km, kn, ln), ()


CASES = {
    "A0_granite_verify32k_full":
        lambda mesh: build_verify_case("granite-3-2b", partial=False,
                                       int8=False, mesh=mesh),
    "A1_granite_verify32k_partial":
        lambda mesh: build_verify_case("granite-3-2b", partial=True,
                                       int8=False, mesh=mesh),
    "A2_granite_verify32k_partial_int8":
        lambda mesh: build_verify_case("granite-3-2b", partial=True,
                                       int8=True, mesh=mesh),
    "B1_qwen32b_decode32k_int8":
        lambda mesh: build_int8_decode_case("qwen1.5-32b", "decode_32k",
                                            mesh),
    "C1_deepseek_long500k_int8pkv":
        lambda mesh: build_int8_decode_case("deepseek-7b", "long_500k",
                                            mesh),
    "D1_deepseek_cp_retrieval":
        lambda mesh: build_cp_retrieval_case("deepseek-7b", mesh),
}


def run_case(name: str) -> dict:
    res = {"case": name, "ok": False}
    try:
        mesh = make_production_mesh()
        t0 = time.time()
        fn, args, donate = CASES[name](mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        res["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t0, 2)
        ma = compiled.memory_analysis()
        res["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            per_device_total=int(ma.argument_size_in_bytes
                                 + ma.output_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 - ma.alias_size_in_bytes))
        ca = compiled.cost_analysis() or {}
        res["flops"] = float(ca.get("flops", 0.0))
        res["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        res["collectives"] = parse_collective_bytes(compiled.as_text())
        res["ok"] = True
    except Exception as e:  # noqa: BLE001
        res["error"] = f"{type(e).__name__}: {e}"
        res["traceback"] = traceback.format_exc()[-1500:]
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default=None, choices=list(CASES) + [None])
    args = ap.parse_args()
    os.makedirs(RESULTS_DIR, exist_ok=True)
    for name in ([args.case] if args.case else CASES):
        print(f"[hillclimb] {name} ...", flush=True)
        r = run_case(name)
        with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
            json.dump(r, f, indent=1)
        if r["ok"]:
            print(f"  -> OK compile={r['compile_s']}s "
                  f"mem={r['memory']['per_device_total']/2**30:.2f}GiB "
                  f"args={r['memory']['argument_bytes']/2**30:.2f}GiB")
        else:
            print(f"  -> FAIL {r['error'][:200]}")


if __name__ == "__main__":
    main()
