"""AOT compiles of the serving path's Pallas kernels for TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: block shapes whose last two dims are not (8, 128)
tiles, VMEM overflow, unpartitionable calls.  These tests compile each
kernel wrapper of ``repro.kernels.ops`` at Qwen3-8B widths (8 KV heads
of 128, 32 query heads, 128-token blocks, bf16) for a described
``v5e:2x2`` topology — no chip needed — and assert the compiled program
calls the Mosaic kernel (``tpu_custom_call``), named after its call site,
and that the paged attention kernels read the stored pool stack in place:
a layer scan over it holds no pool-sized copy.

The topology is described inside a module-scoped fixture (never at
import time): only one process may load the TPU library at a time, so
the call happens in the one worker that runs this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, T, TP, H, HK, DH, BS = 4, 61, 512, 32, 8, 128, 128
NB = 10240 // BS                   # blocks per slot at max_len 10240
NP = B * NB + 1                    # pool pages incl. the null page
NS = 35                            # partial budget: 1 + 32 + 2 blocks
LAYERS = 12                        # pool stack depth (the docqa cell's)
LAYER_POOL_BYTES = NP * BS * HK * DH * 2   # one layer's bf16 K pool


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # the kernels trace differently for the chip (interpret off): start
    # and end with empty trace caches so no CPU trace is reused here and
    # none of these leaks into later tests of this worker
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()


@pytest.fixture
def tpu_kernels(monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(sh):
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = _sds(sh, (LAYERS, NP, BS, HK * DH), bf)   # stored, lane-dense
    layer = _sds(sh, (), i32)
    q = _sds(sh, (B, T, H, DH), bf)
    return {
        "block_summaries": (
            lambda k, n: ops.block_summaries(k, n, BS),
            (_sds(sh, (B, NB * BS, HK, DH), bf), _sds(sh, (B,), i32))),
        "retrieval_scores": (
            ops.retrieval_scores,
            (q, _sds(sh, (B, NB, HK, DH), f32), _sds(sh, (B, NB, HK, DH), f32),
             _sds(sh, (B, T), f32))),
        "paged_verify_attention": (
            ops.paged_verify_attention,
            (q, pool, pool, layer, _sds(sh, (B, NB), i32),
             _sds(sh, (B,), i32))),
        "routed_partial_attention": (
            ops.routed_partial_attention,
            (q, pool, pool, layer, _sds(sh, (B, HK, NS), i32),
             _sds(sh, (B, HK, NS), i32))),
        "paged_prefill_attention": (
            ops.paged_prefill_attention,
            (_sds(sh, (B, TP, H, DH), bf), pool, pool, layer,
             _sds(sh, (B, NB), i32), _sds(sh, (B,), i32),
             _sds(sh, (B,), i32))),
    }


@pytest.mark.parametrize("name", ["block_summaries", "retrieval_scores",
                                  "paged_verify_attention",
                                  "routed_partial_attention",
                                  "paged_prefill_attention"])
def test_kernel_compiles_for_v5e(name, one_chip, tpu_kernels):
    fn, args = _cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


KERNEL_NAMES = {"block_summaries": "specpv_block_summary",
                "retrieval_scores": "specpv_retrieval_score",
                "paged_verify_attention": "specpv_attn_full",
                "routed_partial_attention": "specpv_attn_partial",
                "paged_prefill_attention": "specpv_attn_prefill"}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_op_carries_its_name(name, one_chip, tpu_kernels):
    """Every kernel op of the compiled wrapper is named after its call
    site (``kernels/names.py``): the name a profiler trace shows."""
    fn, args = _cases(one_chip)[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    ops = [line.split(" = ")[0].strip() for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
    assert ops and all(op.startswith(f"%{KERNEL_NAMES[name]}.")
                       for op in ops), ops


PAGED = ["paged_verify_attention", "routed_partial_attention",
         "paged_prefill_attention"]
# ops that alias a buffer rather than write one
_ALIASES = {"parameter", "get-tuple-element", "bitcast", "tuple"}
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1}
_OP = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                 r"(?:\{[^}]*\})? ([\w\-]+)\(")


def _buffers(text):
    """(name, opcode, bytes) of every array-valued op of a compiled
    program's text."""
    for line in text.splitlines():
        m = _OP.match(line)
        if m and m.group(2) in _BYTES:
            n = _BYTES[m.group(2)]
            for d in filter(None, m.group(3).split(",")):
                n *= int(d)
            yield m.group(1), m.group(4), n


@pytest.mark.parametrize("name", PAGED)
def test_layer_scan_reads_the_pool_in_place(name, one_chip, tpu_kernels):
    """A scan over the layers of the stored pool stack, calling the paged
    wrapper at each layer's index as the fused decode step does: the
    kernel's operand is the stored buffer, so no op writes a buffer of
    one layer's pool or more (a scan slice or a relayout would), and the
    program's scratch stays below one layer's K pool."""
    fn, args = _cases(one_chip)[name]
    q, pool_k, pool_v, _, *rest = args

    def scan(q, pool_k, pool_v, *rest):
        def body(acc, layer):
            out = fn(q, pool_k, pool_v, layer, *rest)
            part = out if name == "paged_prefill_attention" else out[2]
            return acc + part.astype(jnp.float32), None

        zero = jnp.zeros(jax.eval_shape(body, 0.0, 0)[0].shape, jnp.float32)
        return jax.lax.scan(body, zero, jnp.arange(LAYERS))[0]

    compiled = jax.jit(scan).lower(q, pool_k, pool_v, *rest).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    big = [(op, code, n) for op, code, n in _buffers(text)
           if code not in _ALIASES and n >= LAYER_POOL_BYTES]
    assert not big, big
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < LAYER_POOL_BYTES, (temp, LAYER_POOL_BYTES)
