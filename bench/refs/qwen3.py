"""Plain reference of the Qwen3 dense decoder (``Qwen3ForCausalLM``,
arXiv:2505.09388), and of what SpecPV's partial verification makes it
attend.

Qwen3's layer is the dense GQA layer of ``refs/dense_gqa.py`` with one
change, in the attention's projections: each query head and each key
head is RMS-normalised over ``head_dim``, with its learned scale
(``q_norm``, ``k_norm``; eps ``rms_norm_eps``), before RoPE::

    q = RoPE(RMSNorm_hd(x Wq) * q_norm, pos)
    k = RoPE(RMSNorm_hd(x Wk) * k_norm, pos)
    v = x Wv

So the keys every layer returns, and the block summaries ``select``
scores, are normalised keys, and so are the queries.

This module runs a private copy of ``refs/dense_gqa.py`` with its
projection step ``_qkv`` replaced by Qwen3's; the rest is that file's:
float32 at ``highest`` matmul precision, layers computed in blocks,
``forward``, ``head``, ``select``, ``select_layers``, ``segment``, and
the float8 control (``fp8=True``: the inputs of every linear layer and
of the LM head rounded to float8 e4m3; the norms stay in float32).
Departures from Qwen3's published forward pass: none in the trunk.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path


def _load_dense():
    spec = importlib.util.spec_from_file_location(
        f"{__name__}_dense_gqa",
        Path(__file__).resolve().parent / "dense_gqa.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _load_dense()
_mm, _rms, _rope = _dense._mm, _dense._rms, _dense._rope


def _qkv(h, lw, pos, dims, fp8):
    heads, kv_heads, dh, eps, theta = dims
    s = h.shape[0]
    x = _rms(h, lw["norm1"], eps)
    a = lw["attn"]
    q = _rms(_mm(x, a["wq"], fp8).reshape(s, heads, dh), a["q_norm"], eps)
    k = _rms(_mm(x, a["wk"], fp8).reshape(s, kv_heads, dh), a["k_norm"], eps)
    v = _mm(x, a["wv"], fp8).reshape(s, kv_heads, dh)
    return _rope(q, pos, theta), _rope(k, pos, theta), v


_dense._qkv = _qkv      # the copy's layers now project as Qwen3 does

forward = _dense.forward
head = _dense.head
select = _dense.select
select_layers = _dense.select_layers
segment = _dense.segment
