"""Qwen3 dense decoder (``Qwen3ForCausalLM``) for the harness: dense GQA
with Qwen3's per-head q/k RMSNorm.  The contract every architecture
module keeps is in ``bench/model.py``; the plain reference is
``bench/refs/qwen3.py``.

Every layer is ``bench/archs/dense_gqa.py``'s pre-norm block, and its
attention normalises each query head and each key head over
``head_dim`` before RoPE, with a learned ``[head_dim]`` scale each
(``q_norm``, ``k_norm``; eps ``rms_norm_eps``).  The program keys the
norm on those weights (``src/repro/models/blocks.py``), so the
``ModelConfig``, the matrix products and the attention counts are the
dense ones; only the weights' layout and the parameter count differ.
The EAGLE-3 draft layer carries no q/k norm: it is a Llama-style layer,
as published EAGLE-3 drafts for Qwen3 are.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from model import load_module

_dense = load_module(Path(__file__).resolve().parent / "dense_gqa.py",
                     f"{__name__}_dense_gqa")


@dataclasses.dataclass(frozen=True)
class Dims(_dense.Dims):
    def layer_params(self) -> int:
        """Weights of one decoder layer: the dense block's and the two
        ``[head_dim]`` norm scales."""
        return super().layer_params() + 2 * self.head_dim


def dims(conf: dict) -> Dims:
    return Dims(**dataclasses.asdict(_dense.dims(conf)))


program_configs = _dense.program_configs
matmul_flops_per_token = _dense.matmul_flops_per_token
attn_flops = _dense.attn_flops
attn_bytes = _dense.attn_bytes


def shapes(dm: Dims):
    """(target, draft) as ``dense_gqa.shapes`` lays them out, with each
    target layer's attention also holding ``q_norm`` and ``k_norm``
    ([layers, head_dim], scales 1 like the block norms)."""
    target, draft = _dense.shapes(dm)
    attn = target["decoder"]["slots"][0]["attn"]
    for name in ("q_norm", "k_norm"):
        attn[name] = ((dm.layers, dm.head_dim), None)
    return target, draft
