"""Dense GQA decoder (Qwen3 without q/k norm) for the harness: its sizes,
the program's configuration, the weights' layout and the work a
position needs.  The contract every architecture module keeps is in
``bench/model.py``; the plain reference is ``bench/refs/dense_gqa.py``.

Every layer is the same pre-norm block: grouped-query attention over the
whole context (``heads`` query heads of ``head_dim``, ``kv_heads`` key
and value heads) and a SwiGLU MLP of width ``ff``.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int         # intermediate (SwiGLU) width
    vocab: int
    tied: bool
    eps: float
    theta: float

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_params(self) -> int:
        """Weights of one decoder layer (projections and norms)."""
        d = self.d
        return (d * self.q_width + 2 * d * self.kv_width
                + self.q_width * d + 3 * d * self.ff + 2 * d)

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        return 2 * self.layers * self.kv_width * itemsize


def dims(conf: dict) -> Dims:
    m = conf["model"]
    return Dims(d=m["hidden_size"], layers=m["num_hidden_layers"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                ff=m["intermediate_size"], vocab=m["vocab_size"],
                tied=bool(m["tie_word_embeddings"]),
                eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]))


def program_configs(conf: dict):
    """(ModelConfig, SpecPVConfig, DraftConfig) of the system under test,
    at the file's sizes, bf16, with its SpecPV and draft settings (repo
    defaults unless the file overrides them)."""
    from repro.configs import DraftConfig, ModelConfig, SpecPVConfig
    dm = dims(conf)
    cfg = ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=dm.layers, d_model=dm.d, num_heads=dm.heads,
        num_kv_heads=dm.kv_heads, head_dim=dm.head_dim, d_ff=dm.ff,
        vocab_size=dm.vocab, tie_embeddings=dm.tied, act="silu",
        norm_eps=dm.eps, rope_theta=dm.theta, dtype="bfloat16",
        param_dtype="bfloat16")
    return (cfg, SpecPVConfig(**conf.get("specpv", {})),
            DraftConfig(**conf.get("draft", {})))


def _layer_shapes(dm: Dims, stack: tuple = ()):
    d, f = dm.d, dm.ff
    s = stack
    return {
        "norm1": (s + (d,), None),
        "attn": {"wq": (s + (d, dm.q_width), d),
                 "wk": (s + (d, dm.kv_width), d),
                 "wv": (s + (d, dm.kv_width), d),
                 "wo": (s + (dm.q_width, d), dm.q_width)},
        "norm2": (s + (d,), None),
        "mlp": {"wi": (s + (d, f), d), "wg": (s + (d, f), d),
                "wo": (s + (f, d), f)},
    }


def shapes(dm: Dims):
    """(target, draft) pytrees of (shape, fan_in), laid out as the
    program's dense model and EAGLE-3 draft take them: target ``{embed,
    final_norm, decoder: {slots: [layer stack], rem: []}, head}`` (no
    ``head`` when the embedding is tied) and draft ``{fuse, in_proj,
    layer, final_norm}``."""
    target = {"embed": ((dm.vocab, dm.d), "embed"),
              "final_norm": ((dm.d,), None),
              "decoder": {"slots": [_layer_shapes(dm, (dm.layers,))],
                          "rem": []}}
    if not dm.tied:
        target["head"] = ((dm.d, dm.vocab), dm.d)
    draft = {"fuse": ((3 * dm.d, dm.d), 3 * dm.d),
             "in_proj": ((2 * dm.d, dm.d), 2 * dm.d),
             "layer": _layer_shapes(dm),
             "final_norm": ((dm.d,), None)}
    return target, draft


def matmul_flops_per_token(dm: Dims, lm_head: bool = True) -> float:
    """Projections and MLP of every layer (+ the LM head), per token."""
    d = dm.d
    per_layer = 2.0 * (d * dm.q_width + 2 * d * dm.kv_width
                       + dm.q_width * d + 3 * d * dm.ff)
    return dm.layers * per_layer + (2.0 * d * dm.vocab if lm_head else 0.0)


def layer_attn_flops(dm: Dims, t_q: int, s_kv: int) -> float:
    """One layer: ``t_q`` queries each attending ``s_kv`` keys, all
    heads."""
    return 4.0 * t_q * s_kv * dm.heads * dm.head_dim


def layer_attn_bytes(dm: Dims, t_q: int, s_kv: int,
                     itemsize: int = BF16) -> float:
    """One layer: K and V of the attended keys (read once per kv head)
    plus the queries read and the outputs written."""
    kv = 2.0 * s_kv * dm.kv_heads * dm.head_dim * itemsize
    qo = 2.0 * t_q * dm.heads * dm.head_dim * itemsize
    return kv + qo


def attn_flops(dm: Dims, t_q: int, s_kv: int) -> float:
    """Every layer attends the whole context."""
    return dm.layers * layer_attn_flops(dm, t_q, s_kv)


def attn_bytes(dm: Dims, t_q: int, s_kv: int) -> float:
    return dm.layers * layer_attn_bytes(dm, t_q, s_kv)
