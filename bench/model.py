"""A configuration file's sizes, for the harness and for the reference.

``dims`` reads the published keys (Hugging Face ``config.json`` names)
into the few numbers everything in the benchmark computes with;
``program_configs`` hands the same numbers to the system under test.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent


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


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


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
