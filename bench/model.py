"""A configuration file and the architecture that reads it.

A configuration (``bench/configs/<config>.json``) holds a model's
published keys (Hugging Face ``config.json`` names) under ``"model"``
and names its architecture under ``"reference"``: the harness finds
``bench/archs/<reference>.py`` (``arch``) for the sizes, the program's
configuration, the weights' layout and the work counts, and
``bench/refs/<reference>.py`` (``correct.reference``) for the plain
reference.  No other file of ``bench/`` knows a model's widths.

An architecture module defines these five, and the harness asks it for
nothing else:

``dims(conf)`` and its ``Dims``
    The sizes the module's other functions and its reference compute
    with, read from ``conf["model"]``.  The harness reads one field
    itself, ``vocab`` (the token ids the traffic draws), and hands the
    object back to the module (``arch_of``) and to the reference.
    ``Dims.layer_params()`` is the weights of one decoder layer (a
    count); ``Dims.kv_bytes_per_token(itemsize=2)`` the bytes one token
    keeps in the KV cache over every layer.
``program_configs(conf)``
    ``(ModelConfig, SpecPVConfig, DraftConfig)`` of the system under
    test at the file's sizes, in bf16.
``shapes(dm)``
    ``(target, draft)`` pytrees of ``(shape, fan_in)`` leaves, laid out
    as the program takes its weights; ``weights.make_weights`` draws
    them (fan_in None: ones; ``"embed"``: N(0, 0.02); a number n:
    N(0, 1/n)).  The leaves' order fixes the weights a seed gives.
``matmul_flops_per_token(dm, lm_head=True)``
    Operations (2 per multiply-add) of the target model's matrix
    products for one position, through every layer and the LM head.
``attn_flops(dm, t_q, s_kv)`` and ``attn_bytes(dm, t_q, s_kv)``
    Operations, and bytes of HBM traffic in the served type, of ``t_q``
    queries attending ``s_kv`` keys of context, summed over the layers
    that attend.

Which layers a SpecPV refresh selects blocks in is the reference's to
say (``select_layers`` beside ``select`` in ``refs/<reference>.py``).
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    """The Python file ``path`` as the module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses and arch_of look it up
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


def arch(conf: dict, root: Path = BENCH):
    """The architecture module ``archs/<reference>.py`` of ``conf``."""
    name = conf["reference"]
    return load_module(root / "archs" / f"{name}.py", f"arch_{name}")


def arch_of(dm):
    """The architecture module whose ``dims`` made ``dm``."""
    return sys.modules[type(dm).__module__]


def dims(conf: dict):
    """``conf``'s sizes, by its architecture in this checkout."""
    return arch(conf).dims(conf)


def program_configs(conf: dict):
    """``conf``'s program configuration, by its architecture in this
    checkout."""
    return arch(conf).program_configs(conf)
