"""The dense GQA architecture module (``archs/dense_gqa.py``) and the
harness around it compute exactly what the harness computed before the
architecture moved out of ``model.py``, ``weights.py`` and ``flops.py``:
every value ``record_dense_identity.py`` recorded from that harness
(``data/dense_identity.json``), compared with exact equality."""
import dataclasses
import json
from pathlib import Path

import pytest

import correct as cor
import flops
import model
import tiny
import weights
from record_dense_identity import (CONFIGS, GRID, flat_shapes, leaf_digests,
                                   logits_digest, to_json)

DATA = Path(__file__).resolve().parent / "data" / "dense_identity.json"
RECORD = json.loads(DATA.read_text())


@pytest.fixture(scope="module")
def tiny_conf(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    return model.load_config("tiny", root / "bench")


@pytest.fixture(params=CONFIGS + ("tiny",))
def case(request, tiny_conf):
    name = request.param
    conf = tiny_conf if name == "tiny" else model.load_config(name)
    return conf, RECORD["configs"][name]


def test_the_record_covers_the_grid():
    assert [(t, s) for t in RECORD["grid"]["t_q"]
            for s in RECORD["grid"]["s_kv"]] == GRID
    assert set(RECORD["configs"]) == set(CONFIGS) | {"tiny"}


def test_shape_tree_is_unchanged(case):
    conf, rec = case
    dm = model.dims(conf)
    assert flat_shapes(model.arch_of(dm).shapes(dm),
                       weights._is_leaf) == rec["shapes"]


def test_program_configs_are_unchanged(case):
    conf, rec = case
    cfg, spec, dcfg = model.program_configs(conf)
    assert {"model": to_json(dataclasses.asdict(cfg)),
            "specpv": to_json(dataclasses.asdict(spec)),
            "draft": to_json(dataclasses.asdict(dcfg))} == rec["configs"]


def test_counts_are_unchanged(case):
    conf, rec = case
    dm = model.dims(conf)
    arch = model.arch_of(dm)
    assert {"layer_params": dm.layer_params(),
            "kv_bytes_per_token": dm.kv_bytes_per_token(),
            "matmul_flops_per_token": flops.matmul_flops_per_token(dm),
            "matmul_flops_per_token_no_head":
                flops.matmul_flops_per_token(dm, lm_head=False)
            } == rec["sizes"]
    got = [{"t_q": t, "s_kv": s,
            "verify_flops": flops.verify_flops(dm, t, s),
            "attn_flops": arch.layer_attn_flops(dm, t, s),
            "attn_bytes": arch.layer_attn_bytes(dm, t, s),
            "layers_attn_flops": flops.attn_flops(dm, t, s),
            "layers_attn_bytes": flops.attn_bytes(dm, t, s)}
           for t, s in GRID]
    assert got == rec["counts"]


def test_tiny_weights_and_reference_logits_are_bit_equal(tiny_conf):
    rec = RECORD["configs"]["tiny"]
    dm = model.dims(tiny_conf)
    params, dparams = weights.make_weights(dm, rec["weights"]["seed"])
    assert leaf_digests((params, dparams)) == rec["weights"]["leaves"]
    want = rec["served_logits"]
    req = want["request"]
    assert cor.plan(len(req["prompt"]), len(req["tokens"]), req["steps"])
    lg = cor.served_logits(cor.reference(tiny_conf["reference"]), dm, params,
                           tiny_conf["specpv"], req)
    assert list(lg.shape) == want["shape"]
    assert logits_digest(lg) == want["sha256"]
