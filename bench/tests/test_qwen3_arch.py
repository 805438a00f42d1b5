"""The Qwen3 architecture (``archs/qwen3.py``, ``refs/qwen3.py``): its
published counts, and a tiny Qwen3 cell added the way a configuration
adds one, served through the normal path and judged by the Qwen3
reference: correct as served, not correct with the q/k norm leaves
taken out of the served weights."""
import json
import sys
from pathlib import Path

import pytest

import model
import tiny
from test_arch_files_only import files

BENCH = Path(__file__).resolve().parents[1]
CONFIG = "tiny-qwen3"
CELL = "tiny-qwen3.qa"
SEED = 2**31 + 57


def test_published_counts():
    dm = model.dims(model.load_config("qwen3-4b-qknorm"))
    assert type(dm).__module__ == "arch_qwen3"
    assert dm.layer_params() == 100_930_816
    total = dm.layers * dm.layer_params() + dm.vocab * dm.d + dm.d
    assert dm.tied and total == 4_022_468_096
    assert dm.kv_bytes_per_token() == 147_456


def test_norm_leaves_in_the_target_only():
    dm = model.dims(model.load_config("qwen3-4b-qknorm"))
    target, draft = model.arch_of(dm).shapes(dm)
    attn = target["decoder"]["slots"][0]["attn"]
    assert attn["q_norm"] == ((36, 128), None)
    assert attn["k_norm"] == ((36, 128), None)
    assert "q_norm" not in draft["layer"]["attn"]


def add_cell(root: Path) -> None:
    """The tiny configuration on the Qwen3 architecture, its limits and
    its entries in BENCHMARK.json: new files and appended entries only."""
    bench = root / "bench"
    conf = json.loads((bench / "configs" / "tiny.json").read_text())
    conf.update(name=CONFIG, reference="qwen3")
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(conf))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": tiny.LIMITS}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": CONFIG, "source": "test",
                            "file": f"bench/configs/{CONFIG}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": CONFIG,
                              "traffic": "qa", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("qwen3"))
    before = files(root / "bench")
    add_cell(root)
    new = sorted(k for k in files(root / "bench") if k not in before)
    assert new == [f"configs/{CONFIG}.json", f"limits/{CELL}.json"]
    return root


def drop_norms(engine):
    """Serve the same weights without the q/k norm leaves: the program
    then runs its dense block, as a program without the norm would."""
    def strip(tree):
        if isinstance(tree, dict):
            return {k: strip(v) for k, v in tree.items()
                    if k not in ("q_norm", "k_norm")}
        if isinstance(tree, list):
            return [strip(v) for v in tree]
        return tree
    engine.params = strip(engine.params)


@pytest.mark.parametrize("served", ["qwen3", "without_qk_norm"])
def test_tiny_cell_against_the_reference(root, served):
    keep = {}
    out = tiny.run_tiny(root, SEED, keep=keep, cell=CELL,
                        tamper=drop_norms if served != "qwen3" else None)
    assert Path(keep["ref"].__file__) == root / "bench" / "refs" / "qwen3.py"
    assert sys.modules[type(keep["dims"]).__module__].__name__ == "arch_qwen3"
    assert "q_norm" in keep["weights"]["decoder"]["slots"][0]["attn"]
    assert out["attempted"] > 0
    assert out["correct"] == (served == "qwen3"), out["compared"]
