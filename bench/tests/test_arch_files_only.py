"""A configuration brings its own architecture to the harness as new
files only.

On a copy of the harness (``tiny.make_root``) an architecture is added
under a new name the way a new model adds one: ``archs/<name>.py``,
``refs/<name>.py``, a configuration that names it, the new cell's
limits file and the new entries of ``BENCHMARK.json``.  Copies of the
dense modules stand in for a new model: what is under test is the seam,
not a model.  Every harness file stays byte-identical to the checkout,
and one run of the new cell, shared by the tests, is correct and was
served and checked by the new modules."""
import json
import shutil
import sys
from pathlib import Path

import pytest

import tiny

BENCH = Path(__file__).resolve().parents[1]
NAME = "tiny_gqa"                   # the architecture added
CONFIG = "tiny-gqa"
CELL = "tiny-gqa.qa"
SEED = 2**31 + 29


def files(bench: Path) -> dict:
    """Relative path -> bytes of every file under ``bench`` but the
    tests and the bytecode caches."""
    return {str(p.relative_to(bench)): p.read_bytes()
            for p in sorted(bench.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts
            and p.relative_to(bench).parts[0] != "tests"}


def add_architecture(root: Path) -> None:
    """The new files, and the new entries appended to BENCHMARK.json."""
    bench = root / "bench"
    shutil.copyfile(BENCH / "archs" / "dense_gqa.py",
                    bench / "archs" / f"{NAME}.py")
    shutil.copyfile(BENCH / "refs" / "dense_gqa.py",
                    bench / "refs" / f"{NAME}.py")
    conf = json.loads((bench / "configs" / "tiny.json").read_text())
    conf.update(name=CONFIG, reference=NAME)
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
def added(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("arch"))
    before = files(root / "bench")
    spec_before = json.loads((root / "BENCHMARK.json").read_text())
    add_architecture(root)
    keep = {}
    out = tiny.run_tiny(root, SEED, keep=keep, cell=CELL)
    return {"root": root, "before": before, "spec_before": spec_before,
            "out": out, "keep": keep}


def test_harness_files_are_untouched(added):
    """Each harness file of the copy is the checkout's, byte for byte;
    the new architecture only added files."""
    ours, copy = files(BENCH), files(added["root"] / "bench")
    assert {k: copy.get(k) for k in ours} == ours
    new = {k: v for k, v in copy.items() if k not in added["before"]}
    assert sorted(new) == sorted([
        f"archs/{NAME}.py", f"refs/{NAME}.py", f"configs/{CONFIG}.json",
        f"limits/{CELL}.json"])
    assert {k: copy[k] for k in added["before"]} == added["before"]


def test_benchmark_json_only_gains_entries(added):
    before = added["spec_before"]
    after = json.loads((added["root"] / "BENCHMARK.json").read_text())
    assert set(after) == set(before)
    for key in before:
        if key in ("configs", "workloads"):
            assert after[key][:-1] == before[key]
        else:
            assert after[key] == before[key]
    assert after["workloads"][-1]["name"] == CELL


def test_new_cell_runs_correct(added):
    assert added["out"]["correct"], added["out"]["compared"]
    assert added["out"]["attempted"] > 0


def test_new_modules_were_the_ones_loaded(added):
    bench = added["root"] / "bench"
    keep = added["keep"]
    arch = sys.modules[type(keep["dims"]).__module__]
    assert arch.__name__ == f"arch_{NAME}"
    assert Path(arch.__file__) == bench / "archs" / f"{NAME}.py"
    assert Path(keep["ref"].__file__) == bench / "refs" / f"{NAME}.py"
