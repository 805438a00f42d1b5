"""Tiny cells for the benchmark's CPU tests: the harness's own files, a
two-layer model at toy widths, a two-client QA mix that runs every
SpecPV mode (the budget is 7 blocks of 16 tokens) and a short mix whose
rows all stay in FULL."""
from __future__ import annotations

import argparse
import json
import shutil
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CELL = "tiny.qa"
SHORT = "tiny.short"
LIMITS = {"gap_exact": 0.15, "mean_gap_partial": 0.1}


def make_root(dst: Path) -> Path:
    """A checkout-like directory: ``bench/`` copied, ``src`` linked, and
    the tiny configuration, mix and cell added."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(BENCH.parent / "src")
    conf = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    conf["name"] = "tiny"
    conf["model"].update(hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, intermediate_size=128, vocab_size=256,
                         tie_word_embeddings=False)
    conf["specpv"].update({"block_size": 16,
                      "num_sink_blocks": 1, "retrieval_budget_blocks": 4,
                      "local_window_blocks": 2, "buffer_size": 48})
    conf["serving"].update(prefill_chunk=32, prefill_budget=32)
    (dst / "bench/configs/tiny.json").write_text(json.dumps(conf))
    mix = {"generator": "closed_loop",
           "params": {"clients": 2, "block": 16, "context": [96, 160],
                      "suffix": [16, 32], "turns": 2, "new_tokens": [12, 12],
                      "grid": 4}}
    (dst / "bench/traffic/qa.json").write_text(json.dumps(mix))
    short = {"generator": "closed_loop",
             "params": {"clients": 2, "block": 16, "context": [32, 64],
                        "turns": 1, "new_tokens": [12, 12], "grid": 4}}
    (dst / "bench/traffic/short.json").write_text(json.dumps(short))
    for cell, limits in ((CELL, LIMITS), (SHORT, {"gap_exact": 0.15})):
        (dst / f"bench/limits/{cell}.json").write_text(
            json.dumps({"limits": limits}))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": t,
                           "chips": 1, "why": "test"}
                          for n, t in ((CELL, "qa"), (SHORT, "short"))]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run_tiny(root: Path, seed: int, seconds: float = 2.0, tamper=None,
             keep=None, cell: str = CELL) -> dict:
    import run
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return run.run(args, root=root, chip_check=False,
                   t_start=time.perf_counter(), tamper=tamper, keep=keep)
