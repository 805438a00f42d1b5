"""The control of ``correct``, and the readings its limits are set from.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 10

For each seed, in one process: the cell runs as ``run.py`` runs it (a
short window at the cell's own load), and over the same sample of
finished requests this prints, per class of served token, the program's
widest gap (the *lower* reading of a limit) and the widest gap of the
token the reference computed in float8 puts first (the control: the
*upper* reading).  A limit lies between the two (``bench/correct.py``).
It is not part of a benchmark run.  One JSON line per seed goes to
stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(keep: dict) -> dict:
    import correct as cor
    ref, dm, w, sp = keep["ref"], keep["dims"], keep["weights"], keep["spec"]
    prog, ctrl = [], []
    for r in keep["reqs"]:
        cls = cor.classes(r)
        prog.append(cor.widest(cor.gaps(ref, dm, w, sp, r), cls))
        ctrl.append(cor.widest(cor.control_gaps(ref, dm, w, sp, r), cls))
    w = [len(r["tokens"]) for r in keep["reqs"]]
    return {"program": cor.merge(prog, w), "control": cor.merge(ctrl, w),
            "requests": len(keep["reqs"]),
            "tokens": int(sum(len(r["tokens"]) for r in keep["reqs"]))}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        keep: dict = {}
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        out = run.run(one, t_start=time.perf_counter(), keep=keep)
        line = {"workload": args.workload, "seed": seed,
                "correct": out["correct"], **readings(keep)}
        print(json.dumps(line), flush=True)
        keep.clear()


if __name__ == "__main__":
    main()
