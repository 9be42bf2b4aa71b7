#!/usr/bin/env python3
"""Readings for the limits of ``correct``, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1-12 --seconds <s> \
        [--control-seeds 1-3] [--fault half_batch|unchanged|token --fault-seeds 1-3]

For each seed it drives a whole run of the cell (set-up, a window of
``--seconds``, the comparison with the reference) and prints the numbers
compared. With ``--control-seeds`` those runs also compute the control: the
reference itself in the program's place, one precision below the one the
configuration states (fp8 for bf16), against the float32 reference. With
``--fault`` the runs of ``--fault-seeds`` have that fault planted in the
timed path. The lower reading of a number is its largest over the program's
seeds; the upper is its smallest over the control's (or a fault's) seeds.
Output: one JSON line per run, then a summary line; the benchmark's own
runs never do this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, run as run_lib  # noqa: E402


def seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--base", type=int, default=0,
                    help="added to every seed (fresh seeds for a re-run)")
    a = ap.parse_args(argv)
    cell = common.resolve_cell(a.workload)
    devices = common.tpu_devices(cell["chips"])
    run_lib.compile_cache()
    ctrl = set(seeds(a.control_seeds))
    runs = [(s, None) for s in sorted(set(seeds(a.seeds)) | ctrl)]
    runs += [(s, a.fault) for s in seeds(a.fault_seeds)]
    lower, upper = {}, {}
    for s, fault in runs:
        args = argparse.Namespace(workload=a.workload, seed=a.base + s,
                                  seconds=a.seconds, trace=0, rate=None,
                                  control=(s in ctrl and fault is None), fault=fault)
        out = run_lib.kind_run(cell, args, devices)
        line = {"seed": args.seed, "fault": fault, "numbers": out["numbers"],
                "control": out["control"]}
        print(json.dumps(line), flush=True)
        if fault is None:
            for k, v in out["numbers"].items():
                lower[k] = max(lower.get(k, 0.0), v)
        for k, v in ((fault and out["numbers"]) or out["control"] or {}).items():
            key = f"{fault or 'control'}:{k}"
            upper[key] = min(upper.get(key, float("inf")), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
