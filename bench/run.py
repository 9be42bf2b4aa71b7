#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine's TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json; its configuration, traffic
and settings are files found by name under bench/. The run fails (non-zero
exit, no result) where JAX finds no TPU or fewer chips than the cell needs.
It sets up (weights from the seed, every shape the cell uses compiled or
loaded from the compile cache), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON line
last on standard output. With ``--trace 0`` that line holds the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and it holds the
per-layer metrics, read by bench/metrics/<metric>.py from the trace and the
program's counters.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop cells: override the traffic file's rate "
                         "(for finding the knee; never used by a check)")
    return ap.parse_args(argv)


def kind_run(cell: dict, args, devices, window_open=lambda: None,
             window_close=lambda: None) -> dict:
    """The cell's kind (bench/kinds/<kind>.py) driving one run."""
    kind = common.load_module("kinds", cell["traffic_file"]["kind"])
    spans = common.Spans(bool(args.trace))
    counter = common.CompileCounter()
    out = kind.run(cell, args, devices, spans, counter, window_open, window_close)
    if counter.count:
        common.log(f"{counter.count} compile(s) inside the window")
    out["compiles_in_window"] = counter.count
    return out


def run_cell(cell: dict, args, devices) -> dict:
    """Everything of a run after the chip check: returns the result line."""
    import jax

    trace_dir = str(ROOT / "bench_out" / "trace" / f"{args.workload}-{os.getpid()}")
    marks = {}

    def window_open():
        marks["setup_s"] = time.perf_counter() - T_START
        if args.trace:
            jax.profiler.start_trace(trace_dir)

    def window_close():
        if args.trace:
            jax.profiler.stop_trace()

    out = kind_run(cell, args, devices, window_open, window_close)
    checks = out["checks"]
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    device = common.device_record(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        from bench import trace as trace_lib

        reduced = trace_lib.reduce_dir(trace_dir)  # kept on disk if this raises
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = dict(out["ctx"], trace=reduced, cell=cell, device=device,
                   peaks=common.peaks(device["kind"]))
        for m in cell["per_layer"]:
            value = common.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = reduced.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = dict(out["e2e"], setup_s=marks["setup_s"])
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["compiles_in_window"] = out["compiles_in_window"]
    result["checked"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        common.log(f"check {name} {v!r} limit {lim!r}")
    return result


def compile_cache() -> str:
    """JAX's persistent compile cache in the checkout (the program's own
    choice of directory), holding every program however quick to compile,
    so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def main(argv=None) -> int:
    args = parse(argv)
    cell = common.resolve_cell(args.workload)
    if args.rate is not None:
        cell["traffic_file"] = dict(cell["traffic_file"], rate_per_s=args.rate)
    try:
        devices = common.tpu_devices(cell["chips"])
    except common.NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    common.log(f"compile cache {compile_cache()}; device "
               f"{common.device_record(devices)}")
    result = run_cell(cell, args, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
