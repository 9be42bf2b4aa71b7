"""Shared pieces of the benchmark: locating a cell's files by name, the
device check, host spans, the compile counter and the comparison rule."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def resolve_cell(name: str, man: dict | None = None) -> dict:
    """Everything one cell needs, found by name: its manifest entry, the
    configuration file, the traffic file and the cell's own settings."""
    man = man or manifest()
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; have "
                         f"{[w['name'] for w in man['workloads']]}")
    cfg_entry = next(c for c in man["configs"] if c["name"] == entry["config"])
    cell = dict(entry)
    cell["config_file"] = load_json(ROOT / cfg_entry["file"])
    cell["traffic_file"] = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    cell["settings"] = load_json(BENCH / "workloads" / f"{name}.json")
    cell["end_to_end"] = [m for m in man["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in man["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def tpu_devices(count: int):
    """The TPU devices, asked for by backend name: never the CPU."""
    import jax

    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"bench: no TPU: {e}")
    if len(devices) < count:
        raise NoChip(f"bench: the cell needs {count} TPU chips, found {len(devices)}")
    return devices[:count]


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


_MODULES: dict = {}


def peaks(device_kind: str) -> dict:
    """Published peak rates of one chip of this kind (bench/peaks.json);
    a kind that is not in the table is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def load_module(package: str, name: str):
    """``bench/<package>/<name>.py``, loaded once (file names may hold '-'
    and '.', which an import statement cannot name)."""
    if (package, name) in _MODULES:
        return _MODULES[package, name]
    path = BENCH / package / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{package}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[package, name] = mod
    return mod


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Spans:
    """Host spans around the benchmark's own calls into the program. With
    tracing on, each is a ``jax.profiler.TraceAnnotation``, so the trace
    reduction can name what the host was doing in a device idle gap."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.enabled:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield


class CompileCounter:
    """Counts backend compiles and persistent-cache loads: a window that
    triggers either has met a shape set-up did not warm."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False

        def listener(event, duration, **_):
            if self.armed and ("backend_compile" in event
                               or "cache_retrieval" in event):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def worst_leaf_gap(prog: dict, ref: dict, skip=frozenset()) -> float:
    """Largest |prog[k] - ref[k]| over the leaves, each against the larger of
    ref[k] and the median of ref: the gap of two norms, leaf by leaf."""
    med = statistics.median(float(v) for v in ref.values())
    worst = 0.0
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(float(prog[k]) - float(r)) / max(float(r), med, 1e-30)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def kernel_roofline(ctx: dict, kernel: str) -> float | None:
    """Percent of a kernel's roofline: the least time the chip could take
    for the calls the traced window made (the larger of FLOPs over peak
    FLOP/s and bytes over peak bandwidth, by bench/kernels/<kernel>.py),
    over the kernel's summed device time. None where the cell makes no
    such call; a kernel the cell calls but the trace lacks is an error."""
    calls = ctx.get("kernel_calls", {}).get(kernel)
    if not calls:
        return None
    seconds, count = ctx["trace"].kernel_seconds(kernel)
    if count == 0:
        raise ValueError(f"kernel {kernel!r} is not in the trace")
    if count != len(calls):
        raise ValueError(f"kernel {kernel!r}: {count} events in the trace, "
                         f"{len(calls)} calls counted")
    cost = load_module("kernels", kernel).cost
    flops = byts = 0
    for c in calls:
        f, b = cost(**c)
        flops += f
        byts += b
    pk = ctx["peaks"]
    t_flops = flops / pk["bf16_flops_per_s"]
    t_bytes = byts / pk["hbm_bytes_per_s"]
    log(f"{kernel}: {count} calls, {seconds:.6f} device s, bound by "
        f"{'FLOPs' if t_flops >= t_bytes else 'bytes'}")
    return 100.0 * max(t_flops, t_bytes) / seconds


def mfu(ctx: dict) -> float:
    """Model FLOPs of the traced window's work, over its length, over the
    chips' peak, in percent."""
    pk = ctx["peaks"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return 100.0 * ctx["model_flops"] / ctx["trace"].window_s / pk


def idle(ctx: dict) -> float:
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def now() -> float:
    return time.perf_counter()
