"""Reduction of a profiler trace to the numbers the per-layer readers use.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per operation run on the device. Host
spans are the benchmark's own ``TraceAnnotation``s (``bench.<name>``); the
traced window is the span ``bench.window``.

Out of a trace comes a :class:`Reduced`: busy seconds (the union of the
device's op intervals inside the window, averaged over devices), the window
length, the summed device seconds of each op name, and the device's idle
gaps, each named by the innermost host span open at its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

OP_LINES = ("XLA Ops",)
# ops that contain other ops (a scan's loop): busy, but not ops of their own
CONTAINERS = ("while", "conditional", "call")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str    # the HLO instruction's name, e.g. "flash_fwd.13", "fusion.462"
    start: int   # ns
    end: int     # ns


def op_name(event_name: str) -> str:
    """A device op event is named by its HLO text,
    ``%flash_fwd.13 = (...) custom-call(...)``: keep the instruction's own
    name (a Pallas kernel's is its ``name=`` with a numeric suffix)."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def base_name(op: str) -> str:
    """``flash_fwd.13`` -> ``flash_fwd``."""
    return re.sub(r"\.\d+$", "", op)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: dict            # op name -> device seconds, summed over devices
    op_count: dict        # op name -> events
    idle_by_span: dict    # host span -> idle seconds, averaged over devices

    def kernel_seconds(self, kernel: str) -> tuple[float, int]:
        """Device seconds and events of a Pallas kernel, found by its stable
        name, per device (summed over devices, then divided, like busy)."""
        total, count = 0.0, 0
        for name, s in self.op_s.items():
            if base_name(name) == kernel:
                total += s
                count += self.op_count[name]
        return total / self.n_devices, count // self.n_devices

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (grouped by HLO op name less
        its numeric suffix) and the idle time by host span."""
        by_base = collections.Counter()
        for k, v in self.op_s.items():
            by_base[base_name(k)] += v / self.n_devices
        ops = by_base.most_common(top)
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(pd, window_span: str = WINDOW_SPAN) -> Reduced:
    host_spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            evs = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    evs.append(Event(op_name(e.name), e.start_ns, e.end_ns))
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.start_ns, e.end_ns, e.name))
    windows = [s for s in host_spans if s[2] == window_span]
    if not windows:
        raise ValueError(f"trace has no {window_span} span")
    w0, w1 = windows[0][0], windows[0][1]
    spans = sorted(s for s in host_spans if s[2] != window_span)
    starts = [s[0] for s in spans]

    def innermost(t):
        """The shortest span open at ``t`` (spans nest; siblings do not
        overlap, so the candidates are the last few that began before t)."""
        best = None
        for s in reversed(spans[max(0, bisect.bisect_right(starts, t) - 64):
                                bisect.bisect_right(starts, t)]):
            if s[0] <= t < s[1] and (best is None or s[1] - s[0] < best[1] - best[0]):
                best = s
        return best[2] if best else "no span"
    devices = [d for d in devices if d]
    if not devices:
        raise ValueError("trace has no device operations")

    op_s = collections.Counter()
    op_count = collections.Counter()
    busy = 0.0
    idle = collections.Counter()
    for evs in devices:
        inside = [e for e in evs if e.end > w0 and e.start < w1]
        for e in inside:
            if base_name(e.name) in CONTAINERS:
                continue
            op_s[e.name] += (min(e.end, w1) - max(e.start, w0)) * 1e-9
            op_count[e.name] += 1
        merged = _union((max(e.start, w0), min(e.end, w1)) for e in inside)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            idle[innermost((gs + ge) / 2)] += (ge - gs) * 1e-9
    n = len(devices)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy / n, n_devices=n,
                   op_s=dict(op_s), op_count=dict(op_count),
                   idle_by_span={k: v / n for k, v in idle.items()})


def reduce_dir(log_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(log_dir)))
