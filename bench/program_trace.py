"""The program's own spans in a traced run, on the device's clock.

The serving engine opens ``repro.serve.*`` spans (``src/repro/runtime/
spans.py``) as ``jax.profiler.TraceAnnotation``s, so with the window traced
they land in the same ``.xplane.pb`` as the device's operations. This module
reduces that file to what the per-layer readers of the engine need:

- the window, from the benchmark's ``bench.window`` span;
- the host spans named ``repro.*`` and ``bench.*``, each with its stats
  (``uid`` on the spans of one request);
- the device's operation intervals (``XLA Ops`` of ``/device:TPU:<n>``).

It finds the trace where ``bench/run.py`` writes it,
``bench_out/trace/<cell>-<pid>``, parses it once per process, and keeps the
result. On a program without these spans every reader gets nothing to read
and returns None.

Device operations carry no scope path here: on a TPU v5e the ``XLA Ops``
events hold the HLO instruction's text and their time, and no ``op_name``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import math
import os

from bench import common
from bench import trace as trace_lib
from bench.kinds.serve import nearest_rank

PREFIXES = ("repro.", "bench.")
STEP = "repro.serve.step"
SUBMIT = "repro.serve.submit"
ADMIT = "repro.serve.admit"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int   # ns
    end: int     # ns
    ids: dict


@dataclasses.dataclass(eq=False)
class ProgramTrace:
    window: tuple[int, int]    # ns
    spans: list[Span]          # repro.* and bench.* host spans, by start
    busy: list[list]           # per device: merged op intervals [start, end] in the window

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def idle(self, start: int, end: int) -> float:
        """Device-idle ns in [start, end] (inside the window), averaged
        over devices."""
        start, end = max(start, self.window[0]), min(end, self.window[1])
        if end <= start:
            return 0.0
        total = 0.0
        for merged in self.busy:
            total += (end - start) - _overlap(merged, start, end)
        return total / len(self.busy)


def _overlap(merged: list, start: int, end: int) -> int:
    """ns of the sorted, disjoint intervals ``merged`` inside [start, end]."""
    i = max(0, bisect.bisect_right(merged, [start]) - 1)
    got = 0
    while i < len(merged) and merged[i][0] < end:
        got += max(0, min(merged[i][1], end) - max(merged[i][0], start))
        i += 1
    return got


def reduce_profile(pd, window_span: str = trace_lib.WINDOW_SPAN) -> ProgramTrace:
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            evs = [(e.start_ns, e.end_ns) for line in plane.lines
                   if line.name in trace_lib.OP_LINES for e in line.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, int(e.start_ns), int(e.end_ns),
                                          dict(e.stats)))
    windows = [s for s in spans if s.name == window_span]
    if not windows:
        raise ValueError(f"trace has no {window_span} span")
    w0, w1 = windows[0].start, windows[0].end
    busy = [[list(iv) for iv in trace_lib._union(
        (max(s, w0), min(e, w1)) for s, e in evs if e > w0 and s < w1)]
        for evs in devices]
    if not busy:
        raise ValueError("trace has no device operations")
    spans.sort(key=lambda s: (s.start, -s.end))
    return ProgramTrace(window=(w0, w1), spans=spans, busy=busy)


@functools.lru_cache(maxsize=1)
def _load(log_dir: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(trace_lib.find_xplane(log_dir)))


def load(ctx: dict) -> ProgramTrace | None:
    """The traced run's program trace, or None where it holds no
    ``repro.serve.step`` span (a program without these spans)."""
    log_dir = str(common.ROOT / "bench_out" / "trace" /
                  f"{ctx['cell']['name']}-{os.getpid()}")
    pt = _load(log_dir)
    return pt if pt.named(STEP) else None


def _in_window(pt: ProgramTrace, s: Span) -> bool:
    return pt.window[0] <= s.start and s.end <= pt.window[1]


@dataclasses.dataclass
class Requests:
    wait_ns: list        # admit start - submit start, per matched request
    hold_ns: list        # admit end -> end of the enclosing step
    left_out: int        # admitted before any matched admission, no submit
                         # span: submitted before the trace began
    unmatched: int       # admissions with no submit span after that point
    never_admitted: int  # submits in the window with no admission in the trace
    repeated: int        # uids with more than one submit, or more than one admission


@functools.lru_cache(maxsize=1)
def requests(pt: ProgramTrace) -> Requests:
    """Per request of the window (its admission inside it), joined on
    ``uid``: the wait in the queue and the hold of its first token. The
    engine admits in FIFO order, so a request submitted before the trace
    began is admitted before any whose submit the trace holds: such
    admissions are left out, and any later one without a submit is a
    fault of the spans, counted as unmatched."""
    submits = {s.ids["uid"]: s for s in pt.named(SUBMIT)}
    admitted = collections.Counter(a.ids["uid"] for a in pt.named(ADMIT))
    steps = pt.named(STEP)
    step_starts = [s.start for s in steps]
    admits = [a for a in pt.named(ADMIT) if _in_window(pt, a)]
    out = Requests([], [], 0, 0, sum(
        1 for s in submits.values() if _in_window(pt, s) and s.ids["uid"] not in admitted),
        len(pt.named(SUBMIT)) - len(submits) + sum(1 for c in admitted.values() if c > 1))
    seen_match = False
    for a in admits:
        sub = submits.get(a.ids["uid"])
        if sub is None:
            if seen_match:
                out.unmatched += 1
            else:
                out.left_out += 1
            continue
        seen_match = True
        out.wait_ns.append(a.start - sub.start)
        i = bisect.bisect_right(step_starts, a.start) - 1
        if i >= 0 and steps[i].end >= a.end and steps[i].end <= pt.window[1]:
            out.hold_ns.append(steps[i].end - a.end)
    common.log(f"program trace: {len(admits)} admissions in the window, "
               f"{len(out.wait_ns)} matched to their submit, {out.left_out} left out "
               f"(submitted before the trace), {out.unmatched} unmatched, "
               f"{out.never_admitted} submits with no admission in the trace, "
               f"{out.repeated} uids repeated")
    return out


def _innermost(spans: list, t: float) -> str:
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name


def step_idle(pt: ProgramTrace) -> tuple[float, int, dict]:
    """Device-idle ns inside the ``repro.serve.step`` spans of the window:
    (mean per step, steps, idle ns by the innermost ``repro.serve.*`` span
    open over it). The idle time of a step is the host's own time in it."""
    steps = [s for s in pt.named(STEP) if _in_window(pt, s)]
    serve = [s for s in pt.spans if s.name.startswith("repro.serve.")]
    starts = [s.start for s in serve]
    by_span: dict = {}
    total = 0.0
    for st in steps:
        inner = serve[bisect.bisect_left(starts, st.start):
                      bisect.bisect_left(starts, st.end)]
        inner = [s for s in inner if s.end <= st.end]
        edges = sorted({st.start, st.end} | {x for s in inner for x in (s.start, s.end)})
        for a, b in zip(edges, edges[1:]):
            gap = pt.idle(a, b)
            if gap:
                name = _innermost(inner, (a + b) / 2)
                by_span[name] = by_span.get(name, 0.0) + gap
                total += gap
    return (total / len(steps) if steps else math.nan), len(steps), by_span


def engine_step_share(pt: ProgramTrace) -> float:
    """Share of the device-idle time inside the benchmark's
    ``bench.engine_step`` spans that a ``repro.serve.step`` span covers."""
    outer = [s for s in pt.named("bench.engine_step") if _in_window(pt, s)]
    steps = pt.named(STEP)
    starts = [s.start for s in steps]
    idle = covered = 0.0
    for o in outer:
        idle += pt.idle(o.start, o.end)
        for s in steps[bisect.bisect_left(starts, o.start):bisect.bisect_left(starts, o.end)]:
            covered += pt.idle(s.start, min(s.end, o.end))
    return covered / idle if idle else math.nan


def step_idle_ms(ctx: dict) -> float | None:
    """The ``step_idle_ms.<cell>`` readers: mean device-idle ms per engine
    step, logged by innermost span."""
    pt = load(ctx)
    if pt is None:
        return None
    mean_ns, n, by_span = step_idle(pt)
    total = sum(by_span.values())
    common.log(f"program trace: {n} engine steps in the window, device idle "
               f"{total * 1e-9:.6f} s inside them, " + ", ".join(
                   f"{k} {v * 1e-9:.6f} s" for k, v in
                   sorted(by_span.items(), key=lambda kv: -kv[1])))
    common.log(f"program trace: {100 * engine_step_share(pt):.3f}% of the device idle "
               f"time inside bench.engine_step lies inside {STEP}")
    return mean_ns * 1e-6 if n else None
