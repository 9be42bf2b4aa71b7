"""CPU tests of bench/program_trace.py on synthetic traces: the window's
clipping, the join of submits and admissions on ``uid`` (a request
submitted before the trace began among them), the hold of a first token,
and the device-idle time inside an engine step by innermost span.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_trace  # noqa: E402


def _xspace(device_events, host_events):
    """An XSpace text proto: one TPU plane with ``XLA Ops`` events (name,
    start_ns, end_ns) and one host plane whose events (name, start_ns,
    end_ns, {stat: int}) carry integer stats."""
    def metadata(names):
        ids = {n: i + 1 for i, n in enumerate(sorted(set(names)))}
        return ids, "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                            for n, i in ids.items())

    dev_ids, dev_meta = metadata(e[0] for e in device_events)
    dev = "".join(f"events {{ metadata_id: {dev_ids[n]} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in device_events)
    host_ids, host_meta = metadata(e[0] for e in host_events)
    stat_ids = {k: i + 1 for i, k in enumerate(sorted({k for e in host_events for k in e[3]}))}
    host = "".join(
        f"events {{ metadata_id: {host_ids[n]} offset_ps: {s * 1000} duration_ps: "
        f"{(e - s) * 1000} " + "".join(f"stats {{ metadata_id: {stat_ids[k]} int64_value: {v} }} "
                                        for k, v in st.items()) + "}\n"
        for n, s, e, st in host_events)
    stat_meta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}\n'
                        for k, i in stat_ids.items())
    return (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 name: "XLA Ops" '
            f"timestamp_ns: 0 {dev} }} {dev_meta} }}\n"
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: "python" '
            f"timestamp_ns: 0 {host} }} {host_meta} {stat_meta} }}\n")


def _trace(device_events, host_events):
    from jax.profiler import ProfileData

    return program_trace.reduce_profile(
        ProfileData.from_text_proto(_xspace(device_events, host_events)))


# Window [1000, 3000]. Request 1 was submitted before the trace began and is
# admitted in the first step; requests 2 and 3 are submitted in the window,
# 2 is admitted in the second step, 3 in the third, which the window cuts
# off; request 4 is submitted and never admitted.
HOST = [
    ("bench.window", 1000, 3000, {}),
    ("repro.serve.step", 1000, 1400, {}),
    ("repro.serve.admit", 1000, 1200, {"uid": 1, "prompt_len": 10, "bucket": 16}),
    ("repro.serve.prefill", 1000, 1150, {"uid": 1}),
    ("repro.serve.insert", 1150, 1200, {"uid": 1}),
    ("repro.serve.generate", 1200, 1400, {"steps": 8, "active": 1}),
    ("repro.serve.generate.wait", 1250, 1400, {}),
    ("repro.serve.submit", 1450, 1460, {"uid": 2}),
    ("repro.serve.submit", 1470, 1480, {"uid": 3}),
    ("bench.engine_step", 1500, 2100, {}),
    ("repro.serve.step", 1505, 2100, {}),
    ("repro.serve.admit", 1600, 1700, {"uid": 2, "prompt_len": 20, "bucket": 32}),
    ("repro.serve.prefill", 1600, 1690, {"uid": 2}),
    ("repro.serve.generate", 1700, 2000, {"steps": 8, "active": 2}),
    ("repro.serve.bookkeep", 2000, 2100, {}),
    ("repro.serve.submit", 2200, 2210, {"uid": 4}),
    ("repro.serve.step", 2900, 3200, {}),
    ("repro.serve.admit", 2900, 3100, {"uid": 3, "prompt_len": 20, "bucket": 32}),
]
# busy: [1000, 1100], [1250, 1400] in step 1; [1650, 1690], [1700, 1950] in
# step 2; an op that runs past the window's end
DEVICE = [("flash_fwd.1", 900, 1100), ("paged_decode.1", 1250, 1400),
          ("flash_fwd.2", 1650, 1690), ("paged_decode.2", 1700, 1950),
          ("fusion.1", 2950, 3500)]


@pytest.fixture(scope="module")
def pt():
    return _trace(DEVICE, HOST)


def test_window_clips_the_device_ops(pt):
    assert pt.window == (1000, 3000)
    assert pt.busy == [[[1000, 1100], [1250, 1400], [1650, 1690], [1700, 1950],
                        [2950, 3000]]]
    assert pt.idle(900, 1300) == 100 + 150 - 100   # [1100, 1250] and the clip at 1000
    assert pt.idle(2950, 3300) == 0


def test_submits_and_admissions_join_on_uid(pt):
    r = program_trace.requests(pt)
    # uid 1 has no submit span and is admitted before any matched request:
    # submitted before the trace began; uid 3's admission ends past the
    # window; uid 4 is never admitted
    assert r.wait_ns == [1600 - 1450]
    assert r.left_out == 1 and r.unmatched == 0
    assert r.never_admitted == 1 and r.repeated == 0
    assert r.hold_ns == [2100 - 1700]


def test_an_admission_without_its_submit_after_a_matched_one_is_unmatched():
    host = HOST + [("repro.serve.step", 2300, 2600, {}),
                   ("repro.serve.admit", 2300, 2400, {"uid": 9, "prompt_len": 5,
                                                     "bucket": 16})]
    r = program_trace.requests(_trace(DEVICE, host))
    assert (r.left_out, r.unmatched, r.repeated) == (1, 1, 0)


def test_a_uid_submitted_or_admitted_twice_is_counted():
    host = HOST + [("repro.serve.submit", 2220, 2230, {"uid": 4}),
                   ("repro.serve.step", 2300, 2600, {}),
                   ("repro.serve.admit", 2300, 2400, {"uid": 2, "prompt_len": 20,
                                                     "bucket": 32})]
    assert program_trace.requests(_trace(DEVICE, host)).repeated == 2


def test_step_idle_is_split_by_the_innermost_span(pt):
    mean, n, by_span = program_trace.step_idle(pt)
    # steps inside the window: [1000, 1400] and [1505, 2100]; the third ends
    # past it
    assert n == 2
    assert by_span == {
        "repro.serve.prefill": 50 + 50,           # [1100, 1150], [1600, 1650]
        "repro.serve.insert": 50,                 # [1150, 1200]
        "repro.serve.generate": 50 + 50,          # [1200, 1250], [1950, 2000]
        "repro.serve.step": 95,                   # [1505, 1600]
        "repro.serve.admit": 10,                  # [1690, 1700]
        "repro.serve.bookkeep": 100,              # [2000, 2100]
    }
    assert mean == pytest.approx(sum(by_span.values()) / 2)


def test_engine_step_idle_lies_under_the_program_step(pt):
    # bench.engine_step [1500, 2100] idles 5 ns before repro.serve.step opens
    idle_outer = 600 - 40 - 250
    assert program_trace.engine_step_share(pt) == pytest.approx((idle_outer - 5) / idle_outer)


def test_readers_find_nothing_in_a_program_without_spans():
    host = [h for h in HOST if not h[0].startswith("repro.")]
    pt = _trace(DEVICE, host)
    assert pt.named(program_trace.STEP) == []
    assert program_trace.step_idle(pt)[1] == 0
