"""The serving engine's own spans and per-request stamps.

With a profiler trace running, a run of the engine leaves ``repro.serve.*``
spans in the trace: one ``submit`` per request, an ``admit`` (with its
``prefill`` and ``insert``) per admission inside a ``step``, and the decode
block's ``generate`` with its ``wait``. Every request's spans carry its
``uid``, and children open and close inside their parents. Without a
trace the spans record nothing and change no output. ``stats()`` and each
``RequestOutput`` report the queue wait and the time to first token from
host stamps taken at the same boundaries.
"""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import RunConfig, get_config
from repro.models import init_model
from repro.runtime.spans import NAMES
from repro.serve import Request, ServeEngine

RCFG = RunConfig(compute_dtype="float32", param_dtype="float32",
                 policy_name="none")
LENGTHS = (9, 23, 5, 17, 12)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    return cfg, params


def _requests(cfg, uid0=0):
    rng = np.random.default_rng(1)
    return [Request(uid=uid0 + i,
                    tokens=rng.integers(0, cfg.vocab_size, size=n).tolist(),
                    max_new_tokens=3 + i % 3)
            for i, n in enumerate(LENGTHS)]


def _engine(cfg, params):
    # two slots for five requests: later ones wait in the queue
    return ServeEngine(cfg, RCFG, params, max_slots=2, max_len=40,
                       decode_block=4, cache_layout="paged", page_size=8)


def _serve(cfg, params):
    return _engine(cfg, params).run(_requests(cfg))


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """Outputs of a traced run and its ``repro.*`` spans (name, start, end,
    stats)."""
    cfg, params = model
    eng = _engine(cfg, params)
    eng.run(_requests(cfg, uid0=100))          # compile outside the trace
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        out = eng.run(_requests(cfg))
    (xplane,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, spans


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_trace_holds_the_engine_spans_by_uid(traced):
    _, spans = traced
    assert {s[0] for s in spans} == {f"repro.{n}" for n in NAMES}
    uids = list(range(len(LENGTHS)))
    for name in ("repro.serve.submit", "repro.serve.admit", "repro.serve.prefill",
                 "repro.serve.insert"):
        assert sorted(s[3]["uid"] for s in spans if s[0] == name) == uids, name
    admits = [s for s in spans if s[0] == "repro.serve.admit"]
    assert [(a[3]["prompt_len"], a[3]["bucket"]) for a in admits] == [
        (n, 16 if n <= 16 else 32) for n in LENGTHS]


def test_children_open_and_close_inside_their_parents(traced):
    _, spans = traced
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["repro.serve.step"]

    def one_parent(child, parents):
        return sum(_inside(child, p) for p in parents) == 1

    for name in ("repro.serve.admit", "repro.serve.generate", "repro.serve.bookkeep"):
        assert all(one_parent(c, steps) for c in by[name]), name
    for name in ("repro.serve.prefill", "repro.serve.insert"):
        for c in by[name]:
            (parent,) = [a for a in by["repro.serve.admit"] if a[3]["uid"] == c[3]["uid"]]
            assert _inside(c, parent), name
    assert all(one_parent(c, by["repro.serve.generate"])
               for c in by["repro.serve.generate.wait"])
    # submits come before the steps that admit them, and outside any step
    assert not any(_inside(s, st) for s in by["repro.serve.submit"] for st in steps)
    for a in by["repro.serve.admit"]:
        (sub,) = [s for s in by["repro.serve.submit"] if s[3]["uid"] == a[3]["uid"]]
        assert sub[2] <= a[1]


def test_untraced_output_is_the_traced_output(model, traced):
    cfg, params = model
    out, _ = traced
    plain = _serve(cfg, params)
    assert sorted(plain) == sorted(out)
    for uid in plain:
        assert plain[uid].tokens == out[uid].tokens


def test_stats_report_queue_wait_and_time_to_first_token(model):
    cfg, params = model
    eng = _engine(cfg, params)
    out = eng.run(_requests(cfg))
    st = eng.stats()
    for k in ("prefill_s", "insert_s", "decode_tokens", "decode_s",
              "insert_count", "prefill_tokens"):
        assert k in st, k          # read by the benchmark
    assert "p50_token_latency_ms" not in st and "p95_token_latency_ms" not in st
    assert st["insert_count"] == len(LENGTHS)
    assert 0 <= st["queue_wait_ms_p50"] <= st["queue_wait_ms_p90"]
    assert 0 < st["ttft_ms_p50"] <= st["ttft_ms_p90"]
    assert st["ttft_ms_p90"] >= st["queue_wait_ms_p90"]
    waits, ttfts = [], []
    for r in out.values():
        assert not hasattr(r, "decode_s")
        # the first token waits for the queue, its own prefill, and the
        # rest of the step that admitted it
        assert r.ttft_s >= r.queued_s + r.prefill_s
        waits.append(r.queued_s)
        ttfts.append(r.ttft_s)
    # two slots: the last requests wait for earlier ones to finish
    assert max(waits) > min(waits)
    assert st["ttft_ms_p90"] == pytest.approx(1e3 * sorted(ttfts)[4])
    eng.reset_stats()
    assert eng.stats()["ttft_ms_p90"] == 0.0


def test_a_request_admitted_without_the_queue_has_no_stamps(model):
    cfg, params = model
    eng = _engine(cfg, params)
    req = _requests(cfg)[0]
    assert eng.admit_prefix(eng.prefill(eng.params, req), 0) is None
    done = []
    while eng.has_work:
        done += eng.step()
    (r,) = done
    assert r.queued_s is None and r.ttft_s is None
    assert eng.stats()["ttft_ms_p50"] == 0.0
