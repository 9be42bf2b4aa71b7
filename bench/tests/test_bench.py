"""CPU tests of the benchmark's yardstick: trace reduction, kernel FLOP and
byte counts, traffic, the lookup of every file by name, and the refusal to
run without a TPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, traffic  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _xspace(device_events, host_events):
    """An XSpace text proto: one TPU plane and one host plane, each event
    (name, start_ns, end_ns)."""
    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                       for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
                f"timestamp_ns: 0 {evs} }} {meta} }}\n")

    return plane(1, "/device:TPU:0", "XLA Ops", device_events) + \
        plane(2, "/host:CPU", "python", host_events)


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    dev = [("flash_fwd", 100, 300), ("fusion.1", 250, 400),   # overlap
           ("flash_fwd", 600, 700), ("fusion.2", 900, 1000),
           ("fusion.9", 1500, 1600)]                           # past the window
    host = [("bench.window", 100, 1100), ("bench.engine_step", 100, 750),
            ("bench.submit", 750, 1100)]
    return trace_lib.reduce_profile(ProfileData.from_text_proto(_xspace(dev, host)))


def test_trace_busy_is_the_union_of_op_intervals(reduced):
    assert reduced.window_s == pytest.approx(1000e-9)
    # [100, 400] + [600, 700] + [900, 1000] = 500 ns busy
    assert reduced.busy_s == pytest.approx(500e-9)


def test_trace_kernel_time_sums_its_events(reduced):
    seconds, count = reduced.kernel_seconds("flash_fwd")
    assert count == 2
    assert seconds == pytest.approx(300e-9)
    assert reduced.kernel_seconds("paged_decode") == (0.0, 0)


def test_trace_idle_gaps_are_named_by_the_host_span(reduced):
    # gaps [400, 600] in engine_step, [700, 900] and [1000, 1100] in submit
    assert reduced.idle_by_span["bench.engine_step"] == pytest.approx(200e-9)
    assert reduced.idle_by_span["bench.submit"] == pytest.approx(300e-9)
    bd = reduced.breakdown()
    assert bd["idle_gaps"][0][0] == "bench.submit"
    assert [n for n, _ in bd["device_ops"]][0] == "flash_fwd"


def test_kernel_roofline_refuses_a_kernel_missing_from_the_trace(reduced):
    ctx = {"trace": reduced, "peaks": common.peaks("TPU v5 lite"),
           "kernel_calls": {"paged_decode": [dict(ctx=[10], H=16, KV=8, dh=128)]}}
    with pytest.raises(ValueError, match="not in the trace"):
        common.kernel_roofline(ctx, "paged_decode")
    assert common.kernel_roofline(dict(ctx, kernel_calls={}), "paged_decode") is None


# --- kernel FLOPs and bytes, against counts by hand ------------------------
ATTN = dict(B=2, Lq=4, Lk=4, H=4, KV=2, dh=8, causal=True, itemsize=2)


def test_flash_costs_by_hand():
    pairs = 1 + 2 + 3 + 4        # causal 4 x 4
    fwd = common.load_module("kernels", "flash_fwd").cost(**ATTN)
    assert fwd == (2 * 4 * 8 * 2 * 2 * pairs,
                   2 * 2 * (2 * 4 * 4 * 8 + 2 * 4 * 2 * 8) + 4 * 2 * 4 * 4)
    dq = common.load_module("kernels", "flash_dq").cost(**ATTN)
    assert dq[0] == 3 * 2 * 2 * 4 * 8 * pairs
    dkv = common.load_module("kernels", "flash_dkv").cost(**ATTN)
    assert dkv[0] == 4 * 2 * 2 * 4 * 8 * pairs
    full = common.load_module("kernels", "flash_fwd").cost(**dict(ATTN, causal=False))
    assert full[0] == 2 * 4 * 8 * 2 * 2 * 16


def test_pamm_and_decode_costs_by_hand():
    assert common.load_module("kernels", "pamm_compress").cost(b=16, n=8, k=2) == (
        2 * 16 * 2 * 8 + 2 * 16 * 8, 2 * (16 * 8 + 2 * 8) + 12 * 16)
    assert common.load_module("kernels", "pamm_apply").cost(b=16, m=8, k=2) == (
        2 * 16 * 8, 2 * 16 * 8 + 8 * 16 + 4 * 2 * 8)
    f, b = common.load_module("kernels", "paged_decode").cost(ctx=[3, 5], H=4, KV=2, dh=8)
    assert f == 4 * 4 * 8 * 8
    assert b == 2 * (2 * 2 * 8 * 8 + 2 * 2 * 4 * 8) + 4 * 8


def test_model_flops_by_hand():
    fam = common.load_module("families", "dense_decoder")
    cfg = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
               intermediate_size=16, num_hidden_layers=3, vocab_size=10)
    per_layer = 8 * 4 * 4 + 8 * 8 + 3 * 8 * 16
    assert fam.matmul_flops_per_token(cfg) == 2 * (3 * per_layer + 80)
    assert fam.attention_flops(cfg, 5) == 4 * 3 * 8 * 5
    assert fam.train_flops_per_token(cfg, 7) == 3 * (
        fam.matmul_flops_per_token(cfg) + fam.attention_flops(cfg, 4))


# --- traffic ---------------------------------------------------------------
@pytest.mark.parametrize("name", ["chat-poisson", "decode-heavy"])
def test_traffic_same_for_a_seed_other_order_for_another(name):
    t = common.load_json(common.BENCH / "traffic" / f"{name}.json")
    a = traffic.requests(t, 300, 2**31 + 5, 1000)
    b = traffic.requests(t, 300, 2**31 + 5, 1000)
    c = traffic.requests(t, 300, 7, 1000)
    key = lambda rs: [(r.due, r.max_new, r.prompt.tolist()) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed gets the same set of sizes (and gaps), in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    lo, hi = t["prompt"]["min"], t["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    if t["loop"] == "open":
        assert abs(a[-1].due - c[-1].due) < 1e-6   # the same gaps, summed


def test_lognormal_quantiles_have_the_median():
    v = traffic.quantile_values({"dist": "lognormal", "median": 100, "sigma": 1.0,
                                 "min": 1, "max": 10**6}, 1001)
    assert np.median(v) == 100


def test_train_batch_is_from_the_seed():
    import jax

    k = jax.random.key(3)
    a = traffic.train_batch(k, 2, batch=2, seq_len=8, vocab=50)
    b = traffic.train_batch(k, 2, batch=2, seq_len=8, vocab=50)
    c = traffic.train_batch(k, 3, batch=2, seq_len=8, vocab=50)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()


# --- files found by name -----------------------------------------------------
MAN = common.manifest()


def test_manifest_has_the_contract_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    names = [c["name"] for c in MAN["configs"]] + [w["name"] for w in MAN["workloads"]]
    names += [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", [w["name"] for w in MAN["workloads"]])
def test_every_cell_resolves_by_name(name):
    cell = common.resolve_cell(name)
    cfg = cell["config_file"]
    entry = next(c for c in MAN["configs"] if c["name"] == cell["config"])
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert (common.BENCH / "reference" / f"{cfg['family']}.py").exists()
    assert (common.BENCH / "families" / f"{cfg['family']}.py").exists()
    assert (common.BENCH / "kinds" / f"{cell['traffic_file']['kind']}.py").exists()
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert m["moves"] in reported
        assert callable(common.load_module("metrics", m["name"]).read)
    for k in cell["settings"].get("kernels", []):
        assert callable(common.load_module("kernels", k).cost)
    assert all(v is not None for v in cell["settings"]["limits"].values())


def test_configs_state_their_published_sizes():
    for c in MAN["configs"]:
        cfg = common.load_json(common.ROOT / c["file"])
        for k in cfg["reduced"]:
            assert k in cfg["published"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])


def test_peaks_table_refuses_an_unknown_device():
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")


# --- no chip, no result ------------------------------------------------------
def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(common.BENCH / "run.py"), "--workload",
                        MAN["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""
