"""Unit tests for the trip-count-aware HLO cost analyzer (roofline input)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost


def compile_fn(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_matches_xla_on_loop_free_graph():
    def f(a, b):
        return jax.nn.relu(a @ b) @ b.T

    comp = compile_fn(
        f,
        jax.ShapeDtypeStruct((256, 512), jnp.float32),
        jax.ShapeDtypeStruct((512, 1024), jnp.float32),
    )
    xla = hlo_cost.xla_cost_analysis(comp)
    mine = hlo_cost.analyze(comp.as_text())
    # dots dominate; elementwise flops are the only divergence
    assert abs(mine["flops"] - xla["flops"]) / xla["flops"] < 0.01
    assert abs(mine["bytes"] - xla["bytes accessed"]) / xla["bytes accessed"] < 0.05


@pytest.mark.parametrize("n", [3, 7])
def test_scan_flops_scale_with_trip_count(n):
    def g(ws, x):
        def body(c, w):
            return jax.nn.relu(c @ w @ w.T), None
        c, _ = jax.lax.scan(body, x, ws)
        return c

    comp = compile_fn(
        g,
        jax.ShapeDtypeStruct((n, 16, 128), jnp.float32),
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
    )
    mine = hlo_cost.analyze(comp.as_text())
    expected = n * 2 * (2 * 8 * 16 * 128)  # two (8,16)x(16,128)-ish dots per layer
    assert mine["flops"] == expected
    assert mine["unknown_trip_count_loops"] == 0


def test_collectives_counted_inside_loops():
    import os
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single-device mesh: use psum via shard_map to force an all-reduce
    from jax.sharding import PartitionSpec as PS

    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(1, 1)

    def f(xs):
        def body(c, x):
            y = jax.shard_map(lambda t: jax.lax.psum(t, "data"), mesh=mesh,
                          in_specs=PS("data"), out_specs=PS())(x)
            return c + jnp.sum(y), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((4, 8), jnp.float32)).compile()
    mine = hlo_cost.analyze(comp.as_text())
    # on a 1-device mesh XLA may elide the all-reduce; accept either but the
    # parser must not crash and must return the full structure
    assert set(mine["coll_bytes"]) == set(hlo_cost.COLLECTIVES)


def test_parser_handles_tuple_types():
    text = """
HloModule test

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %y = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4,4]) tuple(%i2, %y)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(6)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,4]) tuple(%z, %a)
  %w = (s32[], f32[4,4]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"6"}}
  ROOT %out = f32[4,4]{1,0} get-tuple-element(%w), index=1
}
"""
    mine = hlo_cost.analyze(text)
    assert mine["flops"] == 6 * 2 * 4 * 4 * 4  # 6 trips x (2*M*N*K)


def test_collective_bytes_from_symbol_table():
    text = """
HloModule test

ENTRY %main (a: f32[128,8]) -> f32[128,8] {
  %a = f32[128,8]{1,0} parameter(0)
  ROOT %ar = f32[128,8]{1,0} all-reduce(%a), replica_groups={}, to_apply=%add
}
"""
    mine = hlo_cost.analyze(text)
    assert mine["coll_bytes"]["all-reduce"] == 128 * 8 * 4
    assert mine["coll_counts"]["all-reduce"] == 1


def test_roofline_terms_math():
    from repro.configs import get_config
    from repro.launch.dryrun import roofline_terms

    cfg = get_config("internlm2-1.8b")
    t = roofline_terms(cfg, flops_per_dev=197e12, bytes_per_dev=819e9,
                       coll_bytes_per_dev=50e9, seq_len=4096, global_batch=256,
                       mode="train", n_chips=256)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["dominant"] in ("compute_s", "memory_s", "collective_s")


def test_cell_runnable_skips():
    from repro.configs import get_config
    from repro.launch.dryrun import cell_runnable

    assert cell_runnable(get_config("qwen2-72b"), "long_500k")[0] is False
    assert cell_runnable(get_config("mamba2-370m"), "long_500k")[0] is True
    assert cell_runnable(get_config("h2o-danube-3-4b"), "long_500k")[0] is True
    assert cell_runnable(get_config("qwen2-72b"), "train_4k")[0] is True
