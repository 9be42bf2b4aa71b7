"""Multi-device parity harness for the shard_map DP x TP executor.

Runs ONLY when more than one device is visible — the intended recipe is

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_multidevice.py -q

(the dedicated `multidevice` CI job does exactly that). On a plain
single-device run every test here auto-skips via the ``multidevice``
marker (tests/conftest.py), so tier-1 timing is untouched.

What is held to parity, per DESIGN.md §5:

  * llama-tiny train-step loss/grad/param parity: 1-device jit executor
    (with its mesh-resolved ``blocks=dp`` shard-local PAMM) vs the
    shard_map executor on (data=4) and (data=2, model=2) meshes, PAMM
    active on attn.qkv — f32 near-exact, because the per-shard
    ``shard_site_key`` derivation reproduces the blocked single-device
    sampling bit-for-bit;
  * int8-EF gradient all-reduce: training tracks the uncompressed run
    within documented tolerance and the error-feedback buffers shrink;
  * ZeRO-1: optimizer moments carry the data axis and equal the
    replicated baseline after gather;
  * compressed_psum / tree_compressed_psum collective semantics under a
    real shard_map (the pure quantize helpers are property-tested in
    test_property_hypothesis.py);
  * serving-engine decode parity on a (data=2) mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.data import SyntheticStream
from repro.launch.mesh import make_debug_mesh
from repro.runtime import sharding as sh
from repro.runtime.grad_compress import (
    allreduce_wire_bytes,
    compressed_psum,
    ef_dequantize,
    ef_quantize,
    tree_compressed_psum,
)
from repro.train import (
    init_distributed_state,
    init_train_state,
    make_shard_map_train_step,
    make_train_step,
)
from repro.train.distributed import shard_site_key

# Most of this file needs >1 device; a few tests (PRNG derivation, error
# paths, byte accounting) are single-device and intentionally UNMARKED so
# tier-1 keeps covering them — e.g. the jit executor's loud grad_compress
# rejection must not regress silently between multidevice CI runs.
multidevice = pytest.mark.multidevice

ARCH = "llama-tiny"
SPEC = "attn.qkv=pamm(r=1/8)"  # blocks=auto -> DP degree of the mesh


def _rcfg(**kw):
    base = dict(compression=SPEC, lr=5e-3, compute_dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return RunConfig(**base)


def _batches(n, *, global_batch=8, seq_len=32, seed=0):
    cfg = get_config(ARCH)
    stream = SyntheticStream.for_arch(cfg, seq_len, global_batch, seed=seed)
    return [
        {k: jnp.asarray(v) for k, v in stream.get_batch(i).items()}
        for i in range(n)
    ]


def _run_jit(rcfg, batches, *, mesh_for_plan, steps=None):
    """Single-device baseline; the mesh only steers plan resolution, so
    ``blocks=auto`` matches the executor under test."""
    cfg = get_config(ARCH)
    state, _ = init_train_state(cfg, rcfg, jax.random.key(rcfg.seed))
    step = jax.jit(make_train_step(
        cfg, rcfg, total_steps=len(batches), mesh=mesh_for_plan))
    metrics = []
    for i, b in enumerate(batches[:steps]):
        state, m = step(state, b, jnp.int32(i))
        metrics.append({k: float(v) for k, v in m.items()})
    return state, metrics


def _run_shard_map(rcfg, batches, *, mesh, steps=None):
    cfg = get_config(ARCH)
    state, _ = init_distributed_state(cfg, rcfg, jax.random.key(rcfg.seed), mesh)
    step = make_shard_map_train_step(
        cfg, rcfg, total_steps=len(batches), mesh=mesh)
    metrics = []
    for i, b in enumerate(batches[:steps]):
        state, m = step(state, b, jnp.int32(i))
        metrics.append({k: float(v) for k, v in m.items()})
    return state, metrics


def _max_tree_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(
            np.asarray(x, np.float32) - np.asarray(y, np.float32)))), a, b)))


# ---------------------------------------------------------------------------
# train-step parity
# ---------------------------------------------------------------------------
@multidevice
@pytest.mark.parametrize("data,model,spec", [
    (4, 1, SPEC),
    (2, 2, SPEC),
    # awkward ratio: ceil(r*b_global)=13 generators, 13 % dp != 0 — the
    # per-shard k must still be the blocked baseline's 13//4=3, not
    # ceil(r*b_shard)=4 (PammPolicy.block_share localization)
    (4, 1, "attn.qkv=pamm(r=1/20)"),
    # k_global=1 < dp: the blocked compress must keep one generator PER
    # block (no global-compress fallback) or the executors diverge
    (4, 1, "attn.qkv=pamm(r=1/256)"),
])
def test_train_step_parity_vs_jit(data, model, spec):
    """shard_map executor == jit executor with blocks=dp, f32 near-exact,
    with PAMM active on attn.qkv — losses, telemetry, and the params after
    three steps (i.e. the synced gradients) all agree."""
    mesh = make_debug_mesh(data, model)
    batches = _batches(3)
    rcfg = _rcfg(compression=spec)
    sj, mj = _run_jit(rcfg, batches, mesh_for_plan=mesh)
    ss, ms = _run_shard_map(rcfg, batches, mesh=mesh)
    for a, b in zip(mj, ms):
        assert a["loss"] == pytest.approx(b["loss"], abs=5e-5)
        assert a["nll"] == pytest.approx(b["nll"], abs=5e-5)
        assert a["grad_norm"] == pytest.approx(b["grad_norm"], rel=5e-5)
    assert _max_tree_diff(sj.params, ss.params) < 5e-4


@multidevice
def test_mesh_shapes_agree_with_each_other():
    """(data=4) and (data=2, model=2) runs agree with exact compression:
    the distributed math (per-shard fwd/bwd, DP pmean, TP collectives,
    ZeRO-1 update) is mesh-shape-independent. (With PAMM active each mesh
    shape samples per ITS dp degree — each is held exactly to its own
    blocked jit baseline in test_train_step_parity_vs_jit instead.)"""
    batches = _batches(3)
    rcfg = _rcfg(compression="", policy_name="none")
    s4, m4 = _run_shard_map(rcfg, batches, mesh=make_debug_mesh(4, 1))
    s22, m22 = _run_shard_map(rcfg, batches, mesh=make_debug_mesh(2, 2))
    for a, b in zip(m4, m22):
        assert a["loss"] == pytest.approx(b["loss"], abs=5e-5)
    assert _max_tree_diff(s4.params, s22.params) < 5e-4


@multidevice
def test_telemetry_aggregated_across_shards():
    """Per-site telemetry is psum'd over shards — global stored bytes and
    kept fraction, not shard-0 numbers — and matches the single-device
    blocked run, whose state has the same total size."""
    mesh = make_debug_mesh(4, 1)
    batches = _batches(1)
    rcfg = _rcfg()
    _, mj = _run_jit(rcfg, batches, mesh_for_plan=mesh)
    _, ms = _run_shard_map(rcfg, batches, mesh=mesh)
    site = "site/stage0.attn.attn.qkv"
    assert ms[0][f"{site}/stored_mb"] == pytest.approx(
        mj[0][f"{site}/stored_mb"], rel=1e-6)
    assert ms[0][f"{site}/kept_frac"] == pytest.approx(1.0)
    assert ms[0][f"{site}/beta"] == pytest.approx(1.0)


def test_shard_site_keys_decorrelated():
    """Each DP shard draws a distinct site stream, and shard s's key is
    exactly block s's key of the blocked single-device derivation."""
    key = jax.random.key(123)
    dp = 4
    keys = [
        jax.random.key_data(shard_site_key(key, 5, dp=dp, shard=s))
        for s in range(dp)
    ]
    for i in range(dp):
        for j in range(i + 1, dp):
            assert not np.array_equal(keys[i], keys[j])
    blocked = jax.random.split(jax.random.fold_in(key, 5), dp)
    for s in range(dp):
        assert np.array_equal(keys[s], jax.random.key_data(blocked[s]))


# ---------------------------------------------------------------------------
# ZeRO-1
# ---------------------------------------------------------------------------
@multidevice
def test_zero1_opt_state_sharded_and_equal():
    mesh = make_debug_mesh(4, 1)
    batches = _batches(2)
    rcfg = _rcfg()
    sj, _ = _run_jit(rcfg, batches, mesh_for_plan=mesh)
    ss, _ = _run_shard_map(rcfg, batches, mesh=mesh)
    # every Adam moment leaf carries the data axis somewhere in its spec
    for leaf in jax.tree.leaves(ss.opt.m) + jax.tree.leaves(ss.opt.v):
        spec_axes = set()
        for entry in tuple(leaf.sharding.spec):
            if entry is None:
                continue
            spec_axes |= set(entry if isinstance(entry, tuple) else (entry,))
        assert "data" in spec_axes, (leaf.shape, leaf.sharding)
    # and after gather the values equal the replicated baseline
    assert _max_tree_diff(sj.opt.m, ss.opt.m) < 1e-6
    assert _max_tree_diff(sj.opt.v, ss.opt.v) < 1e-6


# ---------------------------------------------------------------------------
# int8-EF gradient all-reduce, end to end
# ---------------------------------------------------------------------------
@multidevice
def test_int8_ef_training_tracks_uncompressed():
    mesh = make_debug_mesh(4, 1)
    batches = _batches(16)
    s_ef, m_ef = _run_shard_map(_rcfg(grad_compress="int8_ef"), batches, mesh=mesh)
    s_un, m_un = _run_shard_map(_rcfg(), batches, mesh=mesh)
    # per-step losses stay within the documented tolerance of the
    # uncompressed run (EF re-injects the quantization error next step)
    for a, b in zip(m_ef, m_un):
        assert a["loss"] == pytest.approx(b["loss"], abs=0.08)
    # both still learn
    assert m_ef[-1]["loss"] < m_ef[0]["loss"]


@multidevice
def test_int8_ef_buffers_per_shard_and_shrinking():
    mesh = make_debug_mesh(4, 1)
    batches = _batches(16)
    cfg = get_config(ARCH)
    rcfg = _rcfg(grad_compress="int8_ef")
    state, _ = init_distributed_state(cfg, rcfg, jax.random.key(0), mesh)
    step = make_shard_map_train_step(cfg, rcfg, total_steps=16, mesh=mesh)
    norms = []
    for i, b in enumerate(batches):
        state, _ = step(state, b, jnp.int32(i))
        norms.append(float(jnp.sqrt(sum(
            jnp.sum(e.astype(jnp.float32) ** 2)
            for e in jax.tree.leaves(state.ef)))))
    # EF buffers: (dp, *param) leading axis sharded over data, shard-local
    # residues decorrelated, and the norm trends down as gradients shrink
    leaf = jax.tree.leaves(state.ef)[0]
    assert leaf.shape[0] == 4
    assert "data" in jax.tree.leaves(tuple(leaf.sharding.spec))
    assert not bool(jnp.all(leaf[0] == leaf[1]))
    assert np.mean(norms[-4:]) < np.mean(norms[:4])
    assert norms[-1] < 2.0 * min(norms)  # bounded: EF never blows up


def test_jit_executor_rejects_grad_compress():
    with pytest.raises(ValueError, match="shard_map executor"):
        make_train_step(get_config(ARCH), _rcfg(grad_compress="int8_ef"))


@multidevice
def test_batch_indivisible_raises_clearly():
    mesh = make_debug_mesh(4, 1)
    cfg = get_config(ARCH)
    rcfg = _rcfg()
    state, _ = init_distributed_state(cfg, rcfg, jax.random.key(0), mesh)
    step = make_shard_map_train_step(cfg, rcfg, total_steps=2, mesh=mesh)
    bad = _batches(1, global_batch=6)[0]
    with pytest.raises(ValueError, match="not divisible by the data-parallel"):
        step(state, bad, jnp.int32(0))


# ---------------------------------------------------------------------------
# collective unit tests (the quantize helpers are property-tested already)
# ---------------------------------------------------------------------------
def _dp_mesh(n):
    return make_debug_mesh(n, 1)


@multidevice
def test_compressed_psum_is_mean_of_dequantized():
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh(8)
    g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16, 5)),
                    jnp.float32)
    err = jnp.zeros_like(g)

    def body(g, e):
        out, new_err = compressed_psum(g[0], e[0], "data")
        return out[None], new_err[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
    out, new_err = jax.jit(f)(g, err)
    # every shard got the same mean; it equals the mean of the shard-wise
    # dequantized payloads, which is within quantization error of the true
    # mean, and err holds exactly the per-shard quantization residue
    for s in range(8):
        np.testing.assert_allclose(out[s], out[0], rtol=0, atol=0)
    q_deq = []
    for s in range(8):
        q, scale, e2 = ef_quantize(g[s], jnp.zeros_like(g[s]))
        q_deq.append(ef_dequantize(q, scale))
        np.testing.assert_allclose(new_err[s], e2, atol=1e-5)
    np.testing.assert_allclose(out[0], jnp.mean(jnp.stack(q_deq), 0), atol=1e-6)
    np.testing.assert_allclose(out[0], jnp.mean(g, axis=0), atol=0.05)


@multidevice
def test_tree_compressed_psum_error_feedback_converges():
    """Summed over steps, EF compensates: the accumulated compressed means
    track the accumulated true means much closer than one step's error."""
    from jax.sharding import PartitionSpec as P

    mesh = _dp_mesh(8)
    rng = np.random.default_rng(1)
    tree_steps = [
        {"a": jnp.asarray(rng.standard_normal((8, 7, 3)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((8, 11)), jnp.float32)}
        for _ in range(6)
    ]
    err = jax.tree.map(lambda t: jnp.zeros_like(t), tree_steps[0])

    def body(g, e):
        loc = jax.tree.map(lambda t: t[0], g)
        el = jax.tree.map(lambda t: t[0], e)
        out, ne = tree_compressed_psum(loc, el, "data")
        return (jax.tree.map(lambda t: t[None], out),
                jax.tree.map(lambda t: t[None], ne))

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
    acc = {"a": 0.0, "b": 0.0}
    true = {"a": 0.0, "b": 0.0}
    for g in tree_steps:
        out, err = f(g, err)
        acc = {k: acc[k] + np.asarray(out[k][0]) for k in acc}
        true = {k: true[k] + np.asarray(jnp.mean(g[k], 0)) for k in true}
    for k in acc:
        # accumulated EF error stays at one-step quantization scale even
        # after 6 steps (no drift)
        assert np.max(np.abs(acc[k] - true[k])) < 0.06, k


def test_wire_bytes_accounting():
    shapes = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32),
              "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
    n = 64 * 64 + 64
    assert allreduce_wire_bytes(shapes, 1, "bf16") == 0
    assert allreduce_wire_bytes(shapes, 4, "bf16") == int(2 * 3 / 4 * n * 2)
    assert allreduce_wire_bytes(shapes, 4, "int8_ef") == int(2 * 3 / 4 * (n + 8))
    assert (allreduce_wire_bytes(shapes, 8, "int8_ef")
            < allreduce_wire_bytes(shapes, 8, "bf16") / 1.9)


# ---------------------------------------------------------------------------
# serving on a data-parallel mesh
# ---------------------------------------------------------------------------
@multidevice
def test_serving_decode_parity_dp2():
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("internlm2-1.8b_smoke")
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    from repro.models import init_model

    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    rng = np.random.default_rng(0)

    def run(mesh):
        eng = ServeEngine(cfg, rcfg, params, max_slots=2, max_len=32,
                          mesh=mesh)
        reqs = [
            Request(uid=i,
                    tokens=[int(t) for t in
                            np.random.default_rng(i).integers(
                                1, cfg.vocab_size, size=12)],
                    max_new_tokens=8)
            for i in range(4)
        ]
        return {u: o.tokens for u, o in eng.run(reqs).items()}

    del rng
    base = run(None)
    dp2 = run(make_debug_mesh(2, 1))
    assert base == dp2
    # slot axis really is sharded
    eng = ServeEngine(cfg, rcfg, params, max_slots=2, max_len=32,
                      mesh=make_debug_mesh(2, 1))
    leaf = next(l for l in jax.tree.leaves(eng.caches) if l.ndim > 1)
    assert "data" in jax.tree.leaves(tuple(leaf.sharding.spec))


@multidevice
def test_serving_slots_indivisible_raises():
    from repro.serve.engine import ServeEngine
    from repro.models import init_model

    cfg = get_config("internlm2-1.8b_smoke")
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    with pytest.raises(ValueError, match="max_slots divisible"):
        ServeEngine(cfg, rcfg, params, max_slots=3, max_len=32,
                    mesh=make_debug_mesh(2, 1))


@multidevice
def test_data_axis_helpers():
    mesh = make_debug_mesh(2, 2)
    assert sh.data_axis_names(mesh) == ("data",)
    assert sh.dp_degree(mesh) == 2
    sh.validate_batch_divisible(8, mesh)
    with pytest.raises(ValueError, match="not divisible"):
        sh.validate_batch_divisible(7, mesh, where="test")
    with pytest.raises(ValueError, match="grad_accum"):
        sh.validate_batch_divisible(8, mesh, grad_accum=3, where="test")


# ---------------------------------------------------------------------------
# sharded paged serving: per-replica page pools on the mesh
# ---------------------------------------------------------------------------
def _paged_serve_tokens(mesh, *, compress=None, max_slots=4, pool_tokens=None):
    from repro.models import init_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("internlm2-1.8b_smoke")
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    eng = ServeEngine(cfg, rcfg, params, max_slots=max_slots, max_len=32,
                      decode_block=4, mesh=mesh, cache_layout="paged",
                      page_size=8, pool_tokens=pool_tokens,
                      cache_compress=compress)
    reqs = [
        Request(uid=i,
                tokens=[int(t) for t in np.random.default_rng(i).integers(
                    1, cfg.vocab_size, size=10)],
                max_new_tokens=6)
        for i in range(6)
    ]
    out = {u: o.tokens for u, o in eng.run(reqs).items()}
    return eng, out


@multidevice
@pytest.mark.parametrize("compress", [None, "int8"])
def test_serving_sharded_paged_parity_dp2(compress):
    """Paged (and int8-quantized) pools sharded per replica over a dp=2
    mesh emit token streams identical to the single-host engine, and the
    pool leaves really carry the data axis."""
    _, base = _paged_serve_tokens(None, compress=compress)
    eng, dp2 = _paged_serve_tokens(make_debug_mesh(2, 1), compress=compress)
    assert base == dp2
    # one allocator per pool per replica shard, each budgeting half the pool
    n_pools = len(eng.pool_labels) // eng.n_replicas
    assert eng.n_replicas == 2
    assert len(eng.allocators) == 2 * n_pools
    assert eng.pool_labels[0].startswith("replica0/")
    # pool leaves are sharded on the page axis (shard axis -> data)
    from repro.models.attention import PAGED_CACHE_TYPES
    node = next(n for st in eng.caches for n in st
                if isinstance(n, PAGED_CACHE_TYPES))
    assert "data" in jax.tree.leaves(tuple(node.k_pages.sharding.spec))
    for alloc in eng.allocators:
        alloc.check_invariant()
        assert alloc.free_pages == alloc.spec.n_pages  # fully drained


@multidevice
def test_serving_sharded_paged_dp4_placement():
    """dp=4: admission spreads requests across replica shards (every shard
    serves someone) and token streams still match single-host."""
    _, base = _paged_serve_tokens(None, max_slots=4)
    eng, dp4 = _paged_serve_tokens(make_debug_mesh(4, 1), max_slots=4)
    assert base == dp4
    assert eng.n_replicas == 4
    assert eng.max_slots // eng.n_replicas == 1


@multidevice
def test_serving_paged_pool_indivisible_raises():
    from repro.models import init_model
    from repro.serve.engine import ServeEngine

    cfg = get_config("internlm2-1.8b_smoke")
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    # 3 pages of 8 tokens: not divisible into 2 replica shards
    with pytest.raises(ValueError, match="DP degree"):
        ServeEngine(cfg, rcfg, params, max_slots=2, max_len=32,
                    cache_layout="paged", page_size=8, pool_tokens=24,
                    mesh=make_debug_mesh(2, 1))
