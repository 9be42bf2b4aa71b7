"""Ring context-parallel attention: zigzag layout units, pair-liveness
truths, config gates (single-device), and — under the forced-8-device
harness (the ``multidevice`` CI job) — ring == single-device parity for
forward and grad-of-sum across {GQA, MQA} x {causal, SWA}, both the jnp
pair reference and the offset Pallas kernels, plus shard_map-executor
train-step parity vs the jit executor at dp x cp in {1x2, 2x2, 1x4}.

SWA windows here deliberately SPAN the zigzag shard seams (window larger
than a chunk, smaller than the shard) — the regression the global
position offsets exist for.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.data import SyntheticStream
from repro.kernels.flash_attention import NEG_INF, flash_attention
from repro.kernels.ring_attention import (
    _merge,
    ring_attention,
    ring_pair_live,
    zigzag_inverse_permutation,
    zigzag_permutation,
    zigzag_shard_positions,
)
from repro.launch.mesh import make_debug_mesh
from repro.runtime import sharding as sh
from repro.train import make_shard_map_train_step, make_train_step

multidevice = pytest.mark.multidevice

ARCH = "llama-tiny"


# ---------------------------------------------------------------------------
# zigzag layout (single device)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,cp", [(16, 2), (64, 4), (96, 2), (128, 8)])
def test_zigzag_permutation_roundtrip(L, cp):
    perm = zigzag_permutation(L, cp)
    inv = zigzag_inverse_permutation(L, cp)
    assert sorted(perm.tolist()) == list(range(L))
    np.testing.assert_array_equal(perm[inv], np.arange(L))
    np.testing.assert_array_equal(inv[perm], np.arange(L))


@pytest.mark.parametrize("L,cp", [(16, 2), (64, 4)])
def test_zigzag_shard_positions_match_permutation(L, cp):
    # shard i's contiguous slice of the permuted sequence sits at exactly
    # the global positions zigzag_shard_positions reports
    perm = zigzag_permutation(L, cp)
    Lc = L // cp
    for i in range(cp):
        pos = np.asarray(zigzag_shard_positions(jnp.int32(i), L, cp))
        np.testing.assert_array_equal(pos, perm[i * Lc:(i + 1) * Lc])


def test_zigzag_balance():
    # fold-in-half: every shard owns one early and one late chunk, so the
    # causal-live key count per shard is equal across shards
    L, cp = 64, 4
    C = L // (2 * cp)
    loads = []
    for i in range(cp):
        pos = np.asarray(zigzag_shard_positions(jnp.int32(i), L, cp))
        loads.append(int((pos[:, None] >= np.arange(L)[None, :]).sum()))
    assert len(set(loads)) == 1


def test_zigzag_indivisible_raises():
    with pytest.raises(ValueError, match="not divisible"):
        zigzag_permutation(30, 4)


# ---------------------------------------------------------------------------
# pair liveness + merge math (single device)
# ---------------------------------------------------------------------------
def test_ring_pair_live_causal():
    C = 8
    # q rows [8, 16) x keys [16, 24): strictly future keys -> dead
    assert not bool(ring_pair_live(8, 16, C, causal=True, window=0))
    # diagonal pair is live, past keys are live
    assert bool(ring_pair_live(8, 8, C, causal=True, window=0))
    assert bool(ring_pair_live(16, 0, C, causal=True, window=0))
    # one overlapping position (k_off + C - 1 == q_off) is live
    assert bool(ring_pair_live(8, 1, C, causal=True, window=0))


def test_ring_pair_live_window():
    C = 8
    # window=4: keys further than 4 behind every q row are dead
    assert not bool(ring_pair_live(32, 0, C, causal=True, window=4))
    assert bool(ring_pair_live(8, 4, C, causal=True, window=4))


def test_merge_neg_inf_safe():
    B, H, C, dh = 1, 2, 4, 8
    o = jnp.ones((B, C, H, dh), jnp.float32)
    lse = jnp.zeros((B, H, C), jnp.float32)
    dead_o = jnp.zeros((B, C, H, dh), jnp.float32)
    dead_lse = jnp.full((B, H, C), NEG_INF, jnp.float32)
    # live + dead == live, exactly; dead + dead has no NaNs
    mo, ml = _merge(o, lse, dead_o, dead_lse)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(o))
    np.testing.assert_allclose(np.asarray(ml), np.asarray(lse))
    mo2, ml2 = _merge(dead_o, dead_lse, dead_o, dead_lse)
    assert np.isfinite(np.asarray(mo2)).all()
    # merge of two live partials == softmax-combining identity
    o2 = 2.0 * jnp.ones((B, C, H, dh), jnp.float32)
    lse2 = jnp.log(3.0) * jnp.ones((B, H, C), jnp.float32)
    mo3, ml3 = _merge(o, lse, o2, lse2)
    np.testing.assert_allclose(np.asarray(ml3), np.log(1 + 3) * np.ones((B, H, C)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mo3), (1 * 1 + 2 * 3) / 4.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# config-time gates (single device)
# ---------------------------------------------------------------------------
def test_validate_seq_divisible():
    mesh = make_debug_mesh(1, 1)
    sh.validate_seq_divisible(30, mesh)  # cp=1: anything goes
    if len(jax.devices()) >= 2:
        mesh_cp = make_debug_mesh(1, 1, context=2)
        sh.validate_seq_divisible(32, mesh_cp)
        with pytest.raises(ValueError) as ei:
            sh.validate_seq_divisible(30, mesh_cp, bq=8)
        msg = str(ei.value)
        assert "2*cp = 4" in msg and "28 or 32" in msg and "context" in msg


def test_resolve_block_structure_cp_gates():
    from repro.models.blocks import resolve_block_structure

    cfg = get_config(ARCH)
    # residual x cp is fine
    assert resolve_block_structure(
        cfg, RunConfig(block_structure="residual"), cp=2) == "residual"
    # reversible x cp>1: decision-table error
    with pytest.raises(ValueError, match="context parallelism"):
        resolve_block_structure(
            cfg, RunConfig(block_structure="reversible"), cp=2)
    # sequence-recurrent kinds cannot context-shard
    rec_cfg = get_config("recurrentgemma-9b_smoke")
    with pytest.raises(ValueError, match="sequence-recurrent"):
        resolve_block_structure(rec_cfg, RunConfig(), cp=2)
    # cp=1 leaves every existing combination untouched
    assert resolve_block_structure(rec_cfg, RunConfig(), cp=1) == "residual"


@multidevice
def test_jit_executor_rejects_context_mesh():
    mesh = make_debug_mesh(1, 1, context=2)
    with pytest.raises(ValueError, match="jit executor"):
        make_train_step(get_config(ARCH), RunConfig(), mesh=mesh)


# ---------------------------------------------------------------------------
# ring == single-device parity (forced 8 devices)
# ---------------------------------------------------------------------------
def _ring_vs_flash(cp, H, KV, window, use_kernel, L=64, B=2, dh=16):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:cp]), ("context",))
    kq, kk, kv_, _ = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, L, H, dh), jnp.float32)
    k = jax.random.normal(kk, (B, L, KV, dh), jnp.float32)
    v = jax.random.normal(kv_, (B, L, KV, dh), jnp.float32)
    perm = zigzag_permutation(L, cp)
    inv = zigzag_inverse_permutation(L, cp)
    cid_g = jnp.arange(cp, dtype=jnp.int32)

    def body(qs, ks, vs, cid):
        pos = zigzag_shard_positions(cid[0], L, cp)
        pos = jnp.broadcast_to(pos[None, :], (qs.shape[0], pos.shape[0]))
        return ring_attention(qs, ks, vs, pos, axis_name="context", cp=cp,
                              causal=True, window=window,
                              use_kernel=use_kernel, bq=16, bk=16)

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "context"), P(None, "context"), P(None, "context"),
                  P("context")),
        out_specs=P(None, "context"), check_vma=False))

    out = np.asarray(f(q[:, perm], k[:, perm], v[:, perm], cid_g))[:, inv]
    ref = np.asarray(flash_attention(q, k, v, causal=True, window=window,
                                     bq=16, bk=16))
    rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 1e-5, f"fwd rel {rel:.2e}"

    def loss_ring(q_, k_, v_):
        return jnp.sum(jnp.sin(f(q_[:, perm], k_[:, perm], v_[:, perm], cid_g)))

    def loss_ref(q_, k_, v_):
        # sum(sin(o)) is invariant under the sequence permutation, so the
        # two losses (and their input grads) agree exactly
        return jnp.sum(jnp.sin(flash_attention(
            q_, k_, v_, causal=True, window=window, bq=16, bk=16)))

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_ref):
        a, b = np.asarray(a), np.asarray(b)
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 1e-5, f"d{name} rel {rel:.2e}"


@multidevice
@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("heads,label", [((4, 2), "gqa"), ((4, 1), "mqa")])
def test_ring_parity_causal(cp, heads, label):
    H, KV = heads
    _ring_vs_flash(cp, H, KV, window=0, use_kernel=False)


@multidevice
@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("window", [12, 24, 40])
def test_ring_parity_swa_seam_spanning(cp, window):
    # L=64: chunk C = 64/(2*cp) in {16, 8}; windows 12/24/40 reach across
    # one or several zigzag seams (and 40 > shard length at cp=4)
    _ring_vs_flash(cp, 4, 2, window=window, use_kernel=False)


@multidevice
@pytest.mark.parametrize("window", [0, 24])
def test_ring_parity_pallas_kernel_offsets(window):
    # the scalar-prefetch offset path through the flash kernels themselves
    _ring_vs_flash(2, 4, 2, window=window, use_kernel=True)


# ---------------------------------------------------------------------------
# executor-level parity (forced 8 devices)
# ---------------------------------------------------------------------------
def _run_steps(mesh_shape, steps=2):
    cfg = get_config(ARCH)
    # exact compression: PAMM's stochastic sampling is shard-count
    # dependent (blocks=auto = dp x cp), so strict cross-mesh parity needs
    # the deterministic path — the PAMM x dp parity story is
    # test_multidevice.py's job.
    rcfg = RunConfig(policy_name="none", compute_dtype="float32",
                     param_dtype="float32", attn_kernel="jnp")
    from repro.train import init_distributed_state

    data, model, cp = mesh_shape
    mesh = make_debug_mesh(data, model, context=cp)
    stream = SyntheticStream.for_arch(cfg, 32, 4)
    batch = {k: jnp.asarray(v) for k, v in stream.get_batch(0).items()}
    state, _ = init_distributed_state(cfg, rcfg, jax.random.key(0), mesh)
    step_fn = make_shard_map_train_step(cfg, rcfg, total_steps=steps, mesh=mesh)
    out = []
    for s in range(steps):
        state, m = step_fn(state, batch, jnp.int32(s))
        out.append((float(m["loss"]), float(m["grad_norm"])))
    return out


@multidevice
@pytest.mark.parametrize("mesh_shape", [(1, 1, 2), (2, 1, 2), (1, 1, 4)])
def test_train_step_parity_vs_single_shard(mesh_shape):
    base = _run_steps((1, 1, 1))
    got = _run_steps(mesh_shape)
    for (l0, g0), (l1, g1) in zip(base, got):
        assert abs(l0 - l1) / max(abs(l0), 1e-9) < 2e-5
        assert abs(g0 - g1) / max(abs(g0), 1e-9) < 2e-4


@multidevice
def test_train_step_cp_swa_arch():
    # a sliding-window architecture (h2o-danube smoke: swa blocks with
    # window=8 < shard length) trains under cp — the window masks cross
    # the zigzag shard seams inside the ring — and the loss matches cp=1
    cfg = get_config("h2o-danube-3-4b_smoke")
    rcfg = RunConfig(policy_name="none", compute_dtype="float32",
                     param_dtype="float32", attn_kernel="jnp")
    from repro.train import init_distributed_state

    losses = []
    for cp in (1, 2):
        mesh = make_debug_mesh(1, 1, context=cp)
        stream = SyntheticStream.for_arch(cfg, 32, 2)
        batch = {k: jnp.asarray(v) for k, v in stream.get_batch(0).items()}
        state, _ = init_distributed_state(cfg, rcfg, jax.random.key(0), mesh)
        step_fn = make_shard_map_train_step(cfg, rcfg, total_steps=2, mesh=mesh)
        _, m = step_fn(state, batch, jnp.int32(0))
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert abs(losses[0] - losses[1]) / max(abs(losses[0]), 1e-9) < 2e-5
