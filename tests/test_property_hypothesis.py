"""Property-based tests (hypothesis) of PAMM and kernel invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (pinned in pyproject.toml; "
    "pip install hypothesis to run the property suite)",
)
from hypothesis import given, settings, strategies as st

from repro.core.pamm import pamm_apply, pamm_compress, pamm_reconstruct
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pamm_apply import segment_matmul
from repro.kernels.pamm_compress import csim_argmax
from repro.runtime.grad_compress import ef_dequantize, ef_quantize

SETTINGS = dict(max_examples=20, deadline=None)

# Flash-kernel shape strategy: random B/L/H/KV/dh within tile bounds — dh
# a lane-friendly multiple of 8, KV drawn as a divisor of H (GQA/MQA/MHA),
# L free so odd lengths exercise independent bq/bk tail padding. Kept
# small: interpret mode executes the full fwd+bwd grids on CPU.
FLASH_SETTINGS = dict(max_examples=8, deadline=None)


@st.composite
def flash_shapes(draw, *, cp=False):
    """Random flash-attention problem shapes.

    With ``cp=True`` also draws a context-parallel degree in {2, 4} and
    constrains L to a zigzag-shardable multiple of 2*cp (the ring's hard
    divisibility gate); window draws relative to the chunk length so some
    samples span shard seams and some kill whole ring pairs.
    """
    B = draw(st.integers(1, 2))
    H = draw(st.sampled_from([1, 2, 4, 8]))
    KV = draw(st.sampled_from([d for d in (1, 2, 4, 8) if H % d == 0]))
    dh = draw(st.sampled_from([8, 16, 32, 64]))
    bq = draw(st.sampled_from([16, 32, 64]))
    bk = draw(st.sampled_from([16, 32, 64]))
    causal = draw(st.booleans())
    if cp:
        deg = draw(st.sampled_from([2, 4]))
        C = draw(st.sampled_from([4, 8, 12]))
        L = 2 * deg * C
        causal = True  # the train path rings causal/SWA attention only
        window = draw(st.sampled_from([0, 0, C - 1, 2 * C + 1]))
        return B, L, H, KV, dh, bq, bk, causal, window, deg
    L = draw(st.integers(2, 96))
    window = draw(st.sampled_from([0, 0, 7, 24])) if causal else 0
    return B, L, H, KV, dh, bq, bk, causal, window


@settings(**SETTINGS)
@given(
    b=st.integers(8, 200),
    n=st.integers(2, 64),
    k=st.integers(1, 32),
    seed=st.integers(0, 2**30),
)
def test_compress_invariants(b, n, k, seed):
    k = min(k, b)
    x = jax.random.normal(jax.random.key(seed), (b, n))
    stt = pamm_compress(x, k, math.inf, jax.random.key(seed + 1))
    # shapes
    assert stt.generators.shape == (k, n)
    assert stt.alpha.shape == (b,)
    assert stt.assign.shape == (b,)
    # assignments in range
    assert int(jnp.min(stt.assign)) >= 0 and int(jnp.max(stt.assign)) < k
    # eps = inf keeps everything -> beta == 1
    assert float(stt.beta) == 1.0
    # projection property: ||x - atilde|| <= ||x|| (projection onto a line
    # through the origin can never be farther than the origin itself)
    recon = pamm_reconstruct(stt)
    err = jnp.linalg.norm(x - recon, axis=1)
    nrm = jnp.linalg.norm(x, axis=1)
    assert bool(jnp.all(err <= nrm * (1 + 1e-4) + 1e-5))


@settings(**SETTINGS)
@given(
    b=st.integers(8, 128),
    m=st.integers(1, 48),
    k=st.integers(1, 16),
    seed=st.integers(0, 2**30),
)
def test_apply_is_linear_in_b(b, m, k, seed):
    """pamm_apply(state, .) must be a linear map (it IS Atilde^T B)."""
    x = jax.random.normal(jax.random.key(seed), (b, 16))
    stt = pamm_compress(x, min(k, b), math.inf, jax.random.key(seed + 1))
    b1 = jax.random.normal(jax.random.key(seed + 2), (b, m))
    b2 = jax.random.normal(jax.random.key(seed + 3), (b, m))
    lhs = pamm_apply(stt, b1 + 2.5 * b2)
    rhs = pamm_apply(stt, b1) + 2.5 * pamm_apply(stt, b2)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-3)


@settings(**SETTINGS)
@given(
    b=st.integers(4, 300),
    n=st.integers(2, 100),
    k=st.integers(1, 40),
    seed=st.integers(0, 2**30),
)
def test_kernel_csim_matches_ref(b, n, k, seed):
    k = min(k, b)
    x = jax.random.normal(jax.random.key(seed), (b, n))
    idx = jax.random.choice(jax.random.key(seed + 1), b, shape=(k,), replace=False)
    c = x[idx]
    cs, f, na = csim_argmax(x, c)
    cs_r, f_r, na_r = ref.csim_argmax_ref(x, c)
    np.testing.assert_allclose(np.abs(np.asarray(cs)), np.abs(np.asarray(cs_r)),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(na), np.asarray(na_r), rtol=1e-5, atol=1e-5)


@settings(**SETTINGS)
@given(
    b=st.integers(4, 300),
    m=st.integers(1, 130),
    k=st.integers(1, 40),
    seed=st.integers(0, 2**30),
)
def test_kernel_segment_matmul_matches_ref(b, m, k, seed):
    key = jax.random.key(seed)
    f = jax.random.randint(key, (b,), 0, k).astype(jnp.int32)
    alpha = jax.random.normal(jax.random.key(seed + 1), (b,))
    gz = jax.random.normal(jax.random.key(seed + 2), (b, m))
    mine = segment_matmul(f, alpha, gz, k)
    oracle = ref.segment_matmul_ref(f, alpha, gz, k)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


@settings(**SETTINGS)
@given(
    shape=st.tuples(st.integers(1, 8), st.integers(1, 64)),
    seed=st.integers(0, 2**30),
)
def test_ef_quantize_error_bound(shape, seed):
    """|residual| <= scale/2 element-wise, and dequant roundtrip is close."""
    g = jax.random.normal(jax.random.key(seed), shape) * 3.0
    err = jnp.zeros_like(g)
    q, scale, new_err = ef_quantize(g, err)
    assert q.dtype == jnp.int8
    deq = ef_dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(deq + new_err), np.asarray(g),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(new_err))) <= float(scale) * 0.5 + 1e-7


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**30))
def test_ef_feedback_accumulates(seed):
    """With a CONSTANT gradient, EF-compressed updates average to the true
    gradient (error feedback kills the bias)."""
    g = jax.random.normal(jax.random.key(seed), (32,))
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    steps = 50
    for _ in range(steps):
        q, scale, err = ef_quantize(g, err)
        total = total + ef_dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(total / steps), np.asarray(g),
                               atol=float(jnp.max(jnp.abs(g))) * 0.02 + 1e-4)


def _flash_oracle(q, k, v, *, causal, window):
    """jnp sdpa over arange positions — same math attn_train differentiates."""
    from repro.models.attention import sdpa

    B, L = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    return sdpa(q, k, v, pos, pos, causal=causal, window=window, chunk=32)


@settings(**FLASH_SETTINGS)
@given(shape=flash_shapes(), seed=st.integers(0, 2**30))
def test_flash_forward_parity_all_shapes(shape, seed):
    B, L, H, KV, dh, bq, bk, causal, window = shape
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, L, H, dh))
    k = jax.random.normal(ks[1], (B, L, KV, dh))
    v = jax.random.normal(ks[2], (B, L, KV, dh))
    o = flash_attention(q, k, v, causal=causal, window=window, bq=bq, bk=bk)
    o_r = _flash_oracle(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), atol=2e-5)


@settings(**FLASH_SETTINGS)
@given(shape=flash_shapes(), seed=st.integers(0, 2**30))
def test_flash_grad_of_sum_parity_all_shapes(shape, seed):
    """grad of sum(flash(q,k,v)) == grad of sum(oracle) for all sampled
    shapes — dq, dk and dv each, through the Pallas backward kernels."""
    B, L, H, KV, dh, bq, bk, causal, window = shape
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, L, H, dh))
    k = jax.random.normal(ks[1], (B, L, KV, dh))
    v = jax.random.normal(ks[2], (B, L, KV, dh))

    def f(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, window=window,
                               bq=bq, bk=bk).sum()

    def g(q_, k_, v_):
        return _flash_oracle(q_, k_, v_, causal=causal, window=window).sum()

    for mine, oracle in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                            jax.grad(g, (0, 1, 2))(q, k, v)):
        denom = max(float(jnp.linalg.norm(oracle)), 1e-12)
        assert float(jnp.linalg.norm(mine - oracle)) / denom < 1e-5


@pytest.mark.multidevice
@settings(max_examples=5, deadline=None)
@given(shape=flash_shapes(cp=True), seed=st.integers(0, 2**30))
def test_ring_parity_random_shapes(shape, seed):
    """Ring context-parallel attention == single-device flash (fwd and
    grad-of-sum, f32 rel < 1e-5) for random shapes and cp degrees, on the
    forced-8-device harness. Inputs ride the zigzag permutation exactly
    as the shard_map executor applies it."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.kernels.ring_attention import (
        ring_attention,
        zigzag_inverse_permutation,
        zigzag_permutation,
        zigzag_shard_positions,
    )

    B, L, H, KV, dh, bq, bk, causal, window, cp = shape
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, L, H, dh))
    k = jax.random.normal(ks[1], (B, L, KV, dh))
    v = jax.random.normal(ks[2], (B, L, KV, dh))
    mesh = Mesh(np.array(jax.devices()[:cp]), ("context",))
    perm = zigzag_permutation(L, cp)
    inv = zigzag_inverse_permutation(L, cp)
    cid = jnp.arange(cp, dtype=jnp.int32)

    def body(qs, ks_, vs, c):
        pos = zigzag_shard_positions(c[0], L, cp)
        pos = jnp.broadcast_to(pos[None, :], (qs.shape[0], pos.shape[0]))
        return ring_attention(qs, ks_, vs, pos, axis_name="context", cp=cp,
                              causal=causal, window=window, bq=bq, bk=bk)

    ring = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "context"),) * 3 + (P("context"),),
        out_specs=P(None, "context"), check_vma=False))

    def f(q_, k_, v_):
        return jnp.sum(jnp.sin(ring(q_[:, perm], k_[:, perm], v_[:, perm], cid)))

    def g(q_, k_, v_):
        return jnp.sum(jnp.sin(flash_attention(
            q_, k_, v_, causal=causal, window=window, bq=bq, bk=bk)))

    out = np.asarray(ring(q[:, perm], k[:, perm], v[:, perm], cid))[:, inv]
    ref_o = np.asarray(flash_attention(q, k, v, causal=causal, window=window,
                                       bq=bq, bk=bk))
    assert np.abs(out - ref_o).max() / (np.abs(ref_o).max() + 1e-9) < 1e-5
    for mine, oracle in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                            jax.grad(g, (0, 1, 2))(q, k, v)):
        denom = max(float(jnp.linalg.norm(oracle)), 1e-12)
        assert float(jnp.linalg.norm(mine - oracle)) / denom < 1e-5
