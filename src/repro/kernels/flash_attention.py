"""Pallas TPU kernels: FlashAttention-2 forward AND backward (causal /
sliding-window, GQA), joined by ``jax.custom_vjp``.

Forward: online-softmax over kv tiles; grid = (B*H, Lq/bq, Lk/bk) with
running (max, denom, acc) carried in VMEM scratch across the kv dimension,
emitting the log-sum-exp row statistic (lse = m + log l) as a residual so
backward never stores probabilities. GQA is handled in the BlockSpec index
maps: the kv tile for query-head h is head ``h // G`` — no repeated K/V in
HBM.

Backward (FlashAttention-2 style): probabilities are recomputed
tile-by-tile from the saved (q, k, v, o, lse):

  * ``delta = rowsum(dO ⊙ O)`` precomputed per query row,
  * dq in a q-major grid (B*H, nq, nk):   dq += (p ⊙ (dO Vᵀ − delta)) K,
  * dk/dv in a kv-major grid (B*KV, nk, G, nq) that also folds the G
    grouped query heads sharing one kv head — dk/dv accumulate across
    (g, iq) in VMEM scratch, so GQA needs no K/V replication in HBM and
    no post-kernel head reduction.

Query and kv lengths pad independently (bq != bk stays safe: tail keys
keep their dk/dv); padded rows/keys are masked exactly like the forward.

The paper composes PAMM with FlashAttention (App. D.1); with this pair
the *training* hot path runs on Pallas end to end — PAMM-compressed QKV
projections (core/linear.py custom_vjp) backprop through these kernels
(models/attention.py::attn_train under ``RunConfig.attn_kernel``).
Oracles: kernels/ref.py::flash_attention_ref and the chunked jnp sdpa.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30
# Denominator floor: a fully-masked row (zero-padded query tail under a
# sliding window) gets lse ~= NEG_INF instead of -inf/NaN; its dO is zero
# so every backward contribution vanishes without special-casing.
DENOM_FLOOR = 1e-30


def _tile_mask(iq, jk, bq: int, bk: int, *, causal: bool, window: int,
               lreal: int, q_off=0, k_off=0):
    """Validity mask of one (bq, bk) score tile — shared fwd/bwd.

    ``q_off``/``k_off`` shift the causal/window comparisons to GLOBAL
    positions (ring context parallelism hands each kernel call one
    sequence chunk whose rows start at a nonzero offset; they may be
    traced scalars). The padded-key exclusion stays in LOCAL coordinates
    — ``lreal`` is the chunk's real length regardless of where it sits
    in the global sequence. Python-int zeros fold away, so the default
    path is bit-identical to the offset-free kernel.
    """
    qpos = q_off + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kloc = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kloc < lreal  # exclude zero-padded keys (local coordinate)
    kpos = k_off + kloc
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (qpos - kpos < window)
    return mask


def _col(row_ref):
    """(1, bq) row block of a per-query statistic -> (bq, 1) column.

    lse and delta travel as lane-major rows of a (B*H, 1, L) array: the
    TPU tiling rule wants a block's last two dims to be (8, 128)-multiples
    or whole, and ``(1, bq)`` of a ``(1, L)`` plane is the lane-dense way
    to meet it without a 128x lane-broadcast copy in HBM."""
    return jnp.expand_dims(row_ref[0], -1)


def _tile_live(iq, jk, bq: int, bk: int, *, causal: bool, window: int,
               q_off=0, k_off=0):
    """False iff the (iq, jk) tile is *entirely* masked, so its MXU work
    can be skipped — with causal masking that is ~half the grid (tiles
    above the diagonal), and a sliding window additionally kills tiles
    far below it. Skipped tiles contributed exact zeros (p underflows),
    so guarding compute with this is bit-identical. Offsets as in
    :func:`_tile_mask` (the predicate is already dynamic — program ids
    are traced — so traced offsets change nothing structurally)."""
    live = None
    if causal:
        # live iff the tile's first key position <= its last query position
        live = k_off + jk * bk <= q_off + iq * bq + (bq - 1)
    if window > 0:
        # live iff the tile's last key position is inside some row's window
        in_window = k_off + jk * bk + (bk - 1) > q_off + iq * bq - window
        live = in_window if live is None else live & in_window
    return jnp.bool_(True) if live is None else live


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, bq: int, bk: int, nk: int, causal: bool, window: int,
                scale: float, lreal: int, offset: bool = False):
    if offset:
        offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qo, ko = offs_ref[0], offs_ref[1]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qo = ko = 0
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(iq, jk, bq, bk, causal=causal, window=window,
                        q_off=qo, k_off=ko))
    def _compute():
        q = q_ref[0].astype(jnp.float32)      # (bq, dh)
        k = k_ref[0].astype(jnp.float32)      # (bk, dh)
        v = v_ref[0].astype(jnp.float32)      # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                              # (bq, bk)
        mask = _tile_mask(iq, jk, bq, bk, causal=causal, window=window,
                          lreal=lreal, q_off=qo, k_off=ko)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                 # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _write():
        l = jnp.maximum(l_ref[...], DENOM_FLOOR)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l)).reshape(1, bq)


# ---------------------------------------------------------------------------
# backward: dq (q-major grid)
# ---------------------------------------------------------------------------
def _dq_kernel(*refs, bq: int, bk: int, nk: int, causal: bool,
               window: int, scale: float, lreal: int, offset: bool = False):
    if offset:
        offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, \
            acc_ref = refs
        qo, ko = offs_ref[0], offs_ref[1]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
        qo = ko = 0
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(iq, jk, bq, bk, causal=causal, window=window,
                        q_off=qo, k_off=ko))
    def _compute():
        q = q_ref[0].astype(jnp.float32)       # (bq, dh)
        k = k_ref[0].astype(jnp.float32)       # (bk, dh)
        v = v_ref[0].astype(jnp.float32)       # (bk, dh)
        do = do_ref[0].astype(jnp.float32)     # (bq, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(iq, jk, bq, bk, causal=causal, window=window,
                          lreal=lreal, q_off=qo, k_off=ko)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - _col(lse_ref))                           # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                        # (bq, bk)
        ds = p * (dp - _col(delta_ref)) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jk == nk - 1)
    def _write():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv (kv-major grid, GQA head folding)
# ---------------------------------------------------------------------------
def _dkv_kernel(*refs, bq: int, bk: int, nq: int, G: int,
                causal: bool, window: int, scale: float, lreal: int,
                offset: bool = False):
    if offset:
        offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, \
            dv_ref, dk_acc, dv_acc = refs
        qo, ko = offs_ref[0], offs_ref[1]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, \
            dk_acc, dv_acc = refs
        qo = ko = 0
    g = pl.program_id(2)
    iq = pl.program_id(3)
    jk = pl.program_id(1)

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(iq, jk, bq, bk, causal=causal, window=window,
                        q_off=qo, k_off=ko))
    def _compute():
        q = q_ref[0].astype(jnp.float32)       # (bq, dh)
        k = k_ref[0].astype(jnp.float32)       # (bk, dh)
        v = v_ref[0].astype(jnp.float32)       # (bk, dh)
        do = do_ref[0].astype(jnp.float32)     # (bq, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(iq, jk, bq, bk, causal=causal, window=window,
                          lreal=lreal, q_off=qo, k_off=ko)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - _col(lse_ref))                       # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(                  # pᵀ dO -> (bk, dh)
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _col(delta_ref)) * scale
        dk_acc[...] += jax.lax.dot_general(                  # dsᵀ q -> (bk, dh)
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when((g == G - 1) & (iq == nq - 1))
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# padded / head-folded layouts (shared by fwd and bwd)
# ---------------------------------------------------------------------------
def _blocking(L: int, dh: int, bq: int, bk: int):
    bq = min(bq, L)
    bk = min(bk, L)
    # q and kv lengths pad independently: the query grid tiles by bq, the kv
    # grid by bk — sharing one pad (the old `pq` for both) mis-sizes nk
    # whenever bq != bk and silently drops tail keys.
    pq = (-L) % bq
    pk = (-L) % bk
    pdh = (-dh) % 128
    return bq, bk, pq, pk, pdh


def _fold_heads(x, pad_len: int, pdh: int):
    """(B, L, N, dh) -> (B*N, L+pad_len, dh+pdh), zero-padded."""
    B, L, N, dh = x.shape
    x = jnp.pad(x, ((0, 0), (0, pad_len), (0, 0), (0, pdh)))
    return x.transpose(0, 2, 1, 3).reshape(B * N, L + pad_len, dh + pdh)


def _fwd_impl(q, k, v, causal, window, bq, bk, interpret, offs=None):
    """``offs``: optional (2,) int32 ``[q_off, k_off]`` — global position
    offsets of the q and kv chunks (traced; ring context parallelism).
    They ride as a scalar-prefetch operand so the mask/liveness math sees
    global positions while the tiling stays chunk-local. ``offs=None`` is
    the original plain-grid lowering, byte-identical to before."""
    B, L, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    bq, bk, pq, pk, pdh = _blocking(L, dh, bq, bk)
    Lqp, Lkp, dhp = L + pq, L + pk, dh + pdh

    qr = _fold_heads(q, pq, pdh)           # (B*H, Lqp, dhp)
    kr = _fold_heads(k, pk, pdh)           # (B*KV, Lkp, dhp)
    vr = _fold_heads(v, pk, pdh)
    nq, nk = Lqp // bq, Lkp // bk
    grid = (B * H, nq, nk)

    def kv_index(bh, iq, jk, *_):
        # query stream bh = b * H + h; kv head = h // G
        return ((bh // H) * KV + (bh % H) // G, jk, 0)

    kern = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                             window=window, scale=scale, lreal=L,
                             offset=offs is not None)
    in_specs = [
        pl.BlockSpec((1, bq, dhp), lambda bh, iq, jk, *_: (bh, iq, 0)),
        pl.BlockSpec((1, bk, dhp), kv_index),
        pl.BlockSpec((1, bk, dhp), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, dhp), lambda bh, iq, jk, *_: (bh, iq, 0)),
        pl.BlockSpec((None, 1, bq), lambda bh, iq, jk, *_: (bh, 0, iq)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Lqp, dhp), q.dtype),
        jax.ShapeDtypeStruct((B * H, 1, Lqp), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, dhp), jnp.float32),
    ]
    if offs is None:
        out, lse = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    else:
        out, lse = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret, name="flash_fwd",
        )(jnp.asarray(offs, jnp.int32), qr, kr, vr)
    out = out.reshape(B, H, Lqp, dhp).transpose(0, 2, 1, 3)[:, :L, :, :dh]
    lse = lse.reshape(B, H, Lqp)[:, :, :L]
    return out, lse


def _bwd_impl(q, k, v, o, lse, do, causal, window, bq, bk, interpret,
              offs=None):
    """``offs``: optional (2,) int32 ``[q_off, k_off]`` scalar-prefetch
    operand carrying global chunk positions (ring context parallelism);
    ``None`` keeps the original plain-grid lowering."""
    B, L, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    bq, bk, pq, pk, pdh = _blocking(L, dh, bq, bk)
    Lqp, Lkp, dhp = L + pq, L + pk, dh + pdh

    qr = _fold_heads(q, pq, pdh)
    kr = _fold_heads(k, pk, pdh)
    vr = _fold_heads(v, pk, pdh)
    dor = _fold_heads(do.astype(q.dtype), pq, pdh)
    # delta = rowsum(dO ⊙ O): the softmax-normalization term of dS. Padded
    # rows carry dO = 0, so lse/delta = 0 there is inert by construction.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta.transpose(0, 2, 1).reshape(B * H, 1, L),
                    ((0, 0), (0, 0), (0, pq)))
    lser = jnp.pad(lse.reshape(B * H, 1, L), ((0, 0), (0, 0), (0, pq)))

    nq, nk = Lqp // bq, Lkp // bk
    offset = offs is not None
    if offset:
        offs = jnp.asarray(offs, jnp.int32)

    def kv_index_q(bh, iq, jk, *_):
        return ((bh // H) * KV + (bh % H) // G, jk, 0)

    dq_kern = functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                                window=window, scale=scale, lreal=L,
                                offset=offset)
    dq_grid = (B * H, nq, nk)
    dq_in_specs = [
        pl.BlockSpec((1, bq, dhp), lambda bh, iq, jk, *_: (bh, iq, 0)),
        pl.BlockSpec((1, bk, dhp), kv_index_q),
        pl.BlockSpec((1, bk, dhp), kv_index_q),
        pl.BlockSpec((1, bq, dhp), lambda bh, iq, jk, *_: (bh, iq, 0)),
        pl.BlockSpec((None, 1, bq), lambda bh, iq, jk, *_: (bh, 0, iq)),
        pl.BlockSpec((None, 1, bq), lambda bh, iq, jk, *_: (bh, 0, iq)),
    ]
    dq_out_specs = pl.BlockSpec((1, bq, dhp), lambda bh, iq, jk, *_: (bh, iq, 0))
    dq_out_shape = jax.ShapeDtypeStruct((B * H, Lqp, dhp), q.dtype)
    dq_scratch = [pltpu.VMEM((bq, dhp), jnp.float32)]
    if offset:
        dq = pl.pallas_call(
            dq_kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=dq_grid, in_specs=dq_in_specs,
                out_specs=dq_out_specs, scratch_shapes=dq_scratch),
            out_shape=dq_out_shape, interpret=interpret, name="flash_dq",
        )(offs, qr, kr, vr, dor, lser, delta)
    else:
        dq = pl.pallas_call(
            dq_kern, grid=dq_grid, in_specs=dq_in_specs,
            out_specs=dq_out_specs, out_shape=dq_out_shape,
            scratch_shapes=dq_scratch, interpret=interpret, name="flash_dq",
        )(qr, kr, vr, dor, lser, delta)

    # kv-major grid; the two inner dims (g, iq) sweep the query stream of
    # one kv head so dk/dv fold GQA inside the kernel's VMEM accumulators.
    def q_index(bkv, jk, g, iq, *_):
        return ((bkv // KV) * H + (bkv % KV) * G + g, iq, 0)

    def qrow_index(bkv, jk, g, iq, *_):
        return ((bkv // KV) * H + (bkv % KV) * G + g, 0, iq)

    def kv_index(bkv, jk, g, iq, *_):
        return (bkv, jk, 0)

    dkv_kern = functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq, G=G,
                                 causal=causal, window=window, scale=scale,
                                 lreal=L, offset=offset)
    dkv_grid = (B * KV, nk, G, nq)
    dkv_in_specs = [
        pl.BlockSpec((1, bq, dhp), q_index),
        pl.BlockSpec((1, bk, dhp), kv_index),
        pl.BlockSpec((1, bk, dhp), kv_index),
        pl.BlockSpec((1, bq, dhp), q_index),
        pl.BlockSpec((None, 1, bq), qrow_index),
        pl.BlockSpec((None, 1, bq), qrow_index),
    ]
    dkv_out_specs = [
        pl.BlockSpec((1, bk, dhp), kv_index),
        pl.BlockSpec((1, bk, dhp), kv_index),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct((B * KV, Lkp, dhp), k.dtype),
        jax.ShapeDtypeStruct((B * KV, Lkp, dhp), v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((bk, dhp), jnp.float32),
        pltpu.VMEM((bk, dhp), jnp.float32),
    ]
    if offset:
        dk, dv = pl.pallas_call(
            dkv_kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=dkv_grid, in_specs=dkv_in_specs,
                out_specs=dkv_out_specs, scratch_shapes=dkv_scratch),
            out_shape=dkv_out_shape, interpret=interpret, name="flash_dkv",
        )(offs, qr, kr, vr, dor, lser, delta)
    else:
        dk, dv = pl.pallas_call(
            dkv_kern, grid=dkv_grid, in_specs=dkv_in_specs,
            out_specs=dkv_out_specs, out_shape=dkv_out_shape,
            scratch_shapes=dkv_scratch, interpret=interpret,
            name="flash_dkv",
        )(qr, kr, vr, dor, lser, delta)

    def unfold(x, N, Lp):
        return x.reshape(B, N, Lp, dhp).transpose(0, 2, 1, 3)[:, :L, :, :dh]

    return (unfold(dq, H, Lqp).astype(q.dtype),
            unfold(dk, KV, Lkp).astype(k.dtype),
            unfold(dv, KV, Lkp).astype(v.dtype))


# ---------------------------------------------------------------------------
# custom_vjp + public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, bq, bk, interpret):
    out, _ = _fwd_impl(q, k, v, causal, window, bq, bk, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, bq, bk, interpret):
    out, lse = _fwd_impl(q, k, v, causal, window, bq, bk, interpret)
    # Residuals are (q, k, v, o, lse): FlashAttention-2 memory semantics —
    # O(L) statistics instead of the (L, L) probability matrix.
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, causal, window, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = True):
    """q: (B, L, H, dh); k, v: (B, L, KV, dh) -> (B, L, H, dh).

    Differentiable: ``jax.grad`` through this runs the Pallas backward
    kernels (dq q-major, dk/dv kv-major with GQA folding). Assumes
    contiguous ``arange`` positions — both the training batch and serving
    prefill satisfy this; slot-addressed decode uses flash_decode.py.
    """
    return _flash(q, k, v, causal, window, bq, bk, interpret)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret")
)
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool = True):
    """Forward that also returns the saved lse statistic (B, H, L) f32.

    ``lse[b, h, i] = logsumexp_j(scale * q_i·k_j)`` over i's visible keys —
    the quantity backward uses to recompute probabilities tile-by-tile
    (parity-tested against ``logsumexp`` of the oracle's scores).
    """
    return _fwd_impl(q, k, v, causal, window, bq, bk, interpret)
