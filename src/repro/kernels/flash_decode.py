"""Pallas TPU kernel: single-query (Lq=1) FlashAttention decode.

Decode-time attention reads ONE query row per sequence against the whole
KV cache. The prefill kernel (flash_attention.py) assumes contiguous
``arange`` positions; decode caches are slot-addressed — a ring buffer for
sliding-window layers stores absolute positions per slot (``slot_pos``,
-1 = empty) — so masking must come from the cache metadata, not iota.

Layout: queries fold to (B*KV, G, dh) — the G grouped query heads that
share one kv head become the sublane dim, so GQA needs no K/V replication
in HBM. Grid = (B*KV, S/bk); online softmax (running max / denom / acc in
VMEM scratch) walks the kv tiles, exactly like the prefill kernel, but the
whole (S,) score row is never materialized — the jnp decode path in
models/attention.py previously built (B, KV, G, 1, S) scores per step.

Oracle: :func:`flash_decode_ref` (also the CPU serving path — interpret
mode is far too slow per decode step for a per-token inner loop).

Paged variant (:func:`flash_paged_decode`): the KV cache lives in a global
page pool ``(n_pages, page_size, KV, dh)`` and each sequence owns a
*block table* — logical kv block ``j`` of sequence ``b`` is physical page
``block_table[b, j]`` (-1 = unmapped). The Pallas kernel gathers its kv
tiles *through* the table: the block table is a scalar-prefetch operand,
so the k/v BlockSpec index maps read the physical page id per grid step,
and a tile whose table entry is -1 is skipped entirely (page-granular
tile liveness; masking inside a live page still comes from ``page_pos``,
the paged counterpart of ``slot_pos``). The jnp oracle gathers the pool
through the same table and defers to :func:`flash_decode_ref`.

Quantized variant (:func:`flash_paged_decode_quant`): the pool stores
int8 (or nibble-packed int4) pages plus fp32 absmax scales — one scale
per ``group``-wide slice of head_dim per token per kv head. The scales
ride as two extra operand blocks gathered through the SAME block-table
index maps as the pages, and dequantization happens in VMEM per kv tile
(:func:`_dequant_tile`) right before the score dot — no fp16/fp32 cache
is ever materialized in HBM. Host-side quantization helpers
(``quantize_kv`` / ``dequantize_kv`` / ``pack_int4`` / ``unpack_int4``)
live here too so models/ and serve/ share one rounding convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 256
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV quantization (cache.kv=int8 / int4(group=...) — DESIGN.md §9)
# ---------------------------------------------------------------------------
def pack_int4(q):
    """Pack int8 values in [-7, 7] into nibbles: (..., d) -> (..., d//2).
    Adjacent dims pair into one byte (dim 2j low nibble, 2j+1 high)."""
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(p):
    """Inverse of :func:`pack_int4`, sign-extending each nibble."""
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    return jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1],
                                                p.shape[-1] * 2)


def quantize_kv(x, bits: int, ngr: int):
    """Symmetric absmax quantization of K/V rows.

    x: (..., dh) -> (q int8 (..., dh) [int4: packed (..., dh//2)],
    scale f32 (..., ngr)) with one scale per ``dh // ngr``-wide group.
    The symmetric range ([-127,127] / [-7,7]) keeps the int4 nibble
    sign-extension trivially exact.
    """
    dh = x.shape[-1]
    g = dh // ngr
    xg = x.astype(jnp.float32).reshape(*x.shape[:-1], ngr, g)
    qmax = 127.0 if bits == 8 else 7.0
    scale = jnp.maximum(jnp.max(jnp.abs(xg), axis=-1), 1e-12) / qmax
    q = jnp.clip(jnp.round(xg / scale[..., None]), -qmax, qmax)
    q = q.reshape(*x.shape[:-1], dh).astype(jnp.int8)
    if bits == 4:
        q = pack_int4(q)
    return q, scale


def dequantize_kv(q, scale, dh: int):
    """(..., dh | dh//2 packed) int8 + (..., ngr) f32 -> (..., dh) f32."""
    if q.shape[-1] != dh:
        q = unpack_int4(q)
    ngr = scale.shape[-1]
    g = dh // ngr
    xg = q.astype(jnp.float32).reshape(*q.shape[:-1], ngr, g)
    return (xg * scale[..., None]).reshape(*q.shape[:-1], dh)


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, spos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, nk: int, kv: int, causal: bool,
                   window: int, scale: float):
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (G, dhp)
    k = k_ref[0].astype(jnp.float32)          # (bk, dhp)
    v = v_ref[0].astype(jnp.float32)          # (bk, dhp)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # (G, bk)

    qpos = qpos_ref[pl.program_id(0) // kv]    # scalar absolute query position
    spos = spos_ref[...]                       # (1, bk) absolute slot positions
    mask = spos >= 0                           # empty / padded slots
    if causal:
        mask = mask & (spos <= qpos)
    if window > 0:
        mask = mask & (qpos - spos < window)
    s = jnp.where(mask, s, NEG_INF)            # (1,bk) broadcasts over G

    m_prev = m_ref[...]                        # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bk", "interpret")
)
def flash_decode_kernel(q, k, v, q_pos, slot_pos, *, causal: bool = True,
                        window: int = 0, bk: int = DEFAULT_BK,
                        interpret: bool = True):
    """q: (B, 1, H, dh); k, v: (B, S, KV, dh); q_pos: (B,) int32 absolute;
    slot_pos: (B, S) int32 absolute-position-per-slot (-1 = empty).
    Returns (B, 1, H, dh)."""
    B, Lq, H, dh = q.shape
    assert Lq == 1, "flash_decode is the single-query path"
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    bk = min(bk, S)
    pk = (-S) % bk
    pdh = (-dh) % 128
    Sp, dhp = S + pk, dh + pdh

    qr = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pdh)))
    qr = qr.reshape(B, KV, G, dhp).reshape(B * KV, G, dhp)
    kr = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, pdh)))
    vr = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, pdh)))
    kr = kr.transpose(0, 2, 1, 3).reshape(B * KV, Sp, dhp)
    vr = vr.transpose(0, 2, 1, 3).reshape(B * KV, Sp, dhp)
    # slot positions as (B, 1, Sp) so a (1, bk) block is whole in its
    # sublane dim (TPU tiling); the query position is an SMEM scalar.
    sposr = jnp.pad(slot_pos, ((0, 0), (0, pk)), constant_values=-1)[:, None]
    qposr = q_pos.reshape(B).astype(jnp.int32)

    nk = Sp // bk
    grid = (B * KV, nk)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, nk=nk, kv=KV, causal=causal,
                          window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, dhp), lambda bh, jk, _: (bh, 0, 0)),
                pl.BlockSpec((1, bk, dhp), lambda bh, jk, _: (bh, jk, 0)),
                pl.BlockSpec((1, bk, dhp), lambda bh, jk, _: (bh, jk, 0)),
                pl.BlockSpec((None, 1, bk),
                             lambda bh, jk, _: (bh // KV, 0, jk)),
            ],
            out_specs=pl.BlockSpec((1, G, dhp), lambda bh, jk, _: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dhp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, dhp), q.dtype),
        interpret=interpret, name="dense_decode",
    )(qposr, qr, kr, vr, sposr)
    return out.reshape(B, KV, G, dhp)[..., :dh].reshape(B, 1, H, dh)


def flash_decode_ref(q, k, v, q_pos, slot_pos, *, causal: bool = True,
                     window: int = 0, scale: float | None = None):
    """Pure-jnp oracle / CPU serving path (same signature, same math).

    Handles any Lq >= 1: the speculative verify path feeds a short block
    of drafted tokens — q (B, Lq, H, dh) with per-row positions q_pos
    (B, Lq) — through the same masking, so verification is a short-Lq
    prefill against the decode cache. ``q_pos`` may also stay (B,) for
    the classic single-query step. Per-row math is identical to running
    the rows one at a time (row-independent einsums), which is what makes
    speculative verify token-identical to sequential decode.

    Materializes (B, Lq, KV, G, S) scores. ``scale`` overrides the
    ``dh**-0.5`` score scale (the svd cache path operates on rank-r
    vectors but must keep the original head_dim's scale).
    """
    B, Lq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(B, Lq, KV, G, dh).astype(jnp.float32)
    s = jnp.einsum("blkgd,bskd->blkgs", qg, k.astype(jnp.float32)) * scale
    qp = q_pos.reshape(B, -1)                       # (B, Lq) or (B, 1)
    qp = qp[:, :, None, None, None]
    sp = slot_pos[:, None, None, None, :]
    mask = sp >= 0
    if causal:
        mask = mask & (sp <= qp)
    if window > 0:
        mask = mask & (qp - sp < window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("blkgs,bskd->blkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Lq, H, dh).astype(q.dtype)


def _row_positions(qpos_ref, b, lq: int, g: int):
    """Absolute position of each score row ``l*g + h`` (query ``l`` of
    sequence ``b``), read from the (B, lq) SMEM scalar table — a scalar
    for the single-query step, an (lq*g, 1) column for speculative verify.
    Built from scalars because a (1, lq) VMEM block would break the TPU
    tiling rule and its transpose to a column does not lower."""
    qpos = qpos_ref[b, lq - 1]
    if lq == 1:
        return qpos
    row = jax.lax.broadcasted_iota(jnp.int32, (lq * g, 1), 0)
    for l in range(lq - 2, -1, -1):
        qpos = jnp.where(row < (l + 1) * g, qpos_ref[b, l], qpos)
    return qpos


def _paged_decode_kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, ppos_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, nb: int, kv: int,
                         lq: int, causal: bool, window: int, scale: float):
    bh = pl.program_id(0)
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Tile liveness is page-granular: an unmapped block-table entry means
    # the whole kv tile is dead, so its loads/FLOPs are skipped — the
    # index map already clamped the page id, making the (ignored) block
    # fetch safe.
    page = bt_ref[bh // kv, jk]

    @pl.when(page >= 0)
    def _tile():
        q = q_ref[0].astype(jnp.float32)       # (lq*G, dhp)
        k = k_ref[0, 0].astype(jnp.float32)    # (psp, dhp)
        v = v_ref[0, 0].astype(jnp.float32)    # (psp, dhp)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                               # (lq*G, psp)

        # per-row query position: row l*G + g is query l (speculative
        # verify feeds lq > 1 drafted tokens at ascending positions)
        qpos = _row_positions(qpos_ref, bh // kv, lq, q_ref.shape[1] // lq)
        spos = ppos_ref[...]                    # (1, psp) absolute positions
        mask = spos >= 0
        if causal:
            mask = mask & (spos <= qpos)
        if window > 0:
            mask = mask & (qpos - spos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(jk == nb - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret", "scale")
)
def flash_paged_decode_kernel(q, k_pages, v_pages, q_pos, block_table,
                              page_pos, *, causal: bool = True,
                              window: int = 0, interpret: bool = True,
                              scale: float | None = None):
    """q: (B, Lq, H, dh); k_pages, v_pages: (n_pages, page_size, KV, dh);
    q_pos: (B,) or (B, Lq) int32 absolute; block_table: (B, nb) int32
    physical page per logical block (-1 = unmapped); page_pos:
    (n_pages, page_size) int32 absolute-position-per-slot (-1 = empty).
    Returns (B, Lq, H, dh).

    Lq > 1 is the speculative-verify path: the Lq drafted queries fold
    into the kernel's row (sublane) dim next to the G grouped heads —
    (B*KV, Lq*G, dhp) — so one grid walk over the pages scores every
    draft at once, with per-row positions rebuilt from the (B, Lq) qpos
    table in SMEM (:func:`_row_positions`). The grid and page gathers are
    identical to the Lq=1 step; only the row count of the score tile
    grows.

    The kv tile size IS the page size, so pick page_size >= the dtype's
    sublane granule (8 for f32, 16 for bf16) on real TPUs; smaller pages
    are padded (pad rows masked via page_pos = -1).
    """
    B, Lq, H, dh = q.shape
    n_pages, ps, KV, _ = k_pages.shape
    nb = block_table.shape[1]
    G = H // KV
    scale = dh ** -0.5 if scale is None else scale
    pdh = (-dh) % 128
    pps = (-ps) % 8
    dhp, psp = dh + pdh, ps + pps

    qr = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pdh)))
    qr = qr.reshape(B, Lq, KV, G, dhp).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B * KV, Lq * G, dhp)
    # kv head becomes the leading (grid-indexed) dim; page stays a whole
    # block so the index map can pick it straight off the block table.
    kt = jnp.pad(k_pages, ((0, 0), (0, pps), (0, 0), (0, pdh))
                 ).transpose(2, 0, 1, 3)        # (KV, n_pages, psp, dhp)
    vt = jnp.pad(v_pages, ((0, 0), (0, pps), (0, 0), (0, pdh))
                 ).transpose(2, 0, 1, 3)
    pposr = _page_pos_rows(page_pos, pps)
    qposr = jnp.broadcast_to(q_pos.reshape(B, -1), (B, Lq)).astype(jnp.int32)
    bt = block_table.astype(jnp.int32)

    def page_of(bh, jk, bt_ref, *_):
        return jnp.maximum(bt_ref[bh // KV, jk], 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, nb=nb, kv=KV, lq=Lq,
                          causal=causal, window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * KV, nb),
            in_specs=[
                pl.BlockSpec((1, Lq * G, dhp),
                             lambda bh, jk, *_: (bh, 0, 0)),
                pl.BlockSpec((1, 1, psp, dhp),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((1, 1, psp, dhp),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((None, 1, psp),
                             lambda bh, jk, *refs:
                             (page_of(bh, jk, *refs), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Lq * G, dhp),
                                   lambda bh, jk, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Lq * G, 1), jnp.float32),
                pltpu.VMEM((Lq * G, 1), jnp.float32),
                pltpu.VMEM((Lq * G, dhp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * KV, Lq * G, dhp), q.dtype),
        interpret=interpret, name="paged_decode",
    )(bt, qposr, qr, kt, vt, pposr)
    out = out.reshape(B, KV, Lq, G, dhp)[..., :dh]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Lq, H, dh)


def _page_pos_rows(page_pos, pps: int):
    """(n_pages, ps) -> (n_pages, 1, ps + pps), pad slots at -1: one page's
    positions are then a (1, psp) block that is whole in both tiled dims."""
    return jnp.pad(page_pos, ((0, 0), (0, pps)), constant_values=-1)[:, None]


def flash_paged_decode_ref(q, k_pages, v_pages, q_pos, block_table, page_pos,
                           *, causal: bool = True, window: int = 0,
                           scale: float | None = None):
    """Pure-jnp oracle / CPU serving path: gather the pool through the
    block table, then defer to :func:`flash_decode_ref`. Unmapped blocks
    gather page 0 (which may belong to another sequence) and are masked
    wholesale by forcing their positions to -1."""
    B = q.shape[0]
    n_pages, ps, KV, dh = k_pages.shape
    nb = block_table.shape[1]
    btc = jnp.maximum(block_table, 0)
    k = k_pages[btc].reshape(B, nb * ps, KV, dh)
    v = v_pages[btc].reshape(B, nb * ps, KV, dh)
    spos = jnp.where(block_table[..., None] >= 0, page_pos[btc], -1)
    return flash_decode_ref(q, k, v, q_pos, spos.reshape(B, nb * ps),
                            causal=causal, window=window, scale=scale)


def flash_paged_decode(q, k_pages, v_pages, q_pos, block_table, page_pos, *,
                       causal: bool = True, window: int = 0,
                       use_pallas: bool | None = None,
                       scale: float | None = None):
    """Dispatch: Pallas paged kernel on TPU, jnp gather+reference elsewhere.

    Row-independence over the batch dim holds exactly as in the dense
    path — pages are exclusively owned by one sequence, so the serving
    parity invariant (batched == solo tokens) carries over.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return flash_paged_decode_kernel(
            q, k_pages, v_pages, q_pos, block_table, page_pos, causal=causal,
            window=window, interpret=jax.default_backend() != "tpu",
            scale=scale)
    return flash_paged_decode_ref(q, k_pages, v_pages, q_pos, block_table,
                                  page_pos, causal=causal, window=window,
                                  scale=scale)


def _dequant_tile(qt, sc, bits: int, group: int):
    """Dequantize one kv tile in VMEM: (psp, dhqp) int8 pages + (psp, ngr)
    f32 scales -> (psp, W) f32.

    int4 tiles unpack to ``[low nibbles | high nibbles]``, i.e. the even
    head dims in lanes [0, dhqp) and the odd ones in [dhqp, 2*dhqp): two
    lane-aligned halves joined by a concat, because interleaving them back
    into dim order is a shape cast Mosaic does not lower. The wrapper
    permutes q to that order and the output back. Grouped scales expand to
    the lane layout by one select per group (a lane-splitting reshape does
    not lower either); pad lanes hold zeros and need no scale.
    """
    x = qt.astype(jnp.int32)
    if bits == 4:
        x = jnp.concatenate([jnp.right_shift(jnp.left_shift(x, 28), 28),
                             jnp.right_shift(x, 4)], axis=-1)
    x = x.astype(jnp.float32)
    ngr = sc.shape[-1]
    if ngr == 1:
        return x * sc
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[-1]), 1)
    if bits == 4:
        half = qt.shape[-1]
        odd = (lane >= half).astype(jnp.int32)
        dim = 2 * (lane - odd * half) + odd
    else:
        dim = lane
    full = jnp.zeros_like(x)
    for j in range(ngr):
        in_group = (dim >= j * group) & (dim < (j + 1) * group)
        full = jnp.where(in_group, sc[:, j:j + 1], full)
    return x * full


def _quant_paged_decode_kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, ks_ref,
                               vs_ref, ppos_ref, o_ref, m_ref, l_ref, acc_ref,
                               *, nb: int, kv: int, lq: int, causal: bool,
                               window: int, scale: float, bits: int,
                               group: int):
    bh = pl.program_id(0)
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = bt_ref[bh // kv, jk]

    @pl.when(page >= 0)
    def _tile():
        q = q_ref[0].astype(jnp.float32)                  # (lq*G, W)
        k = _dequant_tile(k_ref[0, 0], ks_ref[0, 0], bits, group)
        v = _dequant_tile(v_ref[0, 0], vs_ref[0, 0], bits, group)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                          # (lq*G, psp)

        qpos = _row_positions(qpos_ref, bh // kv, lq, q_ref.shape[1] // lq)
        spos = ppos_ref[...]                               # (1, psp)
        mask = spos >= 0
        if causal:
            mask = mask & (spos <= qpos)
        if window > 0:
            mask = mask & (qpos - spos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(jk == nb - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret")
)
def flash_paged_decode_quant_kernel(q, k_pages, v_pages, k_scale, v_scale,
                                    q_pos, block_table, page_pos, *,
                                    causal: bool = True, window: int = 0,
                                    interpret: bool = True):
    """Paged decode over int8/int4 pages with dequantization fused into
    the kv gather. Shapes as :func:`flash_paged_decode_kernel` plus
    k_scale/v_scale ``(n_pages, page_size, KV, ngr)`` f32 — the scales
    ride as extra operand blocks gathered through the same block-table
    index maps, so a tile's scales land in VMEM alongside its pages and
    the fp32 K/V only ever exists one tile at a time.

    The static format is derived from shapes: int4 iff the page's last
    dim is half the query head_dim (nibble-packed); the scale-group width
    is ``dh // ngr``.
    """
    B, Lq, H, dh = q.shape
    n_pages, ps, KV, dhq = k_pages.shape
    bits = 8 if dhq == dh else 4
    assert dhq == (dh if bits == 8 else dh // 2), (dhq, dh)
    ngr = k_scale.shape[-1]
    group = dh // ngr
    nb = block_table.shape[1]
    G = H // KV
    scale = dh ** -0.5
    dhqp = dhq + (-dhq) % 128
    W = dhqp if bits == 8 else 2 * dhqp   # dequantized tile width
    pps = (-ps) % 8
    psp = ps + pps

    if bits == 4:   # q in the tile's [even dims | odd dims] lane order
        q = jnp.concatenate(
            [jnp.pad(q[..., 0::2], ((0, 0),) * 3 + ((0, dhqp - dhq),)),
             jnp.pad(q[..., 1::2], ((0, 0),) * 3 + ((0, dhqp - dhq),))],
            axis=-1)
    qr = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, W - q.shape[-1])))
    qr = qr.reshape(B, Lq, KV, G, W).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B * KV, Lq * G, W)
    kt = jnp.pad(k_pages, ((0, 0), (0, pps), (0, 0), (0, dhqp - dhq))
                 ).transpose(2, 0, 1, 3)        # (KV, n_pages, psp, dhqp)
    vt = jnp.pad(v_pages, ((0, 0), (0, pps), (0, 0), (0, dhqp - dhq))
                 ).transpose(2, 0, 1, 3)
    kst = jnp.pad(k_scale, ((0, 0), (0, pps), (0, 0), (0, 0))
                  ).transpose(2, 0, 1, 3)       # (KV, n_pages, psp, ngr)
    vst = jnp.pad(v_scale, ((0, 0), (0, pps), (0, 0), (0, 0))
                  ).transpose(2, 0, 1, 3)
    pposr = _page_pos_rows(page_pos, pps)
    qposr = jnp.broadcast_to(q_pos.reshape(B, -1), (B, Lq)).astype(jnp.int32)
    bt = block_table.astype(jnp.int32)

    def page_of(bh, jk, bt_ref, *_):
        return jnp.maximum(bt_ref[bh // KV, jk], 0)

    out = pl.pallas_call(
        functools.partial(_quant_paged_decode_kernel, nb=nb, kv=KV, lq=Lq,
                          causal=causal, window=window, scale=scale,
                          bits=bits, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * KV, nb),
            in_specs=[
                pl.BlockSpec((1, Lq * G, W),
                             lambda bh, jk, *_: (bh, 0, 0)),
                pl.BlockSpec((1, 1, psp, dhqp),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((1, 1, psp, dhqp),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((1, 1, psp, ngr),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((1, 1, psp, ngr),
                             lambda bh, jk, *refs:
                             (bh % KV, page_of(bh, jk, *refs), 0, 0)),
                pl.BlockSpec((None, 1, psp),
                             lambda bh, jk, *refs:
                             (page_of(bh, jk, *refs), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Lq * G, W),
                                   lambda bh, jk, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Lq * G, 1), jnp.float32),
                pltpu.VMEM((Lq * G, 1), jnp.float32),
                pltpu.VMEM((Lq * G, W), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * KV, Lq * G, W), q.dtype),
        interpret=interpret, name="paged_decode_quant",
    )(bt, qposr, qr, kt, vt, kst, vst, pposr)
    out = out.reshape(B, KV, Lq, G, W)
    if bits == 4:
        out = jnp.stack([out[..., :dhq], out[..., dhqp:dhqp + dhq]], axis=-1)
    out = out.reshape(B, KV, Lq, G, -1)[..., :dh]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Lq, H, dh)


def flash_paged_decode_quant_ref(q, k_pages, v_pages, k_scale, v_scale,
                                 q_pos, block_table, page_pos, *,
                                 causal: bool = True, window: int = 0):
    """jnp oracle / CPU serving path: dequantize the pools wholesale, then
    defer to the fp paged reference — bit-for-bit the same rounding as the
    fused kernel (both go int -> f32 -> scale multiply)."""
    dh = q.shape[-1]
    k = dequantize_kv(k_pages, k_scale, dh)
    v = dequantize_kv(v_pages, v_scale, dh)
    return flash_paged_decode_ref(q, k, v, q_pos, block_table, page_pos,
                                  causal=causal, window=window)


def flash_paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale, q_pos,
                             block_table, page_pos, *, causal: bool = True,
                             window: int = 0, use_pallas: bool | None = None):
    """Dispatch: fused-dequant Pallas kernel on TPU, jnp dequant+reference
    elsewhere. Same row-independence guarantees as the fp paged path."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return flash_paged_decode_quant_kernel(
            q, k_pages, v_pages, k_scale, v_scale, q_pos, block_table,
            page_pos, causal=causal, window=window,
            interpret=jax.default_backend() != "tpu")
    return flash_paged_decode_quant_ref(
        q, k_pages, v_pages, k_scale, v_scale, q_pos, block_table, page_pos,
        causal=causal, window=window)


def _shard_fold(a, dp: int):
    """(B, ...) -> (dp, B/dp, ...): slot-major contiguous chunks, so shard
    ``s`` owns slots [s*B/dp, (s+1)*B/dp) — the same slot->shard map the
    engine's admission uses."""
    return a.reshape(dp, a.shape[0] // dp, *a.shape[1:])


def flash_sharded_paged_decode(q, k_pages, v_pages, q_pos, block_table,
                               page_pos, *, causal: bool = True,
                               window: int = 0,
                               use_pallas: bool | None = None,
                               scale: float | None = None):
    """Paged decode against per-shard page pools (disaggregated serving).

    Pool leaves carry a leading shard axis — k_pages/v_pages
    ``(dp, n_pages_shard, page_size, KV, dh)``, block_table ``(dp, B/dp,
    nb)`` with page ids LOCAL to the shard, page_pos ``(dp, n_pages_shard,
    page_size)`` — while q ``(B, 1, H, dh)`` / q_pos ``(B,)`` stay
    slot-major over the whole batch. The jnp path vmaps the per-shard
    reference over the shard axis, so every gather stays inside its own
    shard's pool and GSPMD partitions the whole step shard-locally (no
    cross-device gathers). The Pallas path folds the shard axis into the
    pool axis and offsets each shard's (local) block-table ids by
    ``shard * n_pages_shard`` — page ids only ever point into their own
    shard's page range, so the gathers are physically shard-local there
    too; a real-TPU mesh deployment should wrap this in shard_map so the
    partitioner can see that (the interpret-mode parity tests pin the
    numerics either way).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    dp, npl = k_pages.shape[0], k_pages.shape[1]
    B = q.shape[0]
    assert B % dp == 0, (B, dp)
    if use_pallas:
        nb = block_table.shape[-1]
        kg = k_pages.reshape(dp * npl, *k_pages.shape[2:])
        vg = v_pages.reshape(dp * npl, *v_pages.shape[2:])
        pg = page_pos.reshape(dp * npl, page_pos.shape[-1])
        off = (jnp.arange(dp, dtype=jnp.int32) * npl)[:, None, None]
        btg = jnp.where(block_table >= 0, block_table + off, -1)
        return flash_paged_decode_kernel(
            q, kg, vg, q_pos, btg.reshape(B, nb), pg, causal=causal,
            window=window, interpret=jax.default_backend() != "tpu",
            scale=scale)
    fn = functools.partial(flash_paged_decode_ref, causal=causal,
                           window=window, scale=scale)
    out = jax.vmap(fn)(_shard_fold(q, dp), k_pages, v_pages,
                       _shard_fold(q_pos, dp), block_table, page_pos)
    return out.reshape(B, *out.shape[2:])


def flash_sharded_paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale,
                                     q_pos, block_table, page_pos, *,
                                     causal: bool = True, window: int = 0,
                                     use_pallas: bool | None = None):
    """Quantized counterpart of :func:`flash_sharded_paged_decode`: int
    pages + fp32 scales carry the same leading shard axis; dequantization
    stays fused per shard (vmapped ref) or per globalized tile (kernel)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    dp, npl = k_pages.shape[0], k_pages.shape[1]
    B = q.shape[0]
    assert B % dp == 0, (B, dp)
    if use_pallas:
        nb = block_table.shape[-1]
        glob = lambda a: a.reshape(dp * npl, *a.shape[2:])
        off = (jnp.arange(dp, dtype=jnp.int32) * npl)[:, None, None]
        btg = jnp.where(block_table >= 0, block_table + off, -1)
        return flash_paged_decode_quant_kernel(
            q, glob(k_pages), glob(v_pages), glob(k_scale), glob(v_scale),
            q_pos, btg.reshape(B, nb), glob(page_pos), causal=causal,
            window=window, interpret=jax.default_backend() != "tpu")
    fn = functools.partial(flash_paged_decode_quant_ref, causal=causal,
                           window=window)
    out = jax.vmap(fn)(_shard_fold(q, dp), k_pages, v_pages, k_scale,
                       v_scale, _shard_fold(q_pos, dp), block_table,
                       page_pos)
    return out.reshape(B, *out.shape[2:])


def flash_decode(q, k, v, q_pos, slot_pos, *, causal: bool = True,
                 window: int = 0, use_pallas: bool | None = None):
    """Dispatch: Pallas kernel on TPU, jnp reference math elsewhere.

    Both paths are row-independent over the batch dim, so batched decode is
    bit-identical per sequence to a batch-of-1 run (the continuous-batching
    invariant the serving tests pin down).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return flash_decode_kernel(q, k, v, q_pos, slot_pos, causal=causal,
                                   window=window,
                                   interpret=jax.default_backend() != "tpu")
    return flash_decode_ref(q, k, v, q_pos, slot_pos, causal=causal,
                            window=window)
