"""Residual block definitions per kind + per-kind cache plumbing.

Each kind implements:
  init_block(kind, cfg, rcfg, key, dtype)            -> (params, specs)
  block_train(kind, ...)(params, x, ...)             -> (x, aux)
  block_prefill(...)                                 -> (x, cache, aux)
  block_decode(...)(params, x, pos, cache, ...)      -> (x, cache)
  init_block_cache(kind, cfg, B, max_len, dtype)     -> cache pytree

Compression is no longer a single global policy: ``block_train`` receives
a ``SiteCtx`` (core/plan.py) which resolves each projection *role*
(attn.qkv, ffn.gate, ssm.in, ...) to that site's policy and accumulates
per-site telemetry. The old ``policy_for`` kind-level dispatch lives on
only inside the legacy-RunConfig shim (plan.resolved_from_policy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import P, ffn, ffn_sites, init_ffn, init_rms_norm, rms_norm


def _window_for(kind: str, cfg) -> int:
    if kind == "swa":
        return cfg.sliding_window
    if kind == "latt":
        return cfg.local_window
    return 0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_block(kind: str, cfg, key, dtype, *, n_kv_eff: int | None = None,
               e_pad: int = 0):
    ks = jax.random.split(key, 4)
    params: dict = {}
    specs: dict = {}
    params["norm1"], specs["norm1"] = init_rms_norm(cfg.d_model, dtype)

    if kind in ("attn", "swa", "latt", "moe"):
        params["attn"], specs["attn"] = attn_lib.init_attention(
            ks[0], cfg, dtype, n_kv_eff=n_kv_eff
        )
        params["norm2"], specs["norm2"] = init_rms_norm(cfg.d_model, dtype)
        if kind == "moe":
            params["ffn"], specs["ffn"] = moe_lib.init_moe(ks[1], cfg, dtype, e_pad=e_pad)
        else:
            params["ffn"], specs["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif kind == "xattn":
        params["attn"], specs["attn"] = attn_lib.init_attention(
            ks[0], cfg, dtype, cross=True, n_kv_eff=n_kv_eff
        )
        params["norm2"], specs["norm2"] = init_rms_norm(cfg.d_model, dtype)
        params["ffn"], specs["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
        params["gate_ffn"] = jnp.zeros((), dtype)
        specs["gate_ffn"] = P(())
    elif kind == "rec":
        params["rec"], specs["rec"] = rglru_lib.init_rglru(ks[0], cfg, dtype)
        params["norm2"], specs["norm2"] = init_rms_norm(cfg.d_model, dtype)
        params["ffn"], specs["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif kind == "ssm":
        params["ssm"], specs["ssm"] = ssm_lib.init_ssm(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    return params, specs


# ---------------------------------------------------------------------------
# train / prefill / decode
# ---------------------------------------------------------------------------
def block_train(kind, cfg, rcfg, ctx, params, x, positions, extras, key, aux,
                *, want_cache: bool = False, max_len: int = 0,
                cache_positions=None):
    """Returns (x, aux, cache_or_None). ``ctx`` is this block's SiteCtx.

    ``cache_positions``: positions used for the prefill KV-cache insert
    when they differ from the attention positions — a length-bucketed
    prompt marks its pad rows -1 here so they are dropped instead of
    written (critical for ring caches, where a pad row would *evict* a
    real tail token, not just sit masked).
    """
    cache = None
    h = rms_norm(x, params["norm1"], cfg.norm_eps)

    if kind in ("attn", "swa", "latt", "moe"):
        out, (k_roped, v) = attn_lib.attn_train(
            params["attn"], h, positions, cfg, ctx, key,
            window=_window_for(kind, cfg), chunk=rcfg.attn_chunk,
            flash_sdp=rcfg.flash_sdp,
            # The flash kernel pair has a custom VJP (fwd+bwd Pallas), so
            # RunConfig.attn_kernel governs the differentiated training
            # path and prefill alike.
            kernel=attn_lib.use_attn_kernel(rcfg),
            ring_block=getattr(rcfg, "ring_block", 0),
        )
        x = x + out
        if want_cache:
            win = _window_for(kind, cfg)
            size = min(max_len, win) if win else max_len
            kvc = attn_lib.init_kv_cache(
                x.shape[0], size, k_roped.shape[2], k_roped.shape[3], x.dtype, bool(win)
            )
            cache = attn_lib.cache_insert(
                kvc, k_roped, v,
                positions if cache_positions is None else cache_positions)
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind == "moe":
            out2, a = moe_lib.moe_ffn(params["ffn"], h2, cfg,
                                      gather_dispatch=rcfg.moe_gather_dispatch,
                                      token_blocks=rcfg.moe_token_blocks,
                                      ctx=ctx, key=key)
            aux = aux + a
        else:
            out2 = ffn_sites(params["ffn"], h2, ctx, key)
        x = x + out2

    elif kind == "xattn":
        out, (k_img, v_img) = attn_lib.cross_attn(
            params["attn"], h, extras["image_embeds"], cfg, ctx, key,
            chunk=rcfg.attn_chunk, flash_sdp=rcfg.flash_sdp,
        )
        x = x + out
        if want_cache:
            cache = (k_img, v_img)
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + jnp.tanh(params["gate_ffn"].astype(x.dtype)) * ffn_sites(
            params["ffn"], h2, ctx, key
        )

    elif kind == "rec":
        res = rglru_lib.rglru_train(params["rec"], h, cfg, ctx, key, return_cache=want_cache)
        out, cache = res if want_cache else (res, None)
        x = x + out
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + ffn_sites(params["ffn"], h2, ctx, key)

    elif kind == "ssm":
        res = ssm_lib.ssm_train(params["ssm"], h, cfg, ctx, key, return_cache=want_cache)
        out, cache = res if want_cache else (res, None)
        x = x + out
    else:
        raise ValueError(kind)
    return x, aux, cache


# ---------------------------------------------------------------------------
# reversible two-stream blocks (RevNet / olmax `reversible` idiom)
# ---------------------------------------------------------------------------
BLOCK_STRUCTURES = ("residual", "reversible", "reversible_ref")

# Kinds with the two-sublayer mixer/FFN split the F/G decomposition needs:
#   y1 = x1 + F(x2)    F = norm1 -> attention / recurrence
#   y2 = x2 + G(y1)    G = norm2 -> (Mo)FFN
# ssm blocks are single-sublayer (stream 2 would never update), and xattn
# threads cross-modal extras plus a learned gate through its FFN; both stay
# residual-only.
REVERSIBLE_KINDS = ("attn", "swa", "latt", "moe", "rec")


# Kinds a context-parallel (ring attention) mesh can shard over sequence:
# attention kinds dispatch to the ring inside the shard_map body; moe's
# mixer is attention too. rec/ssm are sequence-recurrent (a scan over L
# cannot split across devices without a different parallelism scheme) and
# xattn consumes full-sequence cross-modal extras.
CONTEXT_PARALLEL_KINDS = ("attn", "swa", "latt", "moe")


def resolve_block_structure(cfg, rcfg, *, cp: int = 1) -> str:
    """Validate ``rcfg.block_structure`` against the architecture, remat,
    and the executor's context-parallel degree ``cp``.

    ``reversible_ref`` is the same two-stream math under plain autodiff
    (every (y1, y2) carry is saved) — the parity and memory baseline for
    the memory-saving custom_vjp path, not a setting for real runs.
    """
    structure = getattr(rcfg, "block_structure", "residual") or "residual"
    if structure not in BLOCK_STRUCTURES:
        raise ValueError(
            f"RunConfig.block_structure={structure!r}: must be one of "
            f"{BLOCK_STRUCTURES}")
    if cp > 1:
        bad = sorted({k for unit, _ in cfg.stages for k in unit
                      if k not in CONTEXT_PARALLEL_KINDS})
        if bad:
            raise ValueError(
                f"context parallelism (cp={cp}) supports block kinds "
                f"{CONTEXT_PARALLEL_KINDS}; stage kind(s) {bad} are "
                f"sequence-recurrent or consume full-sequence extras and "
                f"cannot shard over the sequence axis. Drop --mesh-context "
                f"for this architecture.")
        if structure != "residual":
            raise ValueError(
                f"block_structure={structure!r} x context parallelism "
                f"(cp={cp}) is invalid: the reversible stage's custom_vjp "
                f"re-runs F (which now contains the ring's ppermute "
                f"collectives) during stream reconstruction, and the ring's "
                f"own custom_vjp cannot nest inside that replay without "
                f"re-synchronizing every shard per stage. Use "
                f"block_structure='residual' with --mesh-context, or "
                f"cp=1 with reversible blocks.")
    if structure == "residual":
        return structure
    bad = sorted({k for unit, _ in cfg.stages for k in unit
                  if k not in REVERSIBLE_KINDS})
    if bad:
        raise ValueError(
            f"block_structure={structure!r} supports kinds "
            f"{REVERSIBLE_KINDS}; stage kind(s) {bad} have no two-sublayer "
            f"F/G split (ssm is single-sublayer, xattn consumes cross-modal "
            f"extras). Use block_structure='residual' for this architecture.")
    if rcfg.remat != "none":
        raise ValueError(
            f"remat={rcfg.remat!r} x block_structure={structure!r} is "
            f"invalid: the reversible backward already reconstructs the "
            f"residual stream from the stage outputs, and jax.checkpoint "
            f"around the stage would re-save the very (y1, y2) carries it "
            f"erases, then recompute F/G a second time on top. Use "
            f"remat='none' with reversible blocks; remat='full'|'pamm' "
            f"belongs to block_structure='residual'.")
    return structure


def block_f(kind, cfg, rcfg, ctx, params, x, positions, key):
    """First reversible sublayer (token mixer): norm1 -> attn/recurrence.

    Returns the pre-residual output; the caller forms ``y1 = x1 + F(x2)``.
    """
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ("attn", "swa", "latt", "moe"):
        out, _ = attn_lib.attn_train(
            params["attn"], h, positions, cfg, ctx, key,
            window=_window_for(kind, cfg), chunk=rcfg.attn_chunk,
            flash_sdp=rcfg.flash_sdp, kernel=attn_lib.use_attn_kernel(rcfg),
            ring_block=getattr(rcfg, "ring_block", 0),
        )
        return out
    if kind == "rec":
        return rglru_lib.rglru_train(params["rec"], h, cfg, ctx, key)
    raise ValueError(f"kind {kind!r} has no reversible F sublayer")


def block_g(kind, cfg, rcfg, ctx, params, y1, key):
    """Second reversible sublayer: norm2 -> (Mo)FFN.

    Returns ``(G(y1), aux_delta)``; the caller forms ``y2 = x2 + G(y1)``
    and accumulates the (MoE balance) aux loss.
    """
    h2 = rms_norm(y1, params["norm2"], cfg.norm_eps)
    if kind == "moe":
        return moe_lib.moe_ffn(params["ffn"], h2, cfg,
                               gather_dispatch=rcfg.moe_gather_dispatch,
                               token_blocks=rcfg.moe_token_blocks,
                               ctx=ctx, key=key)
    if kind in ("attn", "swa", "latt", "rec"):
        return ffn_sites(params["ffn"], h2, ctx, key), jnp.float32(0)
    raise ValueError(f"kind {kind!r} has no reversible G sublayer")


def _rev_anchor(rcfg, t):
    # Same block-boundary sharding anchors as the residual path (model.py):
    # seq-sharded between blocks under Megatron SP, else batch-sharded and
    # replicated over the model axis. No-op without a mesh in context.
    from repro.runtime.sharding import maybe_constrain

    if rcfg.seq_shard:
        return maybe_constrain(t, ("batch", "ffn", None))
    return maybe_constrain(t, ("batch", None, "embed"))


def _two_sum(a, b):
    """Knuth TwoSum: s = fl(a + b) and its exact rounding error e."""
    s = a + b
    z = s - a
    e = (a - (s - z)) + (b - z)
    return s, e


def _dd_add(hi, lo, b):
    """Compensated stream add: (hi, lo) + b -> renormalized (hi, lo).

    The two-stream carries ride as double-word (hi, lo) pairs because the
    naive revnet inverse ``x = (x + f) - f`` loses the rounding error of
    the forward add — ~1 ulp per layer, compounding through the
    layer-by-layer reconstruction and amplified ~10^3 x through the
    attention vjps (measured ~1.5e-4 relative on f32 llama-tiny grads).
    With the error term carried in ``lo``, add/subtract round-trips are
    exact to O(eps^2) and the backward reconstructs the forward's hi
    stream bit-for-bit. Sublayers consume only ``hi``; under plain
    autodiff TwoSum's error channel has an exactly-zero jacobian, so
    gradients flow as if the adds were plain — the custom bwd relies on
    both properties.
    """
    s, e = _two_sum(hi, b)
    return _two_sum(s, lo + e)


def _rev_stage_primal(cfg, rcfg, unit, si, resolved, positions):
    """Forward runner for one reversible stage: scan of two-stream layers."""

    def body(carry, xs):
        x1h, x1l, x2h, x2l, aux, tele = carry
        bparams, kd = xs
        k_r = jax.random.wrap_key_data(kd)
        for bi, kind in enumerate(unit):
            ctx = resolved.ctx(si, kind, tele)
            bkey = jax.random.fold_in(k_r, bi)
            f_out = block_f(kind, cfg, rcfg, ctx, bparams[bi], x2h,
                            positions, bkey)
            x1h, x1l = _dd_add(x1h, x1l, f_out)          # y1 = x1 + F(x2)
            g_out, a = block_g(kind, cfg, rcfg, ctx, bparams[bi], x1h, bkey)
            x2h, x2l = _dd_add(x2h, x2l, g_out)          # y2 = x2 + G(y1)
            tele = ctx.tele
            aux = aux + a
            x1h, x1l = _rev_anchor(rcfg, x1h), _rev_anchor(rcfg, x1l)
            x2h, x2l = _rev_anchor(rcfg, x2h), _rev_anchor(rcfg, x2l)
        return (x1h, x1l, x2h, x2l, aux, tele), None

    def primal(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data):
        (x1h, x1l, x2h, x2l, aux, tele), _ = jax.lax.scan(
            body, (x1h, x1l, x2h, x2l, aux, tele), (unit_params, key_data))
        return x1h, x1l, x2h, x2l, aux, tele

    return primal


def reversible_stage(cfg, rcfg, unit, si, resolved, unit_params,
                     x1h, x1l, x2h, x2l, aux, tele, positions, key_data, *,
                     save_memory: bool = True):
    """Run one (unit x rep) stage of the two-stream reversible stack.

    Streams are compensated (hi, lo) pairs — see :func:`_dd_add`.

    ``save_memory=True`` wraps the whole stage scan in one ``jax.custom_vjp``
    whose residuals are only the stage OUTPUT streams plus params/keys — no
    per-layer residual-stream activation survives the forward pass (a
    per-block vjp would not achieve this: ``lax.scan`` saves its carries
    per iteration). The backward walks layers top-down (a ``reverse=True``
    scan), reconstructs each layer's inputs exactly

        x2 = y2 - G(y1)        then        x1 = y1 - F(x2)

    and accumulates parameter cotangents with per-sublayer ``jax.vjp`` —
    so the PAMM/compact custom_vjps and the Pallas flash bwd kernel run
    inside the reconstruction exactly as they would under plain autodiff,
    with one layer's activations live at a time.

    ``save_memory=False`` ("reversible_ref") is the same math under plain
    autodiff, used as the grad-parity and memory-accounting baseline.

    ``key_data``: raw uint32 key data of the per-layer keys, shape
    ``(rep, ...)`` — integer inputs take float0 cotangents through the
    custom_vjp where a typed key array could not.
    """
    primal = _rev_stage_primal(cfg, rcfg, unit, si, resolved, positions)
    if not save_memory:
        return primal(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data)

    @jax.custom_vjp
    def run(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data):
        return primal(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data)

    def run_fwd(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data):
        out = primal(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data)
        y1h, y1l, y2h, y2l, _, _ = out
        return out, (unit_params, y1h, y1l, y2h, y2l, key_data)

    def run_bwd(res, cts):
        unit_params, y1h, y1l, y2h, y2l, key_data = res
        # TwoSum's error channel has a zero jacobian, so the lo outputs
        # carry no gradient into the stage (dy1l/dy2l are dropped exactly
        # as plain autodiff of the primal would), while the lo INPUTS feed
        # the hi chain with coefficient 1 — dx?l equals the hi cotangent.
        dy1, _dy1l, dy2, _dy2l, daux, dtele = cts

        def back(carry, xs):
            y1h, y1l, y2h, y2l, dy1, dy2 = carry
            bparams, kd = xs
            k_r = jax.random.wrap_key_data(kd)
            dparams = [None] * len(unit)
            for bi in reversed(range(len(unit))):
                kind = unit[bi]
                bkey = jax.random.fold_in(k_r, bi)
                p = bparams[bi]

                # Telemetry was already accumulated in the forward pass;
                # the recompute uses a recording-free ctx.
                def g_fn(p_, y1_, kind=kind, bkey=bkey):
                    return block_g(kind, cfg, rcfg,
                                   resolved.ctx(si, kind, None), p_, y1_, bkey)

                # Reconstruct with a PLAIN primal call (the same jaxpr the
                # forward traced), not jax.vjp's linearized primal — the
                # partial-eval trace reorders the math enough that its
                # output can drift ~1 ulp from the forward's, and drift
                # compounds through the layer-by-layer reconstruction.
                g_out, _a = g_fn(p, y1h)
                x2h, x2l = _dd_add(y2h, y2l, -g_out)
                _, g_vjp = jax.vjp(g_fn, p, y1h)
                dpg, dy1_g = g_vjp((dy2, daux))
                dy1 = dy1 + dy1_g

                def f_fn(p_, x2_, kind=kind, bkey=bkey):
                    return block_f(kind, cfg, rcfg,
                                   resolved.ctx(si, kind, None), p_, x2_,
                                   positions, bkey)

                x1h, x1l = _dd_add(y1h, y1l, -f_fn(p, x2h))
                _, f_vjp = jax.vjp(f_fn, p, x2h)
                dpf, dx2_f = f_vjp(dy1)
                dparams[bi] = jax.tree.map(jnp.add, dpg, dpf)
                dy2 = dy2 + dx2_f
                y1h, y1l = _rev_anchor(rcfg, x1h), _rev_anchor(rcfg, x1l)
                y2h, y2l = _rev_anchor(rcfg, x2h), _rev_anchor(rcfg, x2l)
            return (y1h, y1l, y2h, y2l, dy1, dy2), dparams

        (_, _, _, _, dx1, dx2), dups = jax.lax.scan(
            back, (y1h, y1l, y2h, y2l, dy1, dy2), (unit_params, key_data),
            reverse=True)
        dkd = jax.tree.map(
            lambda t: np.zeros(t.shape, dtype=jax.dtypes.float0), key_data)
        return dups, dx1, dx1, dx2, dx2, daux, dtele, dkd

    run.defvjp(run_fwd, run_bwd)
    return run(unit_params, x1h, x1l, x2h, x2l, aux, tele, key_data)


def block_decode(kind, cfg, rcfg, params, x, positions, cache, extras):
    """One-step decode. x: (B, 1, d). Returns (x, new_cache)."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)

    if kind in ("attn", "swa", "latt", "moe"):
        out, cache = attn_lib.attn_decode(
            params["attn"], h, positions, cache, cfg,
            window=_window_for(kind, cfg), kernel=attn_lib.use_attn_kernel(rcfg),
        )
        x = x + out
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind == "moe":
            out2, _ = moe_lib.moe_ffn(params["ffn"], h2, cfg,
                                      gather_dispatch=rcfg.moe_gather_dispatch,
                                      token_blocks=rcfg.moe_token_blocks)
        else:
            out2 = ffn(params["ffn"], h2)
        x = x + out2

    elif kind == "xattn":
        out = attn_lib.cross_attn_decode(params["attn"], h, cache, cfg,
                                         kernel=attn_lib.use_attn_kernel(rcfg))
        x = x + out
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + jnp.tanh(params["gate_ffn"].astype(x.dtype)) * ffn(params["ffn"], h2)

    elif kind == "rec":
        out, cache = rglru_lib.rglru_decode(params["rec"], h, cache, cfg)
        x = x + out
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + ffn(params["ffn"], h2)

    elif kind == "ssm":
        out, cache = ssm_lib.ssm_decode(params["ssm"], h, cache, cfg)
        x = x + out
    else:
        raise ValueError(kind)
    return x, cache


def block_cache_specs(kind, cfg, *, shard_cache_seq: bool = False):
    """Logical axis names for the (layer-stacked) decode cache pytree.

    ``shard_cache_seq``: shard the KV-cache sequence dim over the data axis
    (flash-decoding style) — used for long_500k (global_batch=1) where the
    batch axis cannot feed 16 data shards.
    """
    seq_ax = "batch" if shard_cache_seq else None
    bat_ax = None if shard_cache_seq else "batch"
    if kind in ("attn", "swa", "latt", "moe"):
        return attn_lib.KVCache(
            k=("layers", bat_ax, seq_ax, "heads", None),
            v=("layers", bat_ax, seq_ax, "heads", None),
            slot_pos=("layers", bat_ax, seq_ax),
            ring=("layers",),
        )
    if kind == "xattn":
        return (
            ("layers", bat_ax, None, "heads", None),
            ("layers", bat_ax, None, "heads", None),
        )
    if kind == "rec":
        return rglru_lib.RGLRUCache(
            h=("layers", bat_ax, "ffn"),
            conv_state=("layers", bat_ax, None, "ffn"),
        )
    if kind == "ssm":
        return ssm_lib.SSMCache(
            state=("layers", bat_ax, "heads", None, None),
            conv_state=("layers", bat_ax, None, "ffn"),
        )
    raise ValueError(kind)


def init_block_cache(kind, cfg, B: int, max_len: int, dtype, *, n_kv_eff=None,
                     layout: str = "dense", page_size: int = 0,
                     pool_pages: int | None = None, cache_format=None):
    """Zero-initialized cache (used by serve_step input_specs and decoding).

    ``layout="paged"`` builds :class:`attention.PagedKVCache` for the
    self-attention kinds — a page pool of ``pool_pages`` pages of
    ``page_size`` tokens (default: the dense worst case, B x blocks/slot)
    — instead of the dense (B, S, KV, dh) slab. Ring (sliding-window)
    caches map to a bounded block table: the logical size is the dense
    ring size rounded up to whole pages, and wrap-around stays modulo
    arithmetic. Recurrent/SSM/cross-attn caches are O(1) or fixed-size
    per slot, so they keep their dense layout under either setting.

    ``cache_format`` (a compressed :class:`core.plan.CacheFormat`) swaps
    the page pool for its quantized / low-rank variant. ``pool_pages`` is
    a *byte budget* expressed in dense pages, so a compressed pool gets
    proportionally more pages at the same budget (capped at the dense
    worst case — extra capacity beyond "every slot full" is dead weight).
    """
    if kind in ("attn", "swa", "latt", "moe"):
        win = _window_for(kind, cfg)
        size = min(max_len, win) if win else max_len
        kv = n_kv_eff or cfg.n_kv_heads
        dh = cfg.head_dim
        compressed = cache_format is not None and cache_format.is_compressed
        if compressed and layout != "paged":
            raise ValueError(
                f"cache.kv={cache_format} requires cache_layout='paged' — "
                "the dense slab has no compressed storage path")
        if layout == "paged":
            if page_size < 1:
                raise ValueError(f"paged cache needs page_size >= 1, got {page_size}")
            logical = -(-size // page_size) * page_size
            worst = B * (logical // page_size)
            if compressed and pool_pages is not None:
                # same byte budget buys 1/ratio-sized tokens -> ratio x pages
                base_tb = jnp.zeros((), dtype).dtype.itemsize * 2 * kv * dh
                fmt_tb = cache_format.token_bytes(
                    kv, dh, jnp.zeros((), dtype).dtype.itemsize)
                pool_pages = int(pool_pages * base_tb // max(1, fmt_tb))
            n_pages = worst if pool_pages is None else min(pool_pages, worst)
            n_pages = max(1, n_pages)
            if compressed and cache_format.kind in ("int8", "int4"):
                bits = 8 if cache_format.kind == "int8" else 4
                return attn_lib.init_quant_paged_kv_cache(
                    B, logical, page_size, n_pages, kv, dh, bits,
                    cache_format.n_groups(dh), bool(win))
            if compressed and cache_format.kind == "svd":
                return attn_lib.init_svd_paged_kv_cache(
                    B, logical, page_size, n_pages, kv, dh,
                    cache_format.svd_rank(dh), dtype, bool(win))
            return attn_lib.init_paged_kv_cache(
                B, logical, page_size, n_pages, kv, dh, dtype, bool(win))
        return attn_lib.init_kv_cache(B, size, kv, dh, dtype, bool(win))
    if kind == "xattn":
        kv = n_kv_eff or cfg.n_kv_heads
        return (
            jnp.zeros((B, cfg.vision_tokens, kv, cfg.head_dim), dtype),
            jnp.zeros((B, cfg.vision_tokens, kv, cfg.head_dim), dtype),
        )
    if kind == "rec":
        return rglru_lib.init_rglru_cache(cfg, B, dtype)
    if kind == "ssm":
        return ssm_lib.init_ssm_cache(cfg, B, dtype)
    raise ValueError(kind)
