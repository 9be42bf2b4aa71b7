"""Shared neural building blocks (pure JAX, params = plain pytrees).

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
params pytree with *logical axis names* per dimension (tuples of str|None).
``repro.runtime.sharding`` maps logical names onto mesh axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

P = tuple  # logical partition spec: tuple of logical-axis names (or None)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(key, n_in: int, n_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(n_in)
    return (jax.random.normal(key, (n_in, n_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype):
    # zero-centred scale (applied as 1+scale), standard in Gemma/LLaMA-style code
    return jnp.zeros((d,), dtype), P(("embed",))


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, L, H, dh); positions: (B, L) int32."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                         # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B, L, dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------
def init_ffn(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }
    specs = {
        "w_gate": P(("embed", "ffn")),
        "w_up": P(("embed", "ffn")),
        "w_down": P(("ffn", "embed")),
    }
    return params, specs


def ffn(params, x):
    h = jax.nn.silu(x @ params["w_gate"].astype(x.dtype)) * (x @ params["w_up"].astype(x.dtype))
    return h @ params["w_down"].astype(x.dtype)


def ffn_sites(params, x, ctx, key):
    """SwiGLU FFN with gate/up/down as first-class compression sites.

    ``ctx`` is a plan SiteCtx (core/plan.py); with every role exact this is
    bit-identical to :func:`ffn`. Gate and up read the same x, so when both
    resolve to the same policy ONE compressed state backs both weight
    gradients (the paper's Fig.-2 sharing; telemetry lands on ffn.gate).
    """
    gate_site = ctx.site("ffn.gate")
    up_site = ctx.site("ffn.up")
    if (gate_site is not None and up_site is not None
            and up_site.shared_with == gate_site.path):
        (g, u), stats = gate_site.apply_shared(
            x, [params["w_gate"], params["w_up"]], [None, None], key
        )
        ctx.record(gate_site, stats)
    else:
        g = ctx.apply("ffn.gate", x, params["w_gate"], None, key)
        u = ctx.apply("ffn.up", x, params["w_up"], None, key)
    h = jax.nn.silu(g) * u
    # TP anchor: SwiGLU hidden sharded over 'model' so the down projection
    # closes with one all-reduce (no-op without a mesh in context).
    from repro.runtime.sharding import maybe_constrain

    h = maybe_constrain(h, ("batch", None, "ffn"))
    return ctx.apply("ffn.down", h, params["w_down"], None, key)


# ---------------------------------------------------------------------------
# causal depthwise conv (width w), used by mamba2 and RG-LRU branches
# ---------------------------------------------------------------------------
def causal_depthwise_conv(x: jax.Array, w: jax.Array, state: jax.Array | None = None):
    """x: (B, L, C); w: (W, C). Returns (y, new_state).

    ``state`` is the last W-1 inputs from the previous segment (B, W-1, C);
    None means zero history (training from position 0).
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, W-1+L, C)
    y = jnp.zeros_like(x)
    for i in range(width):
        y = y + xp[:, i : i + x.shape[1], :] * w[i][None, None, :].astype(x.dtype)
    new_state = xp[:, -(width - 1):, :] if width > 1 else state
    return y, new_state


def init_depthwise_conv(key, width: int, channels: int, dtype):
    w = (jax.random.normal(key, (width, channels)) / np.sqrt(width)).astype(dtype)
    return w, P((None, "embed"))


# ---------------------------------------------------------------------------
# chunked softmax cross-entropy (vocab-parallel friendly)
# ---------------------------------------------------------------------------
def chunked_cross_entropy(h, w_head, labels, mask, chunk: int,
                          valid_vocab: int | None = None,
                          site=None, key=None):
    """Mean token NLL without materializing (B, L, V) at once.

    h: (B, L, d) final hidden states; w_head: (d, V); labels: (B, L) int32;
    mask: (B, L) {0,1} float. Scans over sequence chunks; inside each chunk
    logits are (B, chunk, V) — with V sharded over 'model' this is the
    standard Megatron vocab-parallel cross-entropy pattern under GSPMD.

    ``site``/``key``: the plan's ``lm_head`` compression site. When given
    (and not exact), each chunk's hidden states are compressed for the
    head's weight gradient, and the call returns ``(loss, stats)`` with the
    site telemetry accumulated over chunks; otherwise returns ``loss``.
    """
    B, L, d = h.shape
    chunk = min(chunk, L)
    n_chunks = (L + chunk - 1) // chunk
    pad = n_chunks * chunk - L
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    v_total = w_head.shape[1]
    compressed = site is not None and not site.is_exact

    def body(carry, xs):
        tot_nll, tot_cnt, idx, stats_acc = carry
        hb, lb, mb = xs
        if compressed:
            z, stats = site.apply(hb, w_head, None, jax.random.fold_in(key, idx))
            logits = z.astype(jnp.float32)
            stats_acc = stats_acc + stats
        else:
            logits = (hb @ w_head.astype(hb.dtype)).astype(jnp.float32)
        if valid_vocab is not None and valid_vocab < v_total:
            col = jnp.arange(v_total)
            logits = jnp.where(col[None, None, :] < valid_vocab, logits, -1e30)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mb
        return (tot_nll + jnp.sum(nll), tot_cnt + jnp.sum(mb),
                idx + 1, stats_acc), None

    from repro.core.linear import STATS_LEN

    init = (jnp.float32(0), jnp.float32(0), jnp.int32(0),
            jnp.zeros((STATS_LEN,), jnp.float32))
    (tot, cnt, _, stats), _ = jax.lax.scan(body, init, (hc, lc, mc))
    loss = tot / jnp.maximum(cnt, 1.0)
    if site is not None:
        return loss, stats
    return loss
