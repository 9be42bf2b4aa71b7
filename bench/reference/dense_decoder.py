"""Plain reference of a dense pre-norm decoder (InternLM2 / Llama family).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time, with no kernels, caches or batching. It imports nothing
of the program under test: it reads the sizes from the benchmark's
configuration file and takes weights in its own layout (``init_weights``),
which the benchmark also hands, converted, to the program.

Model, per layer (``n_layers`` of them), on the residual stream ``x``:

    h = rmsnorm(x) * (1 + attention_norm)
    q, k, v = h @ wq, h @ wk, h @ wv           (heads of head_dim; GQA: query
                                                head i reads kv head i // G)
    q, k = rope(q), rope(k)                    (rotate-half, base rope_theta)
    x = x + softmax(q k^T / sqrt(head_dim), causal) v @ wo
    h = rmsnorm(x) * (1 + ffn_norm)
    x = x + (silu(h @ w1) * (h @ w3)) @ w2
logits = (rmsnorm(x) * (1 + norm)) @ output

Norm weights are kept as offsets from one, so random offsets of any size
stay exact in every dtype. Training adds the mean next-token NLL, PAMM
(arXiv:2506.02939, Alg. 1) for the QKV weight gradient where the cell
compresses it, global-norm clipping and AdamW.

``mode`` selects the arithmetic of every matrix product: ``"f32"`` (float32
at highest precision: the reference) or ``"fp8"`` (both operands scaled by
their absolute maximum and rounded to float8_e4m3fn, then multiplied in
float32: the control, one precision step below bfloat16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
LAYER_KEYS = ("attention_norm", "wq", "wk", "wv", "wo", "ffn_norm",
              "w1", "w3", "w2")


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], dh=d // h,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def weight_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    n, d, ff, v = s["layers"], s["d"], s["ff"], s["vocab"]
    hd, kvd = s["h"] * s["dh"], s["kv"] * s["dh"]
    return {
        "tok_embeddings": (v, d),
        "layers": {
            "attention_norm": (n, d), "wq": (n, d, hd), "wk": (n, d, kvd),
            "wv": (n, d, kvd), "wo": (n, hd, d), "ffn_norm": (n, d),
            "w1": (n, d, ff), "w3": (n, d, ff), "w2": (n, ff, d),
        },
        "norm": (d,),
        "output": (d, v),
    }


def init_weights(cfg: dict, key, dtype) -> dict:
    """Random weights from ``key``: embeddings N(0, 0.02^2), matrices
    N(0, 1/fan_in), norm offsets N(0, 0.1^2). Call under ``jax.jit``."""
    shapes = weight_shapes(cfg)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda t: isinstance(t, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda t: isinstance(t, tuple))[0]]
    keys = jax.random.split(key, len(flat))
    out = []
    for name, shape, k in zip(names, flat, keys):
        if "norm" in name:
            scale = 0.1
        elif "tok_embeddings" in name:
            scale = 0.02
        else:
            scale = shape[-2] ** -0.5
        out.append((jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype))
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _fp8(a):
    a = a.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    q = (a * (E4M3_MAX / amax)).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * (amax / E4M3_MAX)


def mm(a, b, mode: str, spec: str = "...ij,...jk->...ik"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, offset, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def rope(x, theta):
    """x: (L, heads, dh), positions 0..L-1, rotate-half convention."""
    L, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, mode: str, q_chunk: int):
    """Causal GQA attention, q: (L, H, dh), k/v: (L, KV, dh). Query rows
    go in chunks (each rematerialized in backward) so scores stay small."""
    L, H, dh = q.shape
    KV = k.shape[1]
    G = H // KV
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    cols = jnp.arange(L)

    @jax.checkpoint
    def block(qb, rows):
        s = mm(qb, kr, mode, "qhd,khd->hqk") * dh ** -0.5
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm(p, vr, mode, "hqk,khd->qhd")

    c = min(q_chunk, L)
    outs = [block(q[i:i + c], jnp.arange(i, min(i + c, L)))
            for i in range(0, L, c)]
    return jnp.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# PAMM (paper Alg. 1) for the QKV weight gradient
# ---------------------------------------------------------------------------
def pamm_key(run_seed: int, step: int, layer: int, n_layers: int):
    """The key that samples layer ``layer``'s generators at optimizer step
    ``step``: fold the step into the run's key, then the stage (0), split
    over the stacked layers, fold the block index (0) and the id of the
    ``attn.qkv`` site (0, the first site of a dense decoder)."""
    k = jax.random.fold_in(jax.random.key(run_seed), step)
    k = jax.random.fold_in(k, 0)
    k = jax.random.split(k, n_layers)[layer]
    return jax.random.fold_in(jax.random.fold_in(k, 0), 0)


def pamm_state(x2d, key, ratio: float):
    """Generators C (k rows of x2d drawn without replacement), each row's
    best generator by |cosine similarity|, its coefficient alpha and the
    de-bias factor beta (eps = inf: every row is kept)."""
    b = x2d.shape[0]
    k = max(1, min(b, math.ceil(ratio * b)))
    idx = jax.random.choice(key, b, shape=(k,), replace=False)
    x = x2d.astype(jnp.float32)
    c = x[idx]
    na = jnp.linalg.norm(x, axis=1)
    nc = na[idx]
    cs = jnp.einsum("bn,kn->bk", x, c, precision=HIGHEST) / (
        jnp.maximum(na[:, None], 1e-20) * jnp.maximum(nc[None, :], 1e-20))
    assign = jnp.argmax(jnp.abs(cs), axis=1).astype(jnp.int32)
    csel = jnp.take_along_axis(cs, assign[:, None], axis=1)[:, 0]
    alpha = csel * na / jnp.maximum(nc[assign], 1e-20)
    alpha = jnp.where(na > 0, alpha, 0.0)
    # eps = inf keeps every row, so nothing is dropped and beta = 1
    return c, alpha, assign, jnp.float32(1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _pamm_linear(x, w, c, alpha, assign, beta, mode):
    return mm(x, w, mode)


def _pamm_fwd(x, w, c, alpha, assign, beta, mode):
    return mm(x, w, mode), (w, c, alpha, assign, beta)


def _pamm_bwd(mode, res, g):
    w, c, alpha, assign, beta = res
    dx = mm(g, w.T, mode)
    bt = jax.ops.segment_sum(alpha[:, None] * g, assign,
                             num_segments=c.shape[0])
    dw = beta * mm(c.T, bt, mode)
    z = jnp.zeros_like
    return (dx, dw, z(c), z(alpha),
            np.zeros(assign.shape, jax.dtypes.float0), z(beta))


_pamm_linear.defvjp(_pamm_fwd, _pamm_bwd)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _layer(s, lw, x, mode, pamm=None, q_chunk=1024, collect=None):
    L = x.shape[0]
    h = rmsnorm(x, lw["attention_norm"], s["eps"])
    if collect is not None:
        collect.append(h)
    if pamm is None:
        q, k, v = (mm(h, lw[n], mode) for n in ("wq", "wk", "wv"))
    else:
        q, k, v = (_pamm_linear(h, lw[n], *pamm, mode)
                   for n in ("wq", "wk", "wv"))
    q = rope(q.reshape(L, s["h"], s["dh"]), s["theta"])
    k = rope(k.reshape(L, s["kv"], s["dh"]), s["theta"])
    v = v.reshape(L, s["kv"], s["dh"])
    o = attention(q, k, v, mode, q_chunk).reshape(L, -1)
    x = x + mm(o, lw["wo"], mode)
    h = rmsnorm(x, lw["ffn_norm"], s["eps"])
    f = jax.nn.silu(mm(h, lw["w1"], mode)) * mm(h, lw["w3"], mode)
    return x + mm(f, lw["w2"], mode)


def _layer_weights(w, i):
    return {n: w["layers"][n][i].astype(jnp.float32) for n in LAYER_KEYS}


def hidden(cfg, w, tokens, mode, pamm_states=None, collect=None):
    """Final normed hidden states (L, d) of one sequence."""
    s = sizes(cfg)
    x = w["tok_embeddings"][tokens].astype(jnp.float32)
    for i in range(s["layers"]):
        lw = _layer_weights(w, i)
        pamm = None if pamm_states is None else pamm_states[i]
        fn = jax.checkpoint(functools.partial(_layer, s, mode=mode))
        if collect is not None:
            x = _layer(s, lw, x, mode, pamm, collect=collect)
        else:
            x = fn(lw, x, pamm=pamm)
    return rmsnorm(x, w["norm"], s["eps"])


def logits_at(cfg, w, tokens, rows, mode):
    """Logits (len(rows), V) of one sequence at positions ``rows``; the
    serving comparison. Layers run one at a time through a scan."""
    s = sizes(cfg)
    x = w["tok_embeddings"][tokens].astype(jnp.float32)

    def body(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        return _layer(s, lw, x, mode, q_chunk=512), None

    x, _ = jax.lax.scan(body, x, {n: w["layers"][n] for n in LAYER_KEYS})
    h = rmsnorm(x[rows], w["norm"], s["eps"])
    return mm(h, w["output"], mode)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def seq_nll_sum(cfg, w, tokens, labels, mode, pamm_states=None, chunk=1024):
    """Summed next-token NLL of one sequence (the head runs in row chunks)."""
    h = hidden(cfg, w, tokens, mode, pamm_states)

    @jax.checkpoint
    def part(hb, lb, out):
        lg = mm(hb, out, mode)
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                       - jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0])

    L = tokens.shape[0]
    return sum(part(h[i:i + chunk], labels[i:i + chunk], w["output"])
               for i in range(0, L, chunk))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _collect_inputs(cfg_items, w, tokens, _unused, mode):
    collect = []
    hidden(dict(cfg_items), w, tokens, mode, collect=collect)
    return jnp.stack(collect)


@functools.partial(jax.jit, static_argnums=(0, 6), donate_argnums=(2,))
def _seq_grad(cfg_items, w, acc, tokens, labels, pamm_states, mode):
    """NLL sum of one sequence, and ``acc`` plus its gradient (``acc`` is
    donated, so the batch's gradient builds up in place)."""
    cfg = dict(cfg_items)
    nll, g = jax.value_and_grad(
        lambda ww: seq_nll_sum(cfg, ww, tokens, labels, mode, pamm_states))(w)
    return nll, jax.tree.map(jnp.add, acc, g)


def _frozen(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def loss_and_grads(cfg, w, tokens, labels, mode, pamm=None):
    """Mean NLL over the batch and its gradient. ``pamm``: None (exact) or
    dict(run_seed, step, ratio): the QKV weight gradient of each layer is
    then PAMM's estimate from generators drawn over the whole batch."""
    B, L = tokens.shape
    ci = _frozen(cfg)
    states = None
    if pamm is not None:
        xs = jnp.concatenate([_collect_inputs(ci, w, tokens[b], 0, mode)
                              for b in range(B)], axis=1)   # (layers, B*L, d)
        n = xs.shape[0]
        states = [pamm_state(xs[i], pamm_key(pamm["run_seed"], pamm["step"],
                                             i, n), pamm["ratio"])
                  for i in range(n)]
        del xs
    total = 0.0
    grads = jax.tree.map(jnp.zeros_like, w)
    for b in range(B):
        seq_states = None
        if states is not None:
            sl = slice(b * L, (b + 1) * L)
            seq_states = [(c, a[sl], f[sl], bt) for c, a, f, bt in states]
        nll, grads = _seq_grad(ci, w, grads, tokens[b], labels[b],
                               seq_states, mode)
        total = total + nll
    n_tok = B * L
    return total / n_tok, jax.tree.map(lambda g: g / n_tok, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up over ``warmup_frac`` of ``total_steps``, then cosine
    decay to ``final_frac`` of ``lr``."""
    warm = max(1.0, opt["total_steps"] * opt["warmup_frac"])
    if step < warm:
        return opt["lr"] * step / warm
    prog = min(max((step - warm) / max(1.0, opt["total_steps"] - warm), 0.0), 1.0)
    ff = opt["final_frac"]
    return opt["lr"] * (ff + (1 - ff) * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1, 2, 3))
def _adamw(w, m, v, g, hyper, t, lr, scale):
    """One AdamW step on the clipped gradient ``g * scale``; the weights,
    moments and gradient are donated, so a step needs no second copy. The
    update of wq, wk and wv is scaled by ``qkv_lr_scale``; decay is not."""
    b1, b2, eps, wd, qkv_scale = hyper
    flat_w = jax.tree_util.tree_flatten_with_path(w)[0]
    tree = jax.tree.structure(w)
    res = []
    for (path, p), mm_, vv, gg in zip(flat_w, jax.tree.leaves(m),
                                      jax.tree.leaves(v), jax.tree.leaves(g)):
        name = jax.tree_util.keystr(path)
        s = qkv_scale if any(f"'{n}'" in name for n in ("wq", "wk", "wv")) else 1.0
        gg = gg * scale
        m2 = b1 * mm_ + (1 - b1) * gg
        v2 = b2 * vv + (1 - b2) * gg * gg
        mh = m2 / (1 - b1 ** t)
        vh = v2 / (1 - b2 ** t)
        p2 = p - lr * s * (mh / (jnp.sqrt(vh) + eps)) - lr * wd * p
        res.append((p2, m2, v2))
    return tuple(jax.tree.unflatten(tree, [r[i] for r in res]) for i in range(3))


def train_steps(cfg, w, batches, opt: dict, mode: str, pamm=None,
                grad_stat=lambda g: g):
    """Run ``len(batches)`` optimizer steps from weights ``w`` (float32,
    donated). Returns per-step losses, ``grad_stat`` of the first step's
    clipped gradient (as AdamW receives it) and the weights after the last
    step."""
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
             opt["qkv_lr_scale"])
    norm = jax.jit(global_norm)
    losses, first = [], None
    for step, (tokens, labels) in enumerate(batches):
        p = None if pamm is None else dict(pamm, step=step)
        loss, g = loss_and_grads(cfg, w, tokens, labels, mode, p)
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm(g), 1e-9))
        if first is None:
            first = grad_stat(jax.tree.map(lambda a: a * scale, g))
        w, m, v = _adamw(w, m, v, g, hyper, jnp.float32(step + 1),
                         jnp.float32(lr_at(step, opt)), scale)
        losses.append(float(loss))
    return losses, first, w
