"""Model assembly: embedding -> staged block stack (lax.scan) -> head.

Public API (all pure functions over plain pytrees):

  init_model(cfg, rcfg, key, n_kv_eff=None)       -> (params, specs)
  loss_fn(cfg, rcfg, plan, params, batch, key)    -> (loss, metrics)
  forward(cfg, rcfg, plan, params, batch, key)    -> (hidden, aux)
  prefill(cfg, rcfg, params, batch, max_len, plan=None) -> (logits_last, caches)
  decode_step(cfg, rcfg, params, tokens, pos, caches, extras) -> (logits, caches)

``plan`` is anything ``core.plan.as_resolved`` accepts: a spec string, a
CompressionPlan, a ResolvedPlan, None (derive from ``rcfg``), or — the
deprecated path — a single CompressionPolicy from :func:`make_run_policy`.

``batch``: dict with 'tokens' (B, L) int32 (or 'embeds' (B, L, d) when
cfg.embed_inputs), 'labels', optional 'mask', optional 'image_embeds'
(B, vision_tokens, d). MusicGen labels are (B, L, n_codebooks).

Stages with repeat > 1 run under ``lax.scan`` over stacked per-layer params
so 80-layer models lower to compact HLO (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import plan as plan_lib
from repro.core.policies import CompressionPolicy, make_policy
from repro.models import blocks as blk
from repro.models.layers import P, chunked_cross_entropy, embed_init, init_rms_norm, rms_norm

__all__ = [
    "init_model", "param_specs", "make_run_policy",
    "forward", "loss_fn", "prefill", "decode_step", "init_caches",
]


def make_run_policy(rcfg) -> CompressionPolicy:
    """DEPRECATED: the single global policy of the flat-RunConfig era.

    Still honored everywhere a plan is accepted (the object is wrapped by
    ``core.plan.resolved_from_policy``, reproducing the old kind-level
    dispatch bit-for-bit). New code should set ``rcfg.compression`` to a
    plan spec — see core/plan.py and DESIGN.md §2.
    """
    if rcfg.policy_name == "pamm":
        return make_policy(
            "pamm", ratio=rcfg.pamm_ratio, eps=rcfg.pamm_eps,
            use_kernel=rcfg.use_kernel, n_blocks=rcfg.pamm_blocks,
            k_max=rcfg.pamm_k_max,
        )
    if rcfg.policy_name == "uniform_crs":
        return make_policy("uniform_crs", ratio=rcfg.pamm_ratio)
    if rcfg.policy_name == "compact":
        # matched-memory comparison (paper Fig 4a): CompAct stores b*kp
        # scalars vs the baseline's b*n, so kp/n == the PAMM ratio gives
        # equal stored bytes.
        return make_policy("compact", ratio=rcfg.pamm_ratio)
    return make_policy("none")


def _dtype(rcfg):
    return jnp.dtype(rcfg.compute_dtype), jnp.dtype(rcfg.param_dtype)


def _padded_vocab(cfg, rcfg) -> int:
    """Vocab dim used for embed/head params. Padding to a multiple of the
    model-axis lane granularity lets odd vocabs (49155, 50280) shard over
    'model' instead of being replicated (§Perf). Padded logit columns are
    masked to -inf in the loss; padded embedding rows are never gathered
    (token ids < vocab_size). n_codebook heads keep their native vocab (it
    already divides)."""
    m = getattr(rcfg, "pad_vocab_multiple", 0)
    if not m or cfg.n_codebooks:
        return cfg.vocab_size
    return ((cfg.vocab_size + m - 1) // m) * m


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_model(cfg, rcfg, key, *, n_kv_eff: int | None = None):
    _, pdt = _dtype(rcfg)
    ks = jax.random.split(key, len(cfg.stages) + 3)
    params: dict = {}
    specs: dict = {}

    v_pad = _padded_vocab(cfg, rcfg)
    em = getattr(rcfg, "pad_experts_multiple", 0)
    e_pad = ((cfg.n_experts + em - 1) // em) * em if (em and cfg.n_experts) else 0
    if not cfg.embed_inputs:
        params["embed"] = embed_init(ks[0], v_pad, cfg.d_model, pdt)
        specs["embed"] = P(("vocab", "embed"))

    stages_p, stages_s = [], []
    for si, (unit, rep) in enumerate(cfg.stages):
        unit_p, unit_s = [], []
        for bi, kind in enumerate(unit):
            def one(r):
                return blk.init_block(
                    kind, cfg, jax.random.fold_in(ks[si + 1], r * 16 + bi), pdt,
                    n_kv_eff=n_kv_eff, e_pad=e_pad,
                )
            ps = [one(r)[0] for r in range(rep)]
            sp = one(0)[1]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ps) if rep > 1 else \
                jax.tree.map(lambda x: x[None], ps[0])
            unit_p.append(stacked)
            unit_s.append(jax.tree.map(lambda s: P(("layers",) + tuple(s)), sp,
                                       is_leaf=lambda s: isinstance(s, tuple)))
        stages_p.append(unit_p)
        stages_s.append(unit_s)
    params["stages"] = stages_p
    specs["stages"] = stages_s

    params["final_norm"], specs["final_norm"] = init_rms_norm(cfg.d_model, pdt)
    n_head_out = v_pad * max(1, cfg.n_codebooks)
    params["head"] = (
        jax.random.normal(ks[-1], (cfg.d_model, n_head_out)) * (cfg.d_model ** -0.5)
    ).astype(pdt)
    specs["head"] = P(("embed", "vocab"))
    return params, specs


def param_specs(cfg, rcfg, *, n_kv_eff: int | None = None):
    """(ShapeDtypeStruct tree, spec tree) without allocating parameters."""
    box = {}

    def f(k):
        p, s = init_model(cfg, rcfg, k, n_kv_eff=n_kv_eff)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.key(0))
    return shapes, box["specs"]


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------
def _embed(cfg, params, batch, cdt):
    if cfg.embed_inputs:
        return batch["embeds"].astype(cdt)
    return jnp.take(params["embed"], batch["tokens"], axis=0).astype(cdt)


def _extras(cfg, batch, cdt):
    ex = {}
    if cfg.vision_tokens:
        ex["image_embeds"] = batch["image_embeds"].astype(cdt)
    return ex


# ---------------------------------------------------------------------------
# staged forward (training / scoring)
# ---------------------------------------------------------------------------
def forward(cfg, rcfg, plan, params, batch, key, *, telemetry: dict | None = None):
    """Returns (hidden (B, L, d), aux_loss).

    ``plan``: see module docstring. ``telemetry``: pass a dict to receive
    per-site stats vectors (site path -> STATS_LEN array) accumulated over
    all layers — they ride the layer-scan carries, so they are valid
    tracers in the caller's trace.
    """
    resolved = plan_lib.as_resolved(plan, cfg, rcfg)
    structure = blk.resolve_block_structure(cfg, rcfg)
    cdt, _ = _dtype(rcfg)
    x = _embed(cfg, params, batch, cdt)
    B, L, _ = x.shape
    # Context parallelism hands each shard a non-contiguous (zigzag) slice
    # of the sequence; its global positions arrive in the batch and drive
    # RoPE plus the causal/window masks across shard seams.
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    else:
        positions = positions.astype(jnp.int32)
    extras = _extras(cfg, batch, cdt)
    aux = jnp.float32(0)
    tele = resolved.zero_telemetry()

    if structure != "residual":
        # Reversible two-stream stack (DESIGN.md §3): both streams start at
        # the embedding; the stage custom_vjp reconstructs (x1, x2) from
        # (y1, y2) in backward, so no per-layer residual-stream activation
        # is saved. Streams ride as compensated (hi, lo) pairs so the
        # reconstruction is exact to O(eps^2) (blocks._dd_add). Embedding
        # and head stay on the plain (residual) path.
        zero = jnp.zeros_like(x)
        x1h, x1l, x2h, x2l = x, zero, x, zero
        for si, (unit, rep) in enumerate(cfg.stages):
            stage_key = jax.random.fold_in(key, si)
            kd = jax.random.key_data(jax.random.split(stage_key, rep))
            x1h, x1l, x2h, x2l, aux, tele = blk.reversible_stage(
                cfg, rcfg, unit, si, resolved, params["stages"][si],
                x1h, x1l, x2h, x2l, aux, tele, positions, kd,
                save_memory=(structure == "reversible"))
        # revnet_out-style merge: average the streams before the head.
        x = 0.5 * ((x1h + x1l) + (x2h + x2l))
        if telemetry is not None:
            telemetry.update(tele)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux

    for si, (unit, rep) in enumerate(cfg.stages):
        unit_params = params["stages"][si]
        stage_key = jax.random.fold_in(key, si)

        def body(carry, xs, si=si):
            from repro.runtime.sharding import maybe_constrain

            x_c, aux_c, tele_c = carry
            bparams, k_r = xs
            for bi, kind in enumerate(unit):
                ctx = resolved.ctx(si, kind, tele_c)
                x_c, aux_c, _ = blk.block_train(
                    kind, cfg, rcfg, ctx, bparams[bi], x_c, positions, extras,
                    jax.random.fold_in(k_r, bi), aux_c,
                )
                tele_c = ctx.tele
                if rcfg.seq_shard:
                    # Megatron sequence parallelism: between blocks the
                    # residual stream is sharded over (batch, seq->model);
                    # GSPMD inserts the all-gather / reduce-scatter pairs.
                    x_c = maybe_constrain(x_c, ("batch", "ffn", None))
                else:
                    # Block-boundary anchor: the residual stream is
                    # batch-sharded and REPLICATED over the model axis, so
                    # GSPMD closes each block's TP with the intended
                    # all-reduce of the out/down projections instead of
                    # propagating a model-sharded embed dim downstream.
                    # No-op without a mesh in context.
                    x_c = maybe_constrain(x_c, ("batch", None, "embed"))
            return (x_c, aux_c, tele_c), None

        if rcfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif rcfg.remat == "pamm":
            # Beyond-paper integration: remat everything in the block EXCEPT
            # the compressed PAMM states (tiny) — the backward re-computes
            # activations but re-uses the saved generators/coefficients.
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names("pamm_state"),
            )

        keys = jax.random.split(stage_key, rep)
        if rep > 1:
            (x, aux, tele), _ = jax.lax.scan(body, (x, aux, tele), (unit_params, keys))
        else:
            sliced = jax.tree.map(lambda t: t[0], unit_params)
            (x, aux, tele), _ = body((x, aux, tele), (sliced, keys[0]))

    if telemetry is not None:
        telemetry.update(tele)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def loss_fn(cfg, rcfg, plan, params, batch, key):
    resolved = plan_lib.as_resolved(plan, cfg, rcfg)
    cdt, _ = _dtype(rcfg)
    tele: dict = {}
    h, aux = forward(cfg, rcfg, resolved, params, batch, key, telemetry=tele)
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape[:2], jnp.float32)

    head_site = resolved.head_site()
    if head_site is not None and head_site.is_exact:
        head_site = None
    head_key = jax.random.fold_in(key, 0x1EAD)
    head_stats = None
    if cfg.n_codebooks:
        v = cfg.vocab_size
        nll = jnp.float32(0)
        for c in range(cfg.n_codebooks):
            w_c = params["head"][:, c * v : (c + 1) * v]
            res = chunked_cross_entropy(
                h, w_c, labels[..., c], mask, rcfg.loss_chunk,
                site=head_site, key=jax.random.fold_in(head_key, c),
            )
            if head_site is not None:
                nll_c, stats = res
                head_stats = stats if head_stats is None else head_stats + stats
                nll = nll + nll_c
            else:
                nll = nll + res
        nll = nll / cfg.n_codebooks
    else:
        res = chunked_cross_entropy(h, params["head"], labels, mask, rcfg.loss_chunk,
                                    valid_vocab=cfg.vocab_size,
                                    site=head_site, key=head_key)
        if head_site is not None:
            nll, head_stats = res
        else:
            nll = res
    if head_site is not None and head_stats is not None:
        tele[head_site.path] = tele.get(head_site.path, 0) + head_stats
    moe_coef = 0.01 if cfg.n_experts else 0.0
    total_layers = max(1, cfg.n_layers)
    loss = nll + moe_coef * aux / total_layers
    return loss, {"nll": nll, "aux": aux, "sites": tele}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------
def _require_residual_serving(cfg, rcfg, fn_name: str):
    if blk.resolve_block_structure(cfg, rcfg) != "residual":
        raise NotImplementedError(
            f"{fn_name} does not implement the reversible two-stream stack: "
            f"block_structure='reversible' is a train-time activation-memory "
            f"optimization, and a reversibly-trained model computes a "
            f"different function than the residual stack. Score through "
            f"forward()/loss_fn, or serve with a residual-trained model.")


def init_caches(cfg, rcfg, B: int, max_len: int, *, n_kv_eff=None,
                layout: str | None = None, page_size: int | None = None,
                pool_pages: int | None = None, cache_plan=None):
    """Decode caches for the whole stack (B = batch slots).

    ``layout``/``page_size`` default from ``rcfg.cache_layout`` /
    ``rcfg.kv_page_size``: ``dense`` keeps the slot-contiguous
    (layers, B, S, KV, dh) slabs, ``paged`` builds per-layer page pools
    plus block tables (models/attention.PagedKVCache) so KV residency is
    allocated page-by-page at serve time. ``pool_pages`` caps each pool
    (None = dense-equivalent worst case).

    ``cache_plan`` (a resolved CompressionPlan, default parsed from
    ``rcfg.cache_compress``) maps each stage's attention caches to a
    :class:`core.plan.CacheFormat` — int8/int4 pools quantize on write,
    svd pools store rank-r coefficients (models/attention.py). A
    compressed pool's page count grows with its compression ratio at the
    same ``pool_pages`` byte budget (models/blocks.init_block_cache).
    """
    cdt, _ = _dtype(rcfg)
    layout = layout or getattr(rcfg, "cache_layout", "dense")
    if layout not in ("dense", "paged"):
        raise ValueError(f"cache_layout must be dense|paged, got {layout!r}")
    page_size = page_size or getattr(rcfg, "kv_page_size", 64)
    if cache_plan is None:
        spec = getattr(rcfg, "cache_compress", "") or ""
        cache_plan = plan_lib.cache_plan_from_spec(spec).resolve(cfg)
    caches = []
    for si, (unit, rep) in enumerate(cfg.stages):
        unit_caches = []
        for kind in unit:
            one = blk.init_block_cache(kind, cfg, B, max_len, cdt,
                                       n_kv_eff=n_kv_eff, layout=layout,
                                       page_size=page_size,
                                       pool_pages=pool_pages,
                                       cache_format=cache_plan.cache_format(si, kind))
            stacked = jax.tree.map(lambda t: jnp.broadcast_to(t[None], (rep,) + t.shape), one)
            unit_caches.append(stacked)
        caches.append(unit_caches)
    return caches


def cache_logical_specs(cfg, *, shard_cache_seq: bool = False):
    """Logical spec tree matching ``init_caches`` (for the dry-run)."""
    specs = []
    for unit, rep in cfg.stages:
        specs.append(
            [blk.block_cache_specs(kind, cfg, shard_cache_seq=shard_cache_seq)
             for kind in unit]
        )
    return specs


def prefill(cfg, rcfg, params, batch, max_len: int, plan=None,
            prompt_len=None):
    """Run the prompt, build caches sized ``max_len``. Returns (logits, caches).

    ``plan``: optional CompressionPlan spec/object routed through the same
    per-site resolution as training (``as_resolved``). Forward outputs are
    exact for every policy (compression only approximates grad_W), so a
    serving plan changes no logits — but it exercises plan resolution and
    site dispatch instead of silently bypassing them, and ``None`` keeps
    the zero-overhead exact path.

    ``prompt_len``: optional (B,) int32 of true prompt lengths for
    length-bucketed batches whose tokens are right-padded. The returned
    logits row is then taken at position ``prompt_len - 1`` instead of the
    last row, so the padded tail never picks the first sampled token.
    (With causal attention, pad rows cannot perturb real rows; the serving
    cache splice masks their K/V out — serve/cache.mask_pad_rows.)
    """
    _require_residual_serving(cfg, rcfg, "prefill")
    cdt, _ = _dtype(rcfg)
    resolved = None if plan is None else plan_lib.as_resolved(plan, cfg, rcfg)
    x = _embed(cfg, params, batch, cdt)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    # bucketing pad rows must never be WRITTEN to the prefill cache (a
    # ring cache would evict real tail tokens); -1 makes cache_insert
    # drop them. Attention/RoPE keep the true arange positions.
    cpos = None if prompt_len is None else jnp.where(
        positions < jnp.asarray(prompt_len)[:, None], positions, -1)
    extras = _extras(cfg, batch, cdt)
    aux = jnp.float32(0)
    key = jax.random.key(0)
    caches = []

    for si, (unit, rep) in enumerate(cfg.stages):
        unit_params = params["stages"][si]

        def body2(x_c, bparams, si=si):
            outs = []
            a = jnp.float32(0)
            for bi, kind in enumerate(unit):
                ctx = plan_lib.exact_ctx() if resolved is None else \
                    resolved.ctx(si, kind, None)
                x_c, a, cache = blk.block_train(
                    kind, cfg, rcfg, ctx, bparams[bi], x_c, positions, extras,
                    key, a, want_cache=True, max_len=max_len,
                    cache_positions=cpos,
                )
                outs.append(cache)
            return x_c, tuple(outs)

        if rep > 1:
            x, stage_caches = jax.lax.scan(body2, x, unit_params)
            caches.append(list(stage_caches))
        else:
            sliced = jax.tree.map(lambda t: t[0], unit_params)
            x, stage_caches = body2(x, sliced)
            caches.append([jax.tree.map(lambda t: t[None], c) for c in stage_caches])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if prompt_len is not None:
        x = x[jnp.arange(B), jnp.asarray(prompt_len) - 1][:, None]
    else:
        x = x[:, -1:]
    logits = (x @ params["head"].astype(cdt)).astype(jnp.float32)
    return logits, caches


def decode_step(cfg, rcfg, params, tokens, pos, caches, extras_batch=None):
    """One decode step for the whole batch.

    tokens: (B, L) int32 (or (B, L, d) embeds); pos: (B, L) absolute
    positions. L = 1 is the classic per-token step; L > 1 feeds a
    speculative-verify block through the same path — every per-block op
    is row-independent for attention kinds (attn/swa/latt/xattn), so row
    l's logits match a sequential L = 1 run fed the same prefix exactly.
    Returns (logits (B, L, V*), new_caches).
    """
    _require_residual_serving(cfg, rcfg, "decode_step")
    cdt, _ = _dtype(rcfg)
    if cfg.embed_inputs:
        x = tokens.astype(cdt)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    extras = extras_batch or {}

    new_caches = []
    for si, (unit, rep) in enumerate(cfg.stages):
        unit_params = params["stages"][si]
        unit_caches = caches[si]

        def body(x_c, xs):
            bparams, bcaches = xs
            outs = []
            for bi, kind in enumerate(unit):
                x_c, nc = blk.block_decode(
                    kind, cfg, rcfg, bparams[bi], x_c, pos, bcaches[bi], extras
                )
                outs.append(nc)
            return x_c, tuple(outs)

        if rep > 1:
            x, stage_caches = jax.lax.scan(body, x, (unit_params, unit_caches))
            new_caches.append(list(stage_caches))
        else:
            sliced_p = jax.tree.map(lambda t: t[0], unit_params)
            sliced_c = [jax.tree.map(lambda t: t[0], c) for c in unit_caches]
            x, stage_caches = body(x, (sliced_p, sliced_c))
            new_caches.append([jax.tree.map(lambda t: t[None], c) for c in stage_caches])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["head"].astype(cdt)).astype(jnp.float32)
    return logits, new_caches
