"""Distributed training executor: explicit ``shard_map`` DP x TP train step.

The jit executor (train_step.py) hands the whole step to GSPMD: correct, but
gradient synchronization is invisible — there is no per-shard gradient to
compress, no handle on the collective schedule, and "data parallel" is just
a layout hint. This module makes the data axis *manual*:

  * the mesh's data axes (``('pod','data')`` or ``('data',)``) are manually
    sharded by ``jax.shard_map`` — each shard runs its own
    forward/backward (through the same Pallas flash-attention fwd+bwd and
    PAMM custom_vjp paths as the jit executor) on its slice of the batch;
  * the model axes stay GSPMD-auto (outside ``axis_names``) when they
    have more than one device, so
    tensor parallelism over ``heads``/``ffn``/``vocab`` keeps lowering to
    the intended all-reduces inside each shard's replica group, steered by
    the ``maybe_constrain`` activation annotations at block boundaries
    (model code enters ``sharding.shard_map_ctx`` so those annotations drop
    the manual axes);
  * DP gradient synchronization is an explicit collective: plain
    ``pmean`` by default, or ``tree_compressed_psum`` (int8 error-feedback
    all-reduce, runtime/grad_compress.py) when
    ``RunConfig.grad_compress == "int8_ef"``. EF buffers ride TrainState.ef
    with a leading data-sharded axis — shard i's quantization residue stays
    on shard i;
  * the optimizer update runs OUTSIDE the shard_map under GSPMD with
    ZeRO-1 shardings (``runtime.sharding.opt_state_shardings``) pinned via
    jit out_shardings: XLA lowers it to reduce-scatter(grads) +
    shard-local update + all-gather(params), and each device stores 1/dp
    of the Adam moments.

PRNG / PAMM sharding semantics: plan resolution sees the mesh, so
``blocks=auto`` resolves to the DP degree. Per shard, the blocked policy is
localized (``n_blocks/dp`` blocks, usually 1) and the site key derivation is
replaced by :func:`shard_site_key`, which gives shard ``s`` the exact PRNG
stream of block ``s`` in the blocked single-device formulation. DP shards
are therefore decorrelated (distinct split keys) while the executor stays
bit-compatible with the jit executor's ``blocks=dp`` compression — the
multi-device parity harness (tests/test_multidevice.py) checks both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core.plan import resolve_for_run
from repro.core.policies import PammPolicy
from repro.models import param_specs
from repro.optim import make_optimizer, warmup_cosine
from repro.optim.optimizers import clip_by_global_norm
from repro.runtime import sharding as sh
from repro.runtime.grad_compress import tree_compressed_psum
from repro.train.train_step import (
    GRAD_COMPRESS_SCHEMES,
    TrainState,
    finish_metrics,
    init_train_state,
    loss_and_grad,
)

__all__ = [
    "make_shard_map_train_step",
    "init_distributed_state",
    "state_shardings",
    "shard_site_key",
]


def shard_site_key(key, site_id, *, dp: int, shard):
    """Site key for data shard ``shard`` of ``dp``: block ``shard``'s key in
    the blocked single-device derivation.

    Single device, ``blocks=dp``: ``site_key = fold_in(key, site_id)`` then
    ``pamm_compress_blocked`` gives block ``s`` the key
    ``jax.random.split(site_key, dp)[s]``. Reproducing exactly that chain
    here keeps the shard_map executor's sampling bit-identical to the jit
    executor's shard-local blocking while every shard draws a distinct
    stream (``shard`` may be a tracer).
    """
    return jax.random.split(jax.random.fold_in(key, site_id), dp)[shard]


def _localize_policy(policy, dp: int):
    """Per-shard view of a mesh-resolved policy: a PAMM policy blocked over
    the DP degree compresses its shard's rows in ``n_blocks // dp`` local
    blocks (1 for ``blocks=auto``), with ``block_share=dp`` so the shard's
    generator count is exactly its share of the global blocked run — k
    parity with the jit executor holds for any ratio, not only when
    ``ceil(r * b_global)`` divides by dp. Other policies are per-shard
    already."""
    if isinstance(policy, PammPolicy) and policy.n_blocks > 1:
        import dataclasses

        return dataclasses.replace(
            policy, n_blocks=max(1, policy.n_blocks // dp), block_share=dp)
    return policy


def state_shardings(cfg, rcfg, mesh, *, n_kv_eff=None):
    """NamedSharding tree for a TrainState on ``mesh``.

    params: logical rules (TP over model, replicated over data), uneven
    dims dropped to replication; opt: ZeRO-1 over the data axis (behind
    ``rcfg.zero1``); ef: leading axis data-sharded (present only under
    int8_ef). Returns ``(state_shardings, param_shapes, specs)``.
    """
    shapes, specs = param_specs(cfg, rcfg, n_kv_eff=n_kv_eff)
    param_sh = sh.sanitize_shardings(
        sh.spec_tree_to_shardings(specs, mesh), shapes, mesh
    )
    opt_init, _ = make_optimizer(rcfg.optimizer)
    opt_shapes = jax.eval_shape(opt_init, shapes)
    opt_sh = sh.opt_state_shardings(
        opt_shapes, param_sh, shapes, mesh,
        optimizer=rcfg.optimizer, zero1=rcfg.zero1,
    )
    ef_sh = None
    if getattr(rcfg, "grad_compress", "none") == "int8_ef":
        # one EF row per (data, context) coordinate — under context
        # parallelism every sequence shard quantizes its own gradient
        ef_ns = NamedSharding(mesh, sh.shard_pspec(mesh))
        ef_sh = jax.tree.map(lambda _: ef_ns, shapes)
    return TrainState(params=param_sh, opt=opt_sh, ef=ef_sh), shapes, specs


def init_distributed_state(cfg, rcfg, key, mesh, *, n_kv_eff=None):
    """Initialize a TrainState laid out for the shard_map executor.

    Params follow the logical sharding rules, optimizer moments the ZeRO-1
    layout, and — under ``grad_compress="int8_ef"`` — zeroed error-feedback
    buffers of shape ``(dp, *param.shape)`` sharded over the data axes.
    Returns ``(state, specs)`` like :func:`init_train_state`.
    """
    state_sh, _, specs = state_shardings(cfg, rcfg, mesh, n_kv_eff=n_kv_eff)
    state, _ = init_train_state(cfg, rcfg, key, n_kv_eff=n_kv_eff)
    params = jax.device_put(state.params, state_sh.params)
    opt = jax.device_put(state.opt, state_sh.opt)
    ef = None
    if getattr(rcfg, "grad_compress", "none") == "int8_ef":
        n_shards = sh.dp_degree(mesh) * sh.cp_degree(mesh)
        ef = jax.tree.map(
            lambda p, ns: jax.device_put(
                jnp.zeros((n_shards,) + p.shape, jnp.float32), ns
            ),
            state.params, state_sh.ef,
        )
    return TrainState(params=params, opt=opt, ef=ef), specs


def make_shard_map_train_step(cfg, rcfg, *, total_steps: int = 10000, mesh,
                              n_kv_eff=None):
    """Build the jitted DP x TP train step over ``mesh``.

    The returned ``step(state, batch, step_idx) -> (state, metrics)`` takes
    the TrainState from :func:`init_distributed_state` and a GLOBAL batch
    (leading axis = global batch, sharded or host-local — jit commits it to
    the data axes). Raises at trace time, with a readable message, when the
    global batch does not divide over the data axes.
    """
    if mesh is None:
        raise ValueError("the shard_map executor needs a mesh; use "
                         "make_train_step for single-process runs")
    gc = getattr(rcfg, "grad_compress", "none")
    if gc not in GRAD_COMPRESS_SCHEMES:
        raise ValueError(
            f"unknown grad_compress {gc!r}; have {GRAD_COMPRESS_SCHEMES}")
    from repro.models.blocks import resolve_block_structure

    data_axes = sh.data_axis_names(mesh)
    ctx_axes = sh.context_axis_names(mesh)
    sync_axes = data_axes + ctx_axes
    # Mosaic (Pallas TPU) kernels lower only where every mesh axis is
    # manual, so size-1 axes join the manual set: with model=1 the whole
    # body is manual and the flash/PAMM kernels compile for the chip.
    # Tensor parallelism (model > 1) stays GSPMD-auto.
    manual_axes = sync_axes + tuple(
        a for a in mesh.axis_names
        if a not in sync_axes and mesh.shape[a] == 1)
    dp = sh.dp_degree(mesh)
    cp = sh.cp_degree(mesh)
    n_shards = dp * cp
    dspec = sh.data_pspec(mesh)
    cspec = PS(ctx_axes[0]) if ctx_axes else PS()
    bspec = sh.batch_pspec(mesh)
    efspec = sh.shard_pspec(mesh)

    # Same config-time block_structure x remat x architecture gate as the
    # jit executor, plus the cp decision table (reversible x ring and the
    # sequence-recurrent kinds) — the reversible stage's custom_vjp and the
    # ring's ppermute run inside the shard_map body, so invalid combos must
    # fail before tracing.
    resolve_block_structure(cfg, rcfg, cp=cp)

    # Mesh-resolved plan (backend + blocks=auto -> dp x cp), localized per
    # (data, context) shard.
    resolved_global = resolve_for_run(cfg, rcfg, mesh=mesh)
    if n_shards > 1:
        odd = sorted({
            s.policy.n_blocks for s in resolved_global.compressed_sites
            if isinstance(s.policy, PammPolicy) and s.policy.n_blocks != n_shards
        })
        if odd:
            import warnings

            warnings.warn(
                f"PAMM blocks={odd} != shard count {n_shards} (dp {dp} x "
                f"cp {cp}): the shard_map executor localizes blocks per "
                f"shard with a different key chain than the jit executor's "
                f"global blocked compress — training is valid but NOT "
                f"sampling-compatible between executors. Use blocks=auto "
                f"(= dp x cp) for bit parity.",
                stacklevel=2,
            )
    resolved_base = resolved_global.map_policies(
        lambda p: _localize_policy(p, n_shards)
    )
    _, opt_update = make_optimizer(rcfg.optimizer)
    seed_key = jax.random.key(rcfg.seed)

    def shard_body(sid, cid, key_data, params, ef, batch):
        # sid / cid are (1,)-slices of arange(dp) / arange(cp): this
        # shard's data and context indices. Inputs instead of
        # lax.axis_index because XLA's SPMD partitioner cannot lower
        # PartitionId under partial-auto shard_map on all backends (CPU
        # included). The step key likewise enters as raw uint32 key data:
        # a typed key array crossing the shard_map boundary trips GSPMD's
        # sharding validation for extended dtypes.
        with sh.shard_map_ctx(mesh, manual_axes):
            shard = sid[0] * cp + cid[0]
            resolved = resolved_base
            if n_shards > 1:
                resolved = resolved_base.with_site_key_fn(
                    lambda key, site_id: shard_site_key(
                        key, site_id, dp=n_shards, shard=shard)
                )
            if cp > 1:
                # This shard sees a zigzag slice of the sequence (the
                # global batch is zigzag-permuted below, so the contiguous
                # context slice IS chunks (cid, 2cp-1-cid)); its global
                # positions feed RoPE and the ring's seam-crossing masks.
                from repro.kernels.ring_attention import zigzag_shard_positions

                some = jax.tree.leaves(batch)[0]
                B_loc, L_loc = some.shape[0], some.shape[1]
                pos = zigzag_shard_positions(cid[0], L_loc * cp, cp)
                batch = dict(batch)
                batch["positions"] = jnp.broadcast_to(
                    pos[None, :], (B_loc, L_loc))
            key = jax.random.wrap_key_data(key_data)
            loss, metrics, grads = loss_and_grad(
                cfg, rcfg, resolved, params, batch, key
            )
            if gc == "int8_ef":
                ef_loc = jax.tree.map(lambda e: e[0], ef)
                grads, new_err = tree_compressed_psum(grads, ef_loc, sync_axes)
                new_ef = jax.tree.map(lambda e: e[None], new_err)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, sync_axes), grads)
                new_ef = ef
            # Aggregate telemetry across shards (don't report shard-0
            # numbers): the STATS_LEN vectors are sums/counts, so psum gives
            # global stored bytes, kept/total rows and beta sums.
            metrics = {
                "nll": jax.lax.pmean(metrics["nll"], sync_axes),
                "aux": jax.lax.pmean(metrics["aux"], sync_axes),
                "sites": jax.tree.map(
                    lambda v: jax.lax.psum(v, sync_axes),
                    metrics.get("sites", {})),
            }
            loss = jax.lax.pmean(loss, sync_axes)
            return loss, metrics, grads, new_ef

    grads_fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(dspec, cspec, PS(), PS(), efspec, bspec),
        out_specs=(PS(), PS(), PS(), efspec),
        axis_names=frozenset(manual_axes), check_vma=False,
    )

    seq_perm = None
    if cp > 1:
        from repro.kernels.ring_attention import zigzag_permutation

        def permute_seq(b: dict) -> dict:
            # Zigzag-reorder the sequence axis so each context shard's
            # contiguous slice is its fold-in-half chunk pair (causal load
            # balance). Labels/masks permute with their tokens; token-wise
            # losses are permutation invariant, so metrics are unchanged.
            L = jax.tree.leaves(b)[0].shape[1]
            perm = zigzag_permutation(L, cp)
            return {k: (v[:, perm] if v.ndim >= 2 and v.shape[1] == L else v)
                    for k, v in b.items()}

        seq_perm = permute_seq

    def train_step(state: TrainState, batch: dict, step: jax.Array):
        sid = jnp.arange(max(1, dp), dtype=jnp.int32)
        cid = jnp.arange(max(1, cp), dtype=jnp.int32)
        if seq_perm is not None:
            batch = seq_perm(batch)
        key_data = jax.random.key_data(jax.random.fold_in(seed_key, step))
        loss, metrics, grads, new_ef = grads_fn(
            sid, cid, key_data, state.params, state.ef, batch)
        # Post-sync grads are replicated over data: clip + optimizer run
        # under GSPMD, and the jit out_shardings below pin the ZeRO-1
        # layout, so XLA schedules reduce-scatter(update)/all-gather(params)
        # around the shard-local moment update.
        grads, gnorm = clip_by_global_norm(grads, rcfg.grad_clip)
        lr = warmup_cosine(step, total_steps, rcfg.lr, rcfg.warmup_frac)
        new_params, new_opt = opt_update(
            grads, state.opt, state.params, lr,
            weight_decay=rcfg.weight_decay, pamm_lr_scale=rcfg.pamm_lr_scale,
        )
        out_metrics = finish_metrics(loss, metrics, gnorm, lr)
        return (
            TrainState(params=new_params, opt=new_opt, ef=new_ef),
            out_metrics,
        )

    state_sh, _, _ = state_shardings(cfg, rcfg, mesh, n_kv_eff=n_kv_eff)
    # The global batch enters data-sharded only; the zigzag permutation
    # happens inside the jit, after which the context axis slices fall out
    # of the shard_map in_specs.
    batch_sh = NamedSharding(mesh, dspec)
    jitted = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh, None),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

    def step(state, batch, step_idx):
        # Validate BEFORE jit commits the batch to the mesh — the
        # alternative is an opaque pjit "sharding does not evenly divide"
        # failure on the first uneven batch.
        leaf = jax.tree.leaves(batch)[0]
        sh.validate_batch_divisible(
            leaf.shape[0], mesh, grad_accum=rcfg.grad_accum,
            where="shard_map train step")
        sh.validate_seq_divisible(
            leaf.shape[1], mesh, where="shard_map train step")
        return jitted(state, batch, step_idx)

    return step
