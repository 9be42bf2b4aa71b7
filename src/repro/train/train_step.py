"""Train-step factory: loss -> grads -> clip -> schedule -> optimizer.

The returned ``train_step(state, batch, step)`` is a pure function ready for
``jax.jit`` with in/out shardings from runtime/sharding.py. PRNG for PAMM's
per-step generator sampling is ``fold_in(seed_key, step)`` — deterministic,
checkpoint-free, and identical after an elastic restart (paper App. F notes
per-step sampling; we reproduce it without host RNG state). Each compression
site then folds its canonical ``site_id`` into the per-block key
(core/linear.py), so every site draws an independent stream.

Compression is configured by the run's CompressionPlan (core/plan.py),
resolved ONCE here — with the mesh, when given, so shard-local blocking and
backend choice are derived from the deployment rather than threaded flags.
Per-site telemetry (stored bytes / kept fraction / beta) lands in the
returned metrics under ``site/<path>/...``.

Attention inside the differentiated loss follows ``rcfg.attn_kernel``:
the Pallas FlashAttention-2 fwd+bwd pair (kernels/flash_attention.py) or
the chunked jnp sdpa with flash_sdp remat — both compose with the plan's
PAMM-compressed QKV custom_vjp, so on TPU the whole train step's attention
math runs as Pallas kernels in forward AND backward.

This module is the single-process (jit/GSPMD) executor. The explicit
multi-device executor — per-shard forward/backward under ``shard_map`` with
compressed DP gradient all-reduce and ZeRO-1 layout — lives in
train/distributed.py and shares :func:`loss_and_grad` with this one.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.plan import resolve_for_run
from repro.core.stats import site_telemetry_metrics
from repro.models import loss_fn
from repro.optim import make_optimizer, warmup_cosine
from repro.optim.optimizers import clip_by_global_norm

GRAD_COMPRESS_SCHEMES = ("none", "int8_ef")


class TrainState(NamedTuple):
    params: Any
    opt: Any
    # Error-feedback buffers for the int8 gradient all-reduce, shape
    # (dp, *param.shape) with the leading axis sharded over the data axes —
    # each data shard carries ITS quantization residue. None unless the
    # shard_map executor runs with grad_compress="int8_ef".
    ef: Any = None


def init_train_state(cfg, rcfg, key, *, n_kv_eff=None):
    from repro.models import init_model

    params, specs = init_model(cfg, rcfg, key, n_kv_eff=n_kv_eff)
    opt_init, _ = make_optimizer(rcfg.optimizer)
    return TrainState(params=params, opt=opt_init(params)), specs


def loss_and_grad(cfg, rcfg, resolved, params, batch, key):
    """Value-and-grad of the plan-resolved loss, with microbatch accumulation.

    Returns ``(loss, metrics, grads)``; ``metrics`` is the raw loss_fn aux
    ({"nll", "aux", "sites"}). Shared by the jit executor below and the
    shard_map executor (train/distributed.py), where it runs once per data
    shard on the shard-local batch.
    """
    accum = max(1, rcfg.grad_accum)
    if accum > 1:
        # Microbatch gradient accumulation: peak activation memory drops
        # ~accum-fold; grads averaged in f32. PAMM compresses each
        # microbatch independently (same semantics as smaller DDP shards).
        def micro(b_idx_key):
            mb, mkey = b_idx_key
            return jax.value_and_grad(
                lambda p: loss_fn(cfg, rcfg, resolved, p, mb, mkey), has_aux=True
            )(params)

        micro_batches = jax.tree.map(
            lambda t: t.reshape(accum, t.shape[0] // accum, *t.shape[1:]), batch
        )
        mkeys = jax.random.split(key, accum)

        def body(carry, xs):
            (l_acc, g_acc, m_acc) = carry
            (loss_i, metrics_i), grads_i = micro(xs)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / accum, g_acc, grads_i
            )
            m_acc = jax.tree.map(lambda a, v: a + v / accum, m_acc, metrics_i)
            return (l_acc + loss_i / accum, g_acc, m_acc), None

        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero_m = {"nll": jnp.float32(0), "aux": jnp.float32(0),
                  "sites": resolved.zero_telemetry()}
        (loss, grads32, metrics), _ = jax.lax.scan(
            body, (jnp.float32(0), zero_g, zero_m), (micro_batches, mkeys)
        )
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads32, params)
    else:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, rcfg, resolved, p, batch, key), has_aux=True
        )(params)
    return loss, metrics, grads


def finish_metrics(loss, metrics, gnorm, lr):
    """The scalar metric dict both executors return."""
    out = {
        "loss": loss.astype(jnp.float32),
        "nll": metrics["nll"].astype(jnp.float32),
        "grad_norm": gnorm,
        "lr": lr,
    }
    out.update(site_telemetry_metrics(metrics.get("sites", {})))
    return out


def make_train_step(cfg, rcfg, *, total_steps: int = 10000, mesh=None):
    gc = getattr(rcfg, "grad_compress", "none")
    if gc != "none":
        # This executor runs under one jit: gradients are globally summed by
        # GSPMD inside the backward pass, so there IS no per-shard gradient
        # to quantize — silently proceeding would train uncompressed while
        # the config claims int8_ef. Fail loudly instead.
        raise ValueError(
            f"RunConfig.grad_compress={gc!r} is only honored by the "
            f"shard_map executor (train.distributed.make_shard_map_train_step, "
            f"--executor shard_map); the jit executor would silently train "
            f"uncompressed. Set grad_compress='none' or switch executor."
        )
    if mesh is not None:
        from repro.runtime import sharding as _sh

        if _sh.cp_degree(mesh) > 1:
            # Ring attention needs the context axis MANUAL (ppermute inside
            # the shard_map body); under this executor GSPMD would have to
            # invent the rotation schedule itself, which it cannot.
            raise ValueError(
                f"mesh has a context axis of degree {_sh.cp_degree(mesh)}, "
                f"but the jit executor cannot run ring context-parallel "
                f"attention; use the shard_map executor "
                f"(--executor shard_map / make_shard_map_train_step).")
    from repro.models.blocks import resolve_block_structure

    # Config-time resolution of block_structure x remat x architecture:
    # an invalid combination (e.g. remat='full' with reversible blocks)
    # fails here with a readable error, not at trace time.
    resolve_block_structure(cfg, rcfg)
    resolved = resolve_for_run(cfg, rcfg, mesh=mesh)
    _, opt_update = make_optimizer(rcfg.optimizer)
    seed_key = jax.random.key(rcfg.seed)

    def train_step(state: TrainState, batch: dict, step: jax.Array):
        key = jax.random.fold_in(seed_key, step)
        loss, metrics, grads = loss_and_grad(
            cfg, rcfg, resolved, state.params, batch, key
        )
        grads, gnorm = clip_by_global_norm(grads, rcfg.grad_clip)
        lr = warmup_cosine(step, total_steps, rcfg.lr, rcfg.warmup_frac)
        new_params, new_opt = opt_update(
            grads, state.opt, state.params, lr,
            weight_decay=rcfg.weight_decay, pamm_lr_scale=rcfg.pamm_lr_scale,
        )
        out_metrics = finish_metrics(loss, metrics, gnorm, lr)
        return TrainState(params=new_params, opt=new_opt, ef=state.ef), out_metrics

    return train_step
