"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama-60m --steps 200 \
      --seq-len 256 --global-batch 16 --policy pamm --ratio 512

Runs the full production loop: sharded state, deterministic data pipeline,
fault-tolerant supervisor (checkpoint/restart), straggler watchdog, async
checkpointing. On this CPU container use smoke/small archs; on a real TPU
fleet the same driver runs under the production mesh.
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import RunConfig, get_config
from repro.data import SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.runtime import sharding as sh
from repro.runtime.fault import StragglerWatchdog, run_supervised
from repro.train import (
    init_distributed_state,
    init_train_state,
    make_shard_map_train_step,
    make_train_step,
)


def build_trainer(cfg, rcfg, *, total_steps: int, executor: str = "jit",
                  mesh=None):
    """Initial TrainState, jitted ``step(state, batch, step_idx)`` and the
    batch sharding (None: host batches go to the default device) for one
    executor — the CLI below and ``chip_smoke.py`` both train through it.

    ``shard_map`` needs ``mesh``; it returns state laid out with ZeRO-1
    shardings and a step jitted with donated state. ``jit`` runs on the
    default device, or GSPMD-shards the params over ``mesh`` when given.
    """
    key = jax.random.key(rcfg.seed)
    if executor == "shard_map":
        state, _ = init_distributed_state(cfg, rcfg, key, mesh)
        step_fn = make_shard_map_train_step(
            cfg, rcfg, total_steps=total_steps, mesh=mesh)
        return (state, step_fn,
                jax.sharding.NamedSharding(mesh, sh.data_pspec(mesh)))
    state, specs = init_train_state(cfg, rcfg, key)
    if mesh is not None:
        param_sh = sh.spec_tree_to_shardings(specs, mesh)
        state = state._replace(params=jax.device_put(state.params, param_sh))
    # plan resolution sees the mesh: shard-local PAMM blocking
    # (blocks=auto) and backend selection are derived there, not threaded
    # as flags.
    step_fn = make_train_step(cfg, rcfg, total_steps=total_steps, mesh=mesh)
    return state, jax.jit(step_fn, donate_argnums=(0,)), None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--policy", default="pamm",
                    choices=["pamm", "uniform_crs", "compact", "none"],
                    help="legacy single-policy shorthand (see --compression)")
    ap.add_argument("--ratio", type=float, default=512, help="compression divisor r=1/x")
    ap.add_argument("--compression", default="",
                    help="CompressionPlan spec, e.g. "
                         "'attn.qkv=pamm(r=1/512);ffn.*=compact(r=1/4)'; "
                         "overrides --policy/--ratio (DESIGN.md §2)")
    ap.add_argument("--attn-kernel", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="attention backend for the train step: Pallas "
                         "FlashAttention-2 fwd+bwd kernels or the chunked "
                         "jnp sdpa (auto = pallas on TPU)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data-model", type=int, nargs=2, default=None,
                    metavar=("DATA", "MODEL"), help="debug mesh shape")
    ap.add_argument("--mesh-context", type=int, default=1,
                    help="context-parallel (ring attention) mesh degree: "
                         "the sequence axis zigzag-shards over this many "
                         "devices and k/v rotate via ppermute "
                         "(shard_map executor only; seq-len must divide "
                         "by 2x this)")
    ap.add_argument("--executor", default="jit", choices=["jit", "shard_map"],
                    help="jit = one GSPMD program (single-process default); "
                         "shard_map = explicit DP x TP executor "
                         "(train/distributed.py): per-shard fwd/bwd, manual "
                         "gradient all-reduce (optionally int8-EF "
                         "compressed), ZeRO-1 optimizer sharding")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8_ef"],
                    help="DP gradient all-reduce compression "
                         "(shard_map executor only)")
    ap.add_argument("--block-structure", default="residual",
                    choices=["residual", "reversible"],
                    help="reversible = two-stream RevNet blocks whose "
                         "backward reconstructs the residual stream instead "
                         "of saving it (near-O(1) activation memory in "
                         "depth; attn/moe/rec kinds only, incompatible "
                         "with remat — see models/blocks.py)")
    args = ap.parse_args(argv)
    if args.mesh_context > 1 and args.executor != "shard_map":
        ap.error("--mesh-context > 1 needs --executor shard_map (the ring's "
                 "ppermute collectives require the manual context axis)")

    cfg = get_config(args.arch)
    rcfg = RunConfig(
        compression=args.compression,
        policy_name=args.policy, pamm_ratio=1.0 / args.ratio, lr=args.lr,
        compute_dtype="float32", param_dtype="float32",
        attn_kernel=args.attn_kernel, grad_compress=args.grad_compress,
        block_structure=args.block_structure,
    )
    stream = SyntheticStream.for_arch(cfg, args.seq_len, args.global_batch)

    mesh = None
    if args.executor == "shard_map":
        # default mesh: all visible devices on the data axis (minus the
        # context degree when ring attention is requested)
        cp = max(1, args.mesh_context)
        dm = args.data_model or (max(1, len(jax.devices()) // cp), 1)
        mesh = make_debug_mesh(*dm, context=cp)
        sh.validate_seq_divisible(args.seq_len, mesh, where="launch")
    elif args.data_model:
        mesh = make_debug_mesh(*args.data_model)
    if mesh is not None:
        sh.validate_batch_divisible(args.global_batch, mesh,
                                    grad_accum=rcfg.grad_accum, where="launch")
    state, step_fn, batch_sharding = build_trainer(
        cfg, rcfg, total_steps=args.steps, executor=args.executor, mesh=mesh)

    holder = {"state": state, "metrics": None}
    watchdog = StragglerWatchdog()

    def one_step(step: int):
        batch = {k: jnp.asarray(v) for k, v in stream.get_batch(step).items()}
        if batch_sharding is not None:
            batch = jax.device_put(batch, batch_sharding)
        holder["state"], m = step_fn(holder["state"], batch, jnp.int32(step))
        holder["metrics"] = m
        if step % args.log_every == 0:
            m = {k: float(v) for k, v in m.items()}
            print(f"step {step:6d} loss {m['loss']:.4f} ppl {math.exp(min(m['nll'], 20)):.2f} "
                  f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e}", flush=True)
        return {}

    t0 = time.monotonic()
    if args.ckpt_dir:
        report = run_supervised(
            total_steps=args.steps,
            step_fn=one_step,
            state_provider=lambda: holder["state"],
            state_restorer=lambda tree, s: holder.__setitem__("state", tree),
            ckpt_root=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            watchdog=watchdog,
        )
        print(f"supervisor: {report}")
    else:
        for s in range(args.steps):
            one_step(s)
    dt = time.monotonic() - t0
    tokens = args.steps * args.global_batch * args.seq_len
    print(f"done: {args.steps} steps, {tokens/dt:.0f} tok/s, "
          f"final loss {float(holder['metrics']['loss']):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
