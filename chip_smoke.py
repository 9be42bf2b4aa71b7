#!/usr/bin/env python3
"""Bring-up check: train and serve internlm2-1.8b (arXiv:2403.17297) at its
published widths on a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: device, train, serve
    python3 chip_smoke.py --chips 4   # four chips: shard_map trainer on a
                                      # (data=2, model=1, context=2) mesh
                                      # against the jit executor on chip 0

Everything runs in this one process (a chip belongs to one process), and
any failed check exits non-zero. The last line of standard output is the
JSON result; every other line comes before it. The times and memory it
prints are from a bring-up run, not benchmark numbers. Weights are random
(seeded) and data is synthetic.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import re
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "internlm2-1.8b"
# Train phase: depth cut to 2 layers (the pattern is all attention, so one
# layer is a whole period); widths, heads and vocab as published.
TRAIN_LAYERS = 2
TRAIN_SPEC = "attn.qkv=pamm(r=1/512)"
SEQ_LEN = 1024
GLOBAL_BATCH = 4
TRAIN_STEPS = 3
TRAIN_KERNELS = {"flash_fwd", "flash_dq", "flash_dkv",
                 "pamm_compress", "pamm_apply"}
# Serve phase: all 24 layers in bf16, paged KV cache, greedy decoding.
N_REQUESTS = 8
PROMPT_LEN = 512
GEN_LEN = 32
# Pallas vs jnp prefill, both bf16: the two paths round attention outputs
# at different points (one bf16 step is 2**-8 relative), and 24 layers of
# residual stream carry those differences on. Eight steps of relative L2
# error are allowed, on the last-position logits and on every K/V cache.
PREFILL_REL_L2 = 2.0 ** -5
# --chips 4: both executors take step 0 at lr 0 (warmup), so the two loss
# readings are forwards of the same initial weights on the same batches.
# They differ only in f32 reduction order (data-shard means, ring merges
# of attention across context shards), far below this bound.
LOSS_REL = 1e-4


def fail(msg: str):
    sys.exit(f"chip_smoke: FAIL: {msg}")


def record(phase: str, **fields):
    print("bring-up (not a benchmark) " + json.dumps({"phase": phase, **fields}),
          flush=True)


def tpu_devices(count: int):
    """The TPU devices, asked for by backend name: no fallback to CPU."""
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if len(devices) < count:
        fail(f"needs {count} TPU chips, found {len(devices)}")
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def kernel_names(lowered) -> set[str]:
    """Mosaic kernels in a lowered program. Interpret mode and the jnp
    references lower to plain HLO and leave no name here."""
    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


def train_config():
    from repro.configs import RunConfig, get_config

    base = get_config(ARCH)
    cfg = dataclasses.replace(base, n_layers=TRAIN_LAYERS,
                              stages=((("attn",), TRAIN_LAYERS),))
    # as the training CLI builds it, with the kernel path pinned
    rcfg = RunConfig(compression=TRAIN_SPEC, attn_kernel="pallas",
                     compute_dtype="float32", param_dtype="float32")
    return cfg, rcfg


def train_batches(cfg, n: int) -> list[dict]:
    from repro.data import SyntheticStream

    stream = SyntheticStream.for_arch(cfg, SEQ_LEN, GLOBAL_BATCH)
    return [{k: jnp.asarray(v) for k, v in stream.get_batch(s).items()}
            for s in range(n)]


def run_steps(step_fn, state, batches, batch_sharding=None):
    """Losses and per-step wall seconds (each step ends in a host read)."""
    losses, seconds = [], []
    for s, batch in enumerate(batches):
        if batch_sharding is not None:
            batch = jax.device_put(batch, batch_sharding)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch, jnp.int32(s))
        losses.append(float(metrics["loss"]))
        seconds.append(time.perf_counter() - t0)
    return state, losses, seconds


def phase_train(device):
    from repro.launch.train import build_trainer

    cfg, rcfg = train_config()
    batches = train_batches(cfg, TRAIN_STEPS)
    state, step_fn, _ = build_trainer(cfg, rcfg, total_steps=TRAIN_STEPS)
    n_params = sum(a.size for a in jax.tree.leaves(state.params))

    t0 = time.perf_counter()
    lowered = step_fn.lower(state, batches[0], jnp.int32(0))
    missing = TRAIN_KERNELS - kernel_names(lowered)
    if missing:
        fail(f"train step lowered without the kernels {sorted(missing)}")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    state, losses, seconds = run_steps(compiled, state, batches)
    if not all(math.isfinite(x) for x in losses):
        fail(f"train losses not finite: {losses}")
    record("train", arch=ARCH, layers=TRAIN_LAYERS, params=int(n_params),
           compression=TRAIN_SPEC, seq_len=SEQ_LEN,
           global_batch=GLOBAL_BATCH, losses=losses,
           compile_s=compile_s, first_step_s=seconds[0],
           steady_step_s=statistics.median(seconds[1:]),
           compiled_argument_bytes=int(mem.argument_size_in_bytes),
           compiled_temp_bytes=int(mem.temp_size_in_bytes),
           peak_bytes_in_use=peak_bytes(device))


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_serve(device):
    from repro.configs import RunConfig, get_config
    from repro.data import SyntheticStream
    from repro.models import decode_step, init_model, prefill
    from repro.serve import Request, SamplingParams, ServeEngine

    cfg = get_config(ARCH)
    rcfg = RunConfig(compute_dtype="bfloat16", param_dtype="bfloat16",
                     policy_name="none", attn_kernel="pallas")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    max_len = PROMPT_LEN + GEN_LEN + 1
    prompts = np.asarray(
        SyntheticStream.for_arch(cfg, PROMPT_LEN, N_REQUESTS).get_batch(0)
        ["tokens"])

    # Pallas prefill against the jnp attention path, same weights.
    first = {"tokens": jnp.asarray(prompts[:1])}
    outs = {}
    for kernel in ("pallas", "jnp"):
        rc = dataclasses.replace(rcfg, attn_kernel=kernel)
        fn = jax.jit(functools.partial(prefill, cfg, rc, max_len=max_len))
        if kernel == "pallas" and "flash_fwd" not in kernel_names(
                fn.lower(params, first)):
            fail("prefill lowered without the flash_fwd kernel")
        outs[kernel] = fn(params, first)
    errs = [rel_l2(outs["pallas"][0], outs["jnp"][0])]
    errs += [rel_l2(a, b) for a, b in zip(jax.tree.leaves(outs["pallas"][1]),
                                           jax.tree.leaves(outs["jnp"][1]))
             if jnp.issubdtype(a.dtype, jnp.floating)]
    if not max(errs) <= PREFILL_REL_L2:
        fail(f"pallas prefill differs from jnp: rel L2 {max(errs):.3e} > "
             f"{PREFILL_REL_L2:.3e}")
    del outs

    engine = ServeEngine(cfg, rcfg, params, max_slots=N_REQUESTS,
                         max_len=max_len, cache_layout="paged", page_size=16)

    def serve(uid0: int):
        reqs = [Request(uid=uid0 + i, tokens=prompts[i].tolist(),
                        max_new_tokens=GEN_LEN,
                        sampling=SamplingParams(temperature=0.0))
                for i in range(N_REQUESTS)]
        t0 = time.perf_counter()
        results = engine.run(reqs)
        seconds = time.perf_counter() - t0
        for r in reqs:
            out = results.get(r.uid)
            if out is None or len(out.tokens) != GEN_LEN:
                fail(f"request {r.uid} did not finish: {out}")
        return [results[r.uid].tokens for r in reqs], seconds

    cold_tokens, cold_s = serve(0)
    engine.reset_stats()
    warm_tokens, warm_s = serve(N_REQUESTS)
    if warm_tokens != cold_tokens:
        fail("greedy tokens changed between two runs of the same prompts")
    stats = engine.stats()

    # the engine's decode path, lowered once more: it must hold the paged
    # Pallas decode kernel
    B = N_REQUESTS
    lowered = jax.jit(functools.partial(decode_step, cfg, rcfg)).lower(
        params, jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 1), jnp.int32),
        engine.caches)
    if "paged_decode" not in kernel_names(lowered):
        fail("decode step lowered without the paged_decode kernel")
    record("serve", arch=ARCH, layers=cfg.n_layers, requests=N_REQUESTS,
           prompt_len=PROMPT_LEN, new_tokens=GEN_LEN,
           prefill_rel_l2_max=max(errs), cold_run_s=cold_s,
           warm_run_s=warm_s, warm_prefill_tok_s=stats["prefill_tok_s"],
           warm_decode_tok_s=stats["decode_tok_s"],
           peak_bytes_in_use=peak_bytes(device))


def phase_four_chips(devices):
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import build_trainer

    cfg, rcfg = train_config()
    batches = train_batches(cfg, 2)
    mesh = make_debug_mesh(2, 1, context=2)
    t0 = time.perf_counter()
    state, step_fn, batch_sh = build_trainer(
        cfg, rcfg, total_steps=2, executor="shard_map", mesh=mesh)
    state, sm_losses, sm_s = run_steps(step_fn, state, batches, batch_sh)
    sm_total = time.perf_counter() - t0
    del state, step_fn

    t0 = time.perf_counter()
    state, step_fn, _ = build_trainer(cfg, rcfg, total_steps=2)
    state, jit_losses, jit_s = run_steps(step_fn, state, batches)
    jit_total = time.perf_counter() - t0
    del state, step_fn

    record("four_chips", mesh=dict(mesh.shape), shard_map_losses=sm_losses,
           jit_device0_losses=jit_losses, shard_map_step_s=sm_s,
           jit_step_s=jit_s, shard_map_total_s=sm_total,
           jit_total_s=jit_total,
           peak_bytes_in_use=[peak_bytes(d) for d in devices])
    for a, b in zip(sm_losses, jit_losses):
        if not (math.isfinite(a) and abs(a - b) <= LOSS_REL * abs(b)):
            fail(f"shard_map losses {sm_losses} vs jit {jit_losses} "
                 f"(bound {LOSS_REL} relative)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the shard_map-vs-jit trainer check")
    args = ap.parse_args(argv)

    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_four_chips(devices[:4])
    else:
        phase_train(devices[0])
        phase_serve(devices[0])
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
