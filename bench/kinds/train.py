"""Training cells: the program's jitted train step, steps back to back.

Set-up builds the state from the seed (weights in the reference layout,
converted to the program's tree, and AdamW's zero moments, in one jitted
call), compiles the step, and drives that same compiled step and state
through the first three steps. It reads there what the comparison needs
(each loss, the first clipped gradient from AdamW's first moment, the
change of the weights over the three steps) and hands the state on to the
window, which dispatches further steps until ``--seconds`` have passed.
It keeps up to AHEAD_S seconds of steps dispatched ahead of the one it waits
for (the runtime may queue fewer), so that a stall of the host does not
leave the chip idle. Once its time is up it sends nothing more, waits for
every step it sent, and only then reads the clock, so all the work it sent
counts over all that time; the losses are read after that wait. After the
window the state is freed and the reference runs the same three steps from
the same weights and batches.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic as traffic_lib

CHECK_STEPS = 3
DATA_STREAM = 0x7A1  # folds the data key off the weight key
PAMM_STREAM = 0
AHEAD_S = 5.0  # seconds of steps in flight ahead of the one waited for


def build(cell, seed: int):
    """The cell's step function (jitted, state donated), the jitted state
    initialiser and the batch function."""
    from repro.configs import RunConfig
    from repro.optim import make_optimizer
    from repro.train import TrainState, make_train_step

    cfg = cell["config_file"]
    tr = cell["traffic_file"]
    fam = common.load_module("families", cfg["family"])
    ref = common.load_module("reference", cfg["family"])
    mcfg = fam.model_config(cfg)
    run = cfg["run"]
    opt = cfg["optimizer"]
    rcfg = RunConfig(
        compression=run["compression"], compute_dtype=run["compute_dtype"],
        param_dtype=run["param_dtype"], attn_kernel="auto",
        loss_chunk=run["loss_chunk"], optimizer="adamw", lr=opt["lr"],
        warmup_frac=opt["warmup_frac"], grad_clip=opt["grad_clip"],
        weight_decay=opt["weight_decay"], pamm_lr_scale=opt["qkv_lr_scale"],
        # the step bakes key(rcfg.seed) in as a constant: a seed of the run
        # here would compile a new step for every seed; weights and data
        # carry the run's seed, PAMM's sampling stream stays fixed
        seed=PAMM_STREAM)
    opt_init, _ = make_optimizer("adamw")
    pdt = jnp.dtype(run["param_dtype"])
    key = jax.random.key(seed)

    @jax.jit
    def init_state(key):
        params = fam.to_program(ref.init_weights(cfg, key, pdt))
        return TrainState(params=params, opt=opt_init(params))

    # the jit executor as launch/train.build_trainer builds it
    step_fn = jax.jit(make_train_step(mcfg, rcfg, total_steps=opt["total_steps"]),
                      donate_argnums=(0,))
    batch_fn = jax.jit(functools.partial(
        traffic_lib.train_batch, batch=tr["batch"], seq_len=tr["seq_len"],
        vocab=cfg["vocab_size"]))
    data_key = jax.random.fold_in(key, DATA_STREAM)
    return dict(cfg=cfg, fam=fam, ref=ref, mcfg=mcfg, rcfg=rcfg, key=key, pdt=pdt,
                init_state=init_state, step_fn=step_fn,
                batch=lambda s: batch_fn(data_key, s))


def lowered_kernels(lowered) -> set[str]:
    import re

    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


def program_readings(b, step, state, batches, spans):
    """Run the first steps; return the state after them and the readings."""
    fam, cfg, ref = b["fam"], b["cfg"], b["ref"]
    b1 = cfg["optimizer"]["b1"]
    pdt, key = b["pdt"], b["key"]

    # the key is an argument, not a constant, so one program serves every seed
    @jax.jit
    def change_norms(params, key):
        p0 = ref.init_weights(cfg, key, pdt)
        p = fam.from_program(params)
        return fam.leaf_norms(jax.tree.map(
            lambda a, c: a.astype(jnp.float32) - c.astype(jnp.float32), p, p0))

    # AdamW's first moment after one step from zero is (1 - b1) g
    grad_norms = jax.jit(lambda m: {k: v / (1.0 - b1) for k, v in
                                    fam.leaf_norms(fam.from_program(m)).items()})
    losses, first_grad = [], None
    for s in range(CHECK_STEPS):
        t = common.now()
        with spans("train_step"):
            state, met = step(state, batches[s], jnp.int32(s))
            losses.append(float(met["loss"]))
        step_s = common.now() - t  # the last, warm step's time sizes the window's lead
        if s == 0:
            first_grad = {k: float(v) for k, v in grad_norms(state.opt.m).items()}
    change = {k: float(v) for k, v in change_norms(state.params, key).items()}
    return state, dict(losses=losses, grad=first_grad, change=change), step_s


def reference_readings(b, batches, mode: str = "f32"):
    cfg, fam, ref = b["cfg"], b["fam"], b["ref"]
    init = jax.jit(lambda k: ref.init_weights(cfg, k, jnp.float32))
    run = cfg["run"]
    pamm = None
    if run["compression"].startswith("attn.qkv=pamm"):
        pamm = dict(run_seed=b["rcfg"].seed, ratio=run["pamm_ratio"])
    data = [(bt["tokens"], bt["labels"]) for bt in batches]
    losses, grad, w3 = ref.train_steps(cfg, init(b["key"]), data, cfg["optimizer"], mode,
                                       pamm, grad_stat=jax.jit(fam.leaf_norms))
    change = jax.jit(lambda w, k: fam.leaf_norms(
        jax.tree.map(jnp.subtract, w, init(k))))(w3, b["key"])
    return dict(losses=losses, grad={k: float(v) for k, v in grad.items()},
                change={k: float(v) for k, v in change.items()})


def compare(prog: dict, refr: dict) -> dict:
    """The numbers compared: the worst relative loss gap over the steps, and
    by the worst leaf the gap of the first gradient's norms and of the
    norms of the weights' change. Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out of the change: AdamW moves
    them by round-off alone."""
    loss = max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
               for p, r in zip(prog["losses"], refr["losses"]))
    grad = common.worst_leaf_gap(prog["grad"], refr["grad"])
    med = float(np.median(list(refr["grad"].values())))
    still = {k for k, v in refr["grad"].items() if v < 1e-3 * med}
    change = common.worst_leaf_gap(prog["change"], refr["change"], skip=still)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def broken(step, fault: str):
    """The step with a fault planted (for bench/control.py and the tests):
    ``unchanged`` returns the state it was given; ``half_batch`` drops the
    second half of the batch's tokens, the mean taken over the rest."""
    if fault == "unchanged":
        return lambda state, batch, s: (state, step(
            jax.tree.map(jnp.copy, state), batch, s)[1])
    if fault == "half_batch":
        def half(state, batch, s):
            n = batch["tokens"].shape[1] // 2
            return step(state, {k: v[:, :n] for k, v in batch.items()}, s)
        return half
    raise ValueError(f"unknown fault {fault!r}")


def run(cell, args, devices, spans, counter, window_open, window_close):
    tr = cell["traffic_file"]
    limits = cell["settings"]["limits"]
    fault = getattr(args, "fault", None)
    b = build(cell, args.seed)
    B, L = tr["batch"], tr["seq_len"]
    tokens_per_step = B * L
    state = b["init_state"](b["key"])
    batches = [b["batch"](s) for s in range(CHECK_STEPS)]
    lowered = b["step_fn"].lower(state, batches[0], jnp.int32(0))
    if devices[0].platform == "tpu":
        missing = set(cell["settings"]["kernels"]) - lowered_kernels(lowered)
        if missing:
            raise RuntimeError(f"train step lowered without {sorted(missing)}")
    step = lowered.compile()
    mem = step.memory_analysis()
    if fault:
        step = broken(b["step_fn"] if fault == "half_batch" else step, fault)
    hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    common.log(f"compiled step: argument {mem.argument_size_in_bytes} temp "
               f"{mem.temp_size_in_bytes} output {mem.output_size_in_bytes} "
               f"alias {mem.alias_size_in_bytes} bytes")
    state, prog, step_s = program_readings(b, step, state, batches, spans)
    ahead = max(1, math.ceil(AHEAD_S / step_s))
    common.log(f"set-up steps: peak_bytes_in_use {common.peak_bytes(devices)} "
               f"against memory_analysis {hbm}; last step {step_s:.4f} s, "
               f"{ahead} steps in flight in the window")
    del batches

    # window
    window_open()
    counter.armed = True
    s = CHECK_STEPS
    n_steps, losses, pending = 0, [], collections.deque()
    t0 = common.now()
    with spans("window"):
        while True:
            with spans("batch"):
                batch = b["batch"](s)
            with spans("train_step"):
                state, met = step(state, batch, jnp.int32(s))
            losses.append(met["loss"])
            pending.append(met["loss"])
            s += 1
            n_steps += 1
            if len(pending) > ahead:
                with spans("wait"):
                    pending.popleft().block_until_ready()
            if common.now() - t0 >= args.seconds:
                break
        t_close = common.now()
        with spans("drain"):
            jax.block_until_ready(list(pending))
    t1 = common.now()
    common.log(f"window: {n_steps} steps in {t1 - t0:.4f} s, of which "
               f"{t1 - t_close:.4f} s waiting for the steps in flight at the close")
    losses = [float(x) for x in jax.device_get(losses)]
    counter.armed = False
    window_close()
    peak = common.peak_bytes(devices)
    del state, step, lowered

    tr0 = common.now()
    batches = [b["batch"](k) for k in range(CHECK_STEPS)]
    refr = reference_readings(b, batches)
    numbers = compare(prog, refr)
    if getattr(args, "control", False):
        control = compare(reference_readings(b, batches, mode="fp8"), refr)
        common.log(f"control (reference in fp8 in the program's place): {control}")
    common.log(f"reference took {common.now() - tr0:.1f} s; losses program "
               f"{prog['losses']} reference {refr['losses']}")
    failed = sum(1 for x in losses if not math.isfinite(x))
    cfg = cell["config_file"]
    fam = b["fam"]
    return dict(
        attempted=n_steps, failed=failed, numbers=numbers,
        control=control if getattr(args, "control", False) else None,
        e2e={"train_tokens_per_s": n_steps * tokens_per_step / (t1 - t0)},
        checks=[(k, numbers[k], limits[k]) for k in ("loss_gap", "grad_gap", "change_gap")],
        memory_peak_bytes=peak,
        ctx=dict(
            kind="train", window_s=t1 - t0, steps=n_steps,
            tokens=n_steps * tokens_per_step, hbm_bytes=hbm,
            model_flops=n_steps * tokens_per_step * fam.train_flops_per_token(cfg, L),
            kernel_calls={k: v * n_steps for k, v in kernel_calls(
                b["ref"], cfg, tr, cell["settings"]["kernels"]).items()},
        ))


def kernel_calls(ref, cfg, tr, kernels) -> dict:
    """The shapes of each kernel's calls in one step."""
    s = ref.sizes(cfg)
    B, L = tr["batch"], tr["seq_len"]
    attn = dict(B=B, Lq=L, Lk=L, H=s["h"], KV=s["kv"], dh=s["dh"],
                causal=True, itemsize=2)
    b = B * L
    k = max(1, min(b, math.ceil(cfg["run"].get("pamm_ratio", 0) * b)))
    per_step = {
        "flash_fwd": [attn] * s["layers"],
        "flash_dq": [attn] * s["layers"],
        "flash_dkv": [attn] * s["layers"],
        "pamm_compress": [dict(b=b, n=s["d"], k=k, itemsize=2)] * s["layers"],
        "pamm_apply": [dict(b=b, m=m, k=k, itemsize=2)
                       for m in (s["h"] * s["dh"], s["kv"] * s["dh"],
                                 s["kv"] * s["dh"])] * s["layers"],
    }
    return {kname: per_step[kname] for kname in kernels}
