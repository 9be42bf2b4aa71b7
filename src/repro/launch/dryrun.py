import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For one (arch x input-shape x mesh) cell:
  1. build the production mesh (16x16 single-pod or 2x16x16 multi-pod) on
     512 forced host devices,
  2. assemble ShapeDtypeStruct stand-ins for params / optimizer state /
     batch / caches (no allocation anywhere),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile()``,
  4. print + persist ``memory_analysis()`` / ``cost_analysis()`` and the
     per-type collective operand bytes parsed from the compiled HLO —
     these feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every cell, both meshes
"""
import argparse
import json
import math
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, SHAPES, RunConfig, get_config
from repro.data import make_batch_specs
from repro.launch import mesh as mesh_lib
from repro.models import param_specs
from repro.models.model import cache_logical_specs, init_caches
from repro.runtime import sharding as sh
from repro.train import make_train_step, make_decode_step
from repro.optim import make_optimizer
from repro.train.train_step import TrainState

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
    "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_NAME_RE = re.compile(r"%?([A-Za-z_][\w.\-]*)")


def _type_bytes(type_str: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-type operand bytes from a post-SPMD HLO module.

    Two passes: (1) symbol table instruction-name -> result bytes; (2) for
    every collective op, sum the sizes of its operands (by name lookup, or
    directly if the dump includes operand types). ``-done`` ops are skipped
    (their ``-start`` was counted).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}

    sizes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            # result type is the text before the op name/call
            rhs = m.group(2)
            cut = rhs.find("(")
            head = rhs if cut < 0 else rhs[:cut]
            sizes[m.group(1)] = _type_bytes(head)

    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(
            r"=\s*[^=]*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(-start|-done)?\(", stripped)
        if not m or stripped.startswith(("//", "#")):
            continue
        if m.group(2) == "-done":
            continue
        op = m.group(1)
        paren = stripped[m.end() - 1:]
        depth, end = 0, 0
        for i, ch in enumerate(paren):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = paren[1:end]
        nbytes = _type_bytes(operand_str)  # old-style dump with operand types
        if nbytes == 0:
            for name in _NAME_RE.findall(operand_str):
                nbytes += sizes.get(name, 0)
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def kv_multiplier(cfg, mesh) -> int | None:
    """Replicate KV heads so they divide the model axis (DESIGN.md §5).

    Requires (a) model % n_kv == 0 (so replication is integral) and
    (b) n_heads % model == 0 (so GQA grouping stays valid). Archs that
    cannot satisfy both (granite H=24, musicgen MHA=24) keep their native
    KV count and the sanitizer replicates the head dim instead.
    """
    if cfg.n_kv_heads == 0:
        return None
    model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if cfg.n_kv_heads >= model:
        return None
    if model % cfg.n_kv_heads == 0 and cfg.n_heads % model == 0:
        return model
    return None


def default_runcfg(cfg, mode: str) -> RunConfig:
    big = cfg.param_count() > 5e9
    return RunConfig(
        # blocks=auto: shard-local PAMM blocking is derived from the mesh's
        # data-parallel degree at plan resolution (run_cell passes the mesh).
        # attn.* covers attn.qkv plus attn.cross_kv where present, without
        # tripping the matches-no-site warning on non-multimodal archs.
        compression="attn.*=pamm(r=1/512,eps=inf,blocks=auto,backend=auto)",
        compute_dtype="bfloat16",
        param_dtype="bfloat16" if big else "float32",
        remat="pamm" if mode == "train" else "none",
        seq_shard=big,
        optimizer="adafactor" if cfg.param_count() > 2e11 else "adamw",
        attn_chunk=1024,
        loss_chunk=512,
    )


def rules_for(cfg, mesh) -> dict:
    """FSDP rules (embed dim over data) for models too big to replicate."""
    rules = dict(sh.DEFAULT_RULES)
    if cfg.param_count() > 5e9:
        rules["embed"] = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return rules


def cell_runnable(cfg, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: 500k decode needs sub-quadratic attention (skip per assignment)"
    return True, ""


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, verbose=True,
             rcfg_overrides: dict | None = None, save_hlo: str | None = None) -> dict:
    import dataclasses as _dc

    cfg = get_config(arch)
    shape = next(s for s in SHAPES if s[0] == shape_name)
    _, seq_len, global_batch, mode = shape
    ok, why = cell_runnable(cfg, shape_name)
    result = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "seq_len": seq_len, "global_batch": global_batch,
    }
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rcfg = default_runcfg(cfg, mode)
    if rcfg_overrides:
        rcfg = _dc.replace(rcfg, **rcfg_overrides)
        result["rcfg_overrides"] = {k: repr(v) for k, v in rcfg_overrides.items()}
    rules = rules_for(cfg, mesh)
    n_kv_eff = kv_multiplier(cfg, mesh)

    shapes_tree, spec_tree = param_specs(cfg, rcfg, n_kv_eff=n_kv_eff)
    param_sh = sh.spec_tree_to_shardings(spec_tree, mesh, rules)
    param_sh = sh.sanitize_shardings(param_sh, shapes_tree, mesh)

    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        if mode == "train":
            opt_init, _ = make_optimizer(rcfg.optimizer)
            opt_shapes = jax.eval_shape(opt_init, shapes_tree)
            opt_sh = sh.opt_state_shardings(
                opt_shapes, param_sh, shapes_tree, mesh,
                optimizer=rcfg.optimizer, zero1=rcfg.zero1,
            )
            opt_sh = sh.sanitize_shardings(opt_sh, opt_shapes, mesh)
            state_shapes = TrainState(params=shapes_tree, opt=opt_shapes)
            state_sh = TrainState(params=param_sh, opt=opt_sh)
            batch_specs = make_batch_specs(cfg, seq_len, global_batch, mode="train")
            batch_sh = sh.batch_shardings(batch_specs, mesh)
            step_fn = make_train_step(cfg, rcfg, total_steps=10000, mesh=mesh)
            jitted = jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sh, sh.replicated(mesh)),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(
                state_shapes, batch_specs, jax.ShapeDtypeStruct((), jnp.int32)
            )
        elif mode == "prefill":
            from repro.train import make_prefill

            batch_specs = make_batch_specs(cfg, seq_len, global_batch, mode="serve")
            batch_sh = sh.batch_shardings(batch_specs, mesh)
            prefill_fn = make_prefill(cfg, rcfg, max_len=seq_len + 128)
            jitted = jax.jit(
                prefill_fn, in_shardings=(param_sh, batch_sh)
            )
            lowered = jitted.lower(shapes_tree, batch_specs)
        else:  # decode
            B = global_batch
            shard_seq = B < 16  # long_500k: shard the cache sequence dim
            cache_shapes = jax.eval_shape(
                lambda: init_caches(cfg, rcfg, B, seq_len, n_kv_eff=n_kv_eff)
            )
            cache_logical = cache_logical_specs(cfg, shard_cache_seq=shard_seq)
            # broadcast per-block logical specs over the eval_shape tree
            cache_sh = sh.spec_tree_to_shardings(cache_logical, mesh, rules)
            cache_sh = sh.sanitize_shardings(cache_sh, cache_shapes, mesh)
            if cfg.embed_inputs:
                tok_spec = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)
            else:
                tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            pos_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            extras_specs = {}
            extras_sh = {}
            if cfg.vision_tokens:
                extras_specs["image_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16
                )
                extras_sh = sh.batch_shardings(extras_specs, mesh)
            tok_sh = sh.batch_shardings({"t": tok_spec}, mesh)["t"] if not shard_seq \
                else sh.replicated(mesh)
            decode_fn = make_decode_step(cfg, rcfg)
            jitted = jax.jit(
                decode_fn,
                in_shardings=(param_sh, tok_sh, sh.replicated(mesh), cache_sh, extras_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(3,),
            )
            lowered = jitted.lower(
                shapes_tree, tok_spec, pos_spec, cache_shapes, extras_specs
            )

        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    from repro.launch import hlo_cost as _hlo_cost

    mem = compiled.memory_analysis()
    cost = _hlo_cost.xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    if save_hlo:
        import gzip

        with gzip.open(save_hlo, "wt") as f:
            f.write(hlo)
    from repro.launch import hlo_cost

    mine = hlo_cost.analyze(hlo)

    flops = float(mine["flops"])  # trip-count-aware (hlo_cost.py)
    bytes_accessed = float(mine["bytes"])
    coll = {
        "bytes": mine["coll_bytes"],
        "counts": mine["coll_counts"],
        "total_bytes": mine["total_collective_bytes"],
    }
    result.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {
            "flops_per_device": flops,
            "bytes_accessed_per_device": bytes_accessed,
            "xla_flops_body_once": float(cost.get("flops", 0.0)),
            "xla_bytes_body_once": float(cost.get("bytes accessed", 0.0)),
            "unknown_trip_count_loops": mine["unknown_trip_count_loops"],
        },
        "collectives": coll,
        "roofline": roofline_terms(cfg, flops, bytes_accessed, coll["total_bytes"],
                                   seq_len, global_batch, mode, n_chips),
    })
    if verbose:
        print(json.dumps(result, indent=2))
    return result


def roofline_terms(cfg, flops_per_dev, bytes_per_dev, coll_bytes_per_dev,
                   seq_len, global_batch, mode, n_chips) -> dict:
    compute_s = flops_per_dev / mesh_lib.V5E_PEAK_FLOPS
    memory_s = bytes_per_dev / mesh_lib.V5E_HBM_BW
    collective_s = coll_bytes_per_dev / mesh_lib.V5E_ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    # MODEL_FLOPS: 6*N*D for train, 2*N_active*D for decode/prefill forward
    n_active = cfg.active_param_count()
    tokens = global_batch * (seq_len if mode != "decode" else 1)
    factor = 6 if mode == "train" else 2
    model_flops = factor * n_active * tokens
    hlo_total = flops_per_dev * n_chips
    terms.update({
        "dominant": dom,
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_fraction": model_flops / hlo_total if hlo_total else None,
        "step_time_lower_bound_s": max(terms["compute_s"], memory_s, collective_s),
        "mfu_upper_bound": (model_flops / (n_chips * mesh_lib.V5E_PEAK_FLOPS))
        / max(compute_s, memory_s, collective_s)
        if max(compute_s, memory_s, collective_s) > 0 else None,
    })
    return terms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ASSIGNED_ARCHS), default=None)
    ap.add_argument("--shape", choices=[s[0] for s in SHAPES], default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="RunConfig override, e.g. --set pamm_blocks=16")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for the output JSON name")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        import ast

        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape[0], mp))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip cached] {tag}")
            continue
        print(f"[dryrun] {tag}", flush=True)
        try:
            res = run_cell(arch, shape, mp, verbose=False,
                           rcfg_overrides=overrides or None,
                           save_hlo=(path[:-5] + ".hlo.txt.gz") if args.save_hlo else None)
        except Exception as e:  # a failing cell is a bug — record and continue
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
        print(f"  -> {res['status']}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
