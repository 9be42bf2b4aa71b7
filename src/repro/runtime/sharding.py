"""Logical-axis sharding rules -> mesh PartitionSpecs.

Rules (DESIGN.md §5):
  batch   -> ('pod', 'data')   activations' leading batch axis (DP)
  heads   -> 'model'           attention head projections (TP)
  ffn     -> 'model'           FFN hidden / ssm inner / lru width (TP)
  experts -> 'model'           MoE expert axis (EP)
  vocab   -> 'model'           embedding + lm-head vocab (vocab parallelism)
  embed   -> None              d_model replicated
  layers  -> None              stacked-layer leading axis

ZeRO-1 (``zero1_specs``): optimizer moments take the param spec PLUS the
data axis on the first shardable unsharded dimension, so XLA lowers the
update into reduce-scatter(grads) + shard-local update + all-gather(params)
— optimizer state per chip shrinks by |data| without a hand-written wrapper.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "embed": None,
    "layers": None,
    None: None,
}


def _mesh_axes(mesh: Mesh) -> set[str]:
    return set(mesh.axis_names)


def logical_to_pspec(logical: tuple, mesh: Mesh, rules=None) -> PS:
    rules = rules or DEFAULT_RULES
    axes = _mesh_axes(mesh)
    out = []
    for name in logical:
        rule = rules.get(name)
        if rule is None:
            out.append(None)
        else:
            picked = tuple(a for a in rule if a in axes)
            out.append(picked if len(picked) > 1 else (picked[0] if picked else None))
    return PS(*out)


def spec_tree_to_shardings(spec_tree, mesh: Mesh, rules=None):
    """Map a tree of logical tuples to NamedShardings."""
    is_leaf = lambda s: isinstance(s, tuple) and all(
        isinstance(x, (str, type(None))) for x in s
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, logical_to_pspec(s, mesh, rules)),
        spec_tree, is_leaf=is_leaf,
    )


def validate_divisibility(shapes_tree, spec_tree, mesh: Mesh, rules=None):
    """Return a list of (path, shape, pspec) cells where sharding is uneven."""
    rules = rules or DEFAULT_RULES
    is_leaf = lambda s: isinstance(s, tuple) and all(
        isinstance(x, (str, type(None))) for x in s
    )
    bad = []
    flat_shapes = jax.tree_util.tree_flatten_with_path(shapes_tree)[0]
    flat_specs = jax.tree.flatten(spec_tree, is_leaf=is_leaf)[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for (path, shp), spec in zip(flat_shapes, flat_specs):
        ps = logical_to_pspec(spec, mesh, rules)
        for dim, entry in zip(shp.shape, tuple(ps) + (None,) * (len(shp.shape) - len(tuple(ps)))):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([sizes[a] for a in names]))
            if dim % total:
                bad.append((jax.tree_util.keystr(path), shp.shape, ps))
                break
    return bad


def _spec_axes(spec) -> set[str]:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            used.add(a)
    return used


def zero1_specs(param_pspec_tree, param_shapes_tree, mesh: Mesh, *, axis: str = "data"):
    """Optimizer-moment pspecs: param pspec + ``axis`` on a free dimension.

    Skips params that already consume ``axis`` (e.g. FSDP-sharded embed dim)
    — a mesh axis can appear at most once in a PartitionSpec.
    """
    if axis not in mesh.axis_names:
        return param_pspec_tree
    dsize = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def one(ps: NamedSharding, shp):
        spec = list(tuple(ps.spec) + (None,) * (len(shp.shape) - len(tuple(ps.spec))))
        if axis in _spec_axes(spec):
            return ps
        for i, (dim, entry) in enumerate(zip(shp.shape, spec)):
            if entry is None and dim % dsize == 0 and dim >= dsize:
                spec[i] = axis
                return NamedSharding(mesh, PS(*spec))
        return ps  # no shardable free dim -> keep replicated over data

    return jax.tree.map(one, param_pspec_tree, param_shapes_tree)


def sanitize_shardings(sh_tree, shapes_tree, mesh: Mesh):
    """Drop sharding on any dimension the mesh axes do not divide evenly.

    Catch-all that keeps odd dimensions (vocab 49155, 24 MHA heads, ...)
    runnable by replicating just that dimension; the cells affected are
    reported in EXPERIMENTS.md §Dry-run as replication fallbacks.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(ns: NamedSharding, shp):
        spec = list(tuple(ns.spec) + (None,) * (len(shp.shape) - len(tuple(ns.spec))))
        changed = False
        for i, (dim, entry) in enumerate(zip(shp.shape, spec)):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in names:
                total *= sizes[a]
            if dim % total:
                spec[i] = None
                changed = True
        return NamedSharding(mesh, PS(*spec)) if changed else ns

    return jax.tree.map(fix, sh_tree, shapes_tree)


def opt_state_shardings(opt_shapes, param_sh, param_shapes, mesh: Mesh, *,
                        optimizer: str = "adamw", zero1: bool = True):
    """Shardings for an OptState(step, m, v).

    AdamW: moments mirror the param shardings, plus ZeRO-1 (data axis on a
    free dim). Adafactor: factored stats inherit the param spec minus the
    reduced dim (rows: drop last; cols: drop second-to-last) — they are tiny
    so no ZeRO pass is applied.
    """
    if optimizer == "adamw":
        m_sh = zero1_specs(param_sh, opt_shapes.m, mesh) if zero1 else param_sh
        v_sh = zero1_specs(param_sh, opt_shapes.v, mesh) if zero1 else param_sh
        return type(opt_shapes)(step=replicated(mesh), m=m_sh, v=v_sh)

    def rows_spec(ps: NamedSharding, pshape):
        spec = tuple(ps.spec) + (None,) * (len(pshape.shape) - len(tuple(ps.spec)))
        if len(pshape.shape) >= 2:
            return NamedSharding(mesh, PS(*spec[:-1]))
        return NamedSharding(mesh, PS(*spec))

    def cols_spec(ps: NamedSharding, pshape):
        spec = tuple(ps.spec) + (None,) * (len(pshape.shape) - len(tuple(ps.spec)))
        if len(pshape.shape) >= 2:
            return NamedSharding(mesh, PS(*(spec[:-2] + spec[-1:])))
        return replicated(mesh)

    m_sh = jax.tree.map(rows_spec, param_sh, param_shapes)
    v_sh = jax.tree.map(cols_spec, param_sh, param_shapes)
    return type(opt_shapes)(step=replicated(mesh), m=m_sh, v=v_sh)


def batch_shardings(batch_specs: dict, mesh: Mesh, rules=None) -> dict:
    """Shard every input's leading (batch) axis over ('pod','data')."""
    rules = rules or DEFAULT_RULES
    axes = _mesh_axes(mesh)
    picked = tuple(a for a in ("pod", "data") if a in axes)
    ps = PS(picked if len(picked) > 1 else picked[0] if picked else None)
    return {k: NamedSharding(mesh, ps) for k in batch_specs}


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PS())


def data_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """The mesh's data-parallel axes, in collective order (('pod','data'),
    ('data',) or () for a pure-TP mesh)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_degree(mesh: Mesh) -> int:
    """Number of data-parallel shards (product of pod x data sizes)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    deg = 1
    for a in data_axis_names(mesh):
        deg *= sizes[a]
    return deg


def data_pspec(mesh: Mesh) -> PS:
    """PartitionSpec sharding a leading axis over the data axes."""
    axes = data_axis_names(mesh)
    if not axes:
        return PS()
    return PS(axes if len(axes) > 1 else axes[0])


CONTEXT_AXIS = "context"


def context_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """The mesh's context-parallel (sequence/ring) axes — ``('context',)``
    when present, else ``()``."""
    return tuple(a for a in (CONTEXT_AXIS,) if a in mesh.axis_names)


def cp_degree(mesh: Mesh) -> int:
    """Number of context-parallel (sequence) shards."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    deg = 1
    for a in context_axis_names(mesh):
        deg *= sizes[a]
    return deg


def sync_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """Axes gradients / loss / metrics reduce over: data x context. Every
    (data, context) coordinate computes the loss of a distinct (batch
    slice, sequence slice) block, so the reduction set is their product."""
    return data_axis_names(mesh) + context_axis_names(mesh)


def batch_pspec(mesh: Mesh) -> PS:
    """PartitionSpec for a (B, L, ...) batch leaf: batch over the data
    axes, sequence over the context axis (identity when cp == 1)."""
    daxes = data_axis_names(mesh)
    d_entry = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    caxes = context_axis_names(mesh)
    if not caxes:
        return PS(d_entry)
    return PS(d_entry, caxes[0])


def shard_pspec(mesh: Mesh) -> PS:
    """PartitionSpec sharding a leading per-shard axis over data x context
    combined — the layout of error-feedback buffers and any other
    per-replica state with one row per (data, context) coordinate."""
    axes = sync_axis_names(mesh)
    if not axes:
        return PS()
    return PS(axes if len(axes) > 1 else axes[0])


def validate_seq_divisible(seq_len: int, mesh: Mesh, *, bq: int | None = None,
                           where: str = "train step"):
    """Raise a clear config-time error when the sequence length cannot
    zigzag-shard over the context axis.

    The hard constraint is ``seq_len % (2 * cp) == 0`` — zigzag folds the
    sequence into ``2 * cp`` chunks (each device owns chunks ``i`` and
    ``2cp-1-i``). The kernel's bq/bk tiling pads internally, so chunk
    length need not be a bq multiple; when ``bq`` is given, lengths that
    also make chunks a bq multiple are suggested (zero intra-kernel
    padding), mirroring ``validate_batch_divisible``'s error shape."""
    cp = cp_degree(mesh)
    if cp <= 1:
        return
    fold = 2 * cp
    if seq_len % fold:
        lo = (seq_len // fold) * fold
        hi = lo + fold
        hint = ""
        if bq:
            step = fold * bq
            zlo = (seq_len // step) * step
            hint = (f" (for zero kernel padding, a multiple of cp*2*bq = "
                    f"{step}, e.g. {zlo or step} or {zlo + step})")
        raise ValueError(
            f"{where}: seq_len {seq_len} is not divisible by 2*cp = {fold} "
            f"(context axis {context_axis_names(mesh)} of degree {cp}; "
            f"zigzag sharding folds the sequence into {fold} chunks). "
            f"Nearest valid lengths: {lo or fold} or {hi}{hint}."
        )


def ring_context():
    """(axis_name, cp) when tracing inside a shard_map body that manually
    shards a context axis of degree > 1, else None — the dispatch point
    for ring context-parallel attention (models/attention.attn_train)."""
    sm = _shard_map_context()
    if sm is None:
        return None
    mesh, manual = sm
    if CONTEXT_AXIS not in manual:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    cp = sizes.get(CONTEXT_AXIS, 1)
    return (CONTEXT_AXIS, cp) if cp > 1 else None


def slot_shard_entry(mesh: Mesh):
    """PartitionSpec ENTRY (not a full spec) for a per-slot / per-replica
    axis sharded over the data axes — what serve/cache.shard_slots puts on
    axis 1 of layer-stacked serving leaves. None on a pure-TP mesh."""
    axes = data_axis_names(mesh)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def validate_batch_divisible(global_batch: int, mesh: Mesh, *,
                             grad_accum: int = 1, where: str = "train step"):
    """Raise a clear error when the global batch cannot shard over the data
    axes (the alternative is an opaque XLA "sharding does not evenly divide"
    failure deep inside device_put / jit)."""
    dp = dp_degree(mesh)
    axes = data_axis_names(mesh)
    if dp > 1 and global_batch % dp:
        raise ValueError(
            f"{where}: global batch {global_batch} is not divisible by the "
            f"data-parallel degree {dp} (mesh axes {axes} of shape "
            f"{tuple(mesh.devices.shape)}). Pick a global batch that is a "
            f"multiple of {dp}, or reshape the mesh."
        )
    accum = max(1, grad_accum)
    local = global_batch // max(1, dp)
    if accum > 1 and local % accum:
        raise ValueError(
            f"{where}: per-shard batch {local} (global {global_batch} / "
            f"dp {dp}) is not divisible by grad_accum={accum}."
        )


# ---------------------------------------------------------------------------
# shard_map tracing context: manual axes must not appear in constraints
# ---------------------------------------------------------------------------
_SM_CTX = threading.local()


@contextlib.contextmanager
def shard_map_ctx(mesh: Mesh, manual_axes: tuple):
    """Mark that model code is being traced inside a ``shard_map`` body whose
    ``manual_axes`` are manually sharded (the rest are GSPMD-auto).

    ``maybe_constrain`` then emits explicit NamedSharding constraints with
    the manual axes dropped from the logical rules — inside a partial-auto
    shard_map a constraint may only name auto axes, and the manual (data)
    axes are already physically split by the shard_map itself."""
    prev = getattr(_SM_CTX, "val", None)
    _SM_CTX.val = (mesh, frozenset(manual_axes))
    try:
        yield
    finally:
        _SM_CTX.val = prev


def _shard_map_context():
    return getattr(_SM_CTX, "val", None)


def current_mesh_axis_names() -> tuple[str, ...] | None:
    """Axis names of the mesh set by ``jax.set_mesh``, or None."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else tuple(am.axis_names)


def _rules_pspec(logical: tuple, axes: set) -> PS:
    spec = []
    for name in logical:
        rule = DEFAULT_RULES.get(name)
        if rule is None:
            spec.append(None)
        else:
            picked = tuple(a for a in rule if a in axes)
            spec.append(picked if len(picked) > 1 else (picked[0] if picked else None))
    return PS(*spec)


def maybe_constrain(x, logical: tuple):
    """with_sharding_constraint using whatever mesh is in context (no-op
    outside a mesh context — keeps model code mesh-agnostic for CPU tests).

    Inside a ``shard_map_ctx`` (the shard_map executor's body), the manual
    axes are dropped from the rules and an explicit NamedSharding for the
    remaining (auto/TP) axes is emitted against the body's abstract mesh,
    whose axis types mark the data axes manual — a sharding on the
    all-auto concrete mesh is refused inside a scan body."""
    sm = _shard_map_context()
    if sm is not None:
        mesh, manual = sm
        axes = set(mesh.axis_names) - manual
        if not axes:
            return x   # fully manual body: nothing left to constrain
        ps = _rules_pspec(logical, axes)
        # drop entries the dimension cannot divide (MQA kv heads, odd vocab)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        spec = list(tuple(ps) + (None,) * (x.ndim - len(tuple(ps))))
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([sizes[a] for a in names]))
            if x.shape[i] % total:
                spec[i] = None
        # An all-None spec is still meaningful here: it pins the value
        # REPLICATED over the auto (TP) axes — that is exactly the
        # block-boundary residual anchor — so it is emitted, not skipped.
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(jax.sharding.get_abstract_mesh(), PS(*spec)))
    names = current_mesh_axis_names()
    if names is None:
        return x
    return jax.lax.with_sharding_constraint(x, _rules_pspec(logical, set(names)))
