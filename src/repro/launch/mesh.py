"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Production target: TPU v5e pods. Single pod = 16x16 = 256 chips
("data", "model"); two pods = (2, 16, 16) ("pod", "data", "model"). The
"pod" axis carries only data parallelism across the DCN/ICI boundary —
gradients cross it once per step; everything bandwidth-hungry (TP/EP/SP
collectives) stays inside the "model" axis of one pod.
"""
from __future__ import annotations

import jax

V5E_PEAK_FLOPS = 197e12       # bf16 per chip
V5E_HBM_BW = 819e9            # bytes/s per chip
V5E_ICI_BW = 50e9             # bytes/s per link


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto: sharding stays GSPMD-propagated
    from the rules, not part of the array types (Explicit is the default)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1, context: int = 1):
    """Tiny mesh over however many devices exist (tests/benches: 1 CPU).

    ``context > 1`` appends a context-parallel (ring attention) axis; the
    two-axis shape is preserved otherwise so existing call sites and their
    compiled artifacts are untouched."""
    if context > 1:
        return _make_mesh((data, model, context), ("data", "model", "context"))
    return _make_mesh((data, model), ("data", "model"))
