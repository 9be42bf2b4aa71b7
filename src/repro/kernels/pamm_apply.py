"""Pallas TPU kernel: PAMM segment-sum as a one-hot MXU matmul.

Computes ``Btilde = E^T (alpha ⊙ dZ)`` where ``E = onehot(f) in {0,1}^{b,k}``
(paper Alg. 1 APPROXMM line 6, 'index_add'). Scatter-add is slow on TPU, so
the one-hot tile is materialized **in VMEM only** via an iota==f compare and
contracted on the MXU (DESIGN.md §3):

  grid = (m/bm_m, b/bm_b): step (jm, i) streams a (bm_b, bn_m) tile of dZ
  and (bm_b, 1) tiles of alpha/f; builds onehot (bm_b, k) in registers/VMEM;
  accumulates (k, bn_m) in f32 scratch; writes Btilde tile at the last i.

FLOP cost b*k*m equals the compress-side csim matmul — both are thin MXU
matmuls; the (b, k) one-hot never touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BB = 256
DEFAULT_BM = 512


def _kernel(f_ref, alpha_ref, gz_ref, out_ref, acc_ref, *, b_blocks: int, k: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    f = f_ref[...]                                # (bb, 1) int32
    alpha = alpha_ref[...].astype(jnp.float32)    # (bb, 1)
    gz = gz_ref[...].astype(jnp.float32)          # (bb, bm)
    onehot = (f == jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)).astype(jnp.float32)
    onehot = onehot * alpha                       # fold alpha into E
    acc_ref[...] += jax.lax.dot_general(
        onehot, gz, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (k, bm)

    @pl.when(i == b_blocks - 1)
    def _write():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "bb", "bm", "interpret"))
def segment_matmul(f, alpha, gz, k: int, *, bb: int = DEFAULT_BB,
                   bm: int = DEFAULT_BM, interpret: bool = True):
    """f (b,) int32, alpha (b,), gz (b, m) -> Btilde (k, m) f32.

    Padded rows get alpha = 0 so they contribute nothing.
    """
    b, m = gz.shape
    bb = min(bb, max(8, b))
    bm = min(bm, m)
    pb = (-b) % bb
    pm = (-m) % bm
    pk = (-k) % 128
    fp = jnp.pad(f.astype(jnp.int32), (0, pb))[:, None]
    ap = jnp.pad(alpha.astype(jnp.float32), (0, pb))[:, None]
    gzp = jnp.pad(gz, ((0, pb), (0, pm)))
    K = k + pk
    b_blocks = (b + pb) // bb
    grid = ((m + pm) // bm, b_blocks)

    out = pl.pallas_call(
        functools.partial(_kernel, b_blocks=b_blocks, k=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda jm, i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda jm, i: (i, 0)),
            pl.BlockSpec((bb, bm), lambda jm, i: (i, jm)),
        ],
        out_specs=pl.BlockSpec((K, bm), lambda jm, i: (0, jm)),
        out_shape=jax.ShapeDtypeStruct((K, m + pm), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, bm), jnp.float32)],
        interpret=interpret, name="pamm_apply",
    )(fp, ap, gzp)
    return out[:k, :m]
