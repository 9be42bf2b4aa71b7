"""Pallas TPU kernel: fused PAMM compress core (paper Alg. 1, lines 6-11).

Computes, for each row X_i of a (b, n) activation block, the signed cosine
similarity to its best generator (argmax_j |csim(X_i, C_j)|), the generator
index, and ||X_i|| — in ONE pass over X:

  grid = (b/bm, n/bn); each (i, j) step streams an (bm, bn) tile of X and a
  (k, bn) tile of C HBM->VMEM, accumulates partial dot products (bm, k) and
  squared norms (bm, 1) in f32 VMEM scratch (MXU for the dots), and on the
  last n-tile runs the |csim| arg-max on the VPU (masked lane reductions)
  and writes (cs, idx, norm).

TPU adaptation vs the paper's CUDA version (DESIGN.md §3): the csim matmul
lands on the MXU systolic array; the argmax is a lane reduction (the paper
uses a CUDA tree-reduction kernel); tiles are (8,128)-aligned.

Alpha/eps/beta are cheap O(b) epilogues done in the jit wrapper (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 512


def _kernel(x_ref, c_ref, invnc_ref, cs_ref, idx_ref, norm_ref,
            acc_ref, sq_ref, *, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bn)
    c = c_ref[...].astype(jnp.float32)          # (k, bn)
    acc_ref[...] += jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (bm, k) partial <X_i, C_j>
    sq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)

    @pl.when(j == n_blocks - 1)
    def _epilogue():
        norm_a = jnp.sqrt(sq_ref[...])           # (bm, 1)
        inv_na = 1.0 / jnp.maximum(norm_a, 1e-20)
        csim = acc_ref[...] * inv_na * invnc_ref[...]  # (bm, k)
        # argmax and the winner's value as masked lane reductions: Mosaic
        # does not lower an in-kernel gather (take_along_axis). Ties go to
        # the lowest index, as jnp.argmax does.
        mag = jnp.abs(csim)
        lane = jax.lax.broadcasted_iota(jnp.int32, csim.shape, 1)
        top = jnp.max(mag, axis=1, keepdims=True)
        best = jnp.min(jnp.where(mag == top, lane, csim.shape[1]), axis=1,
                       keepdims=True)                  # (bm, 1)
        cs_ref[...] = jnp.sum(jnp.where(lane == best, csim, 0.0), axis=1,
                              keepdims=True)
        idx_ref[...] = best
        norm_ref[...] = norm_a


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def csim_argmax(x, c, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                interpret: bool = True):
    """x: (b, n), c: (k, n) -> (cs (b,), idx (b,) int32, norm_a (b,)).

    b, n, k are padded to tile multiples internally; inv-norms of padded
    generators are zeroed so padding can never win the argmax.
    """
    b, n = x.shape
    k = c.shape[0]
    bm = min(bm, max(8, b))
    bn = min(bn, n)
    pb = (-b) % bm
    pn = (-n) % bn
    pk = (-k) % 128
    xp = jnp.pad(x, ((0, pb), (0, pn)))
    cp = jnp.pad(c, ((0, pk), (0, pn)))
    norm_c = jnp.linalg.norm(cp.astype(jnp.float32), axis=1)
    invnc = jnp.where(norm_c > 0, 1.0 / jnp.maximum(norm_c, 1e-20), 0.0)[None, :]

    B, N, K = b + pb, n + pn, k + pk
    n_blocks = N // bn
    grid = (B // bm, n_blocks)

    cs, idx, norm = pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, K), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
        interpret=interpret, name="pamm_compress",
    )(xp, cp, invnc)
    return cs[:b, 0], jnp.minimum(idx[:b, 0], k - 1), norm[:b, 0]
