"""The Pallas kernels of the main paths compile for a TPU v5e.

Interpret mode (every other kernel test) runs a kernel body as plain JAX
and accepts layouts the chip's compiler refuses: blocks that break the
(8, 128) tiling rule, in-kernel gathers, interleaving shape casts. Here
each kernel is compiled, not run, for one chip of a ``v5e:2x2`` topology
described without a chip, at internlm2-1.8b widths (16 heads, 8 KV heads,
head_dim 128, d_model 2048; arXiv:2403.17297).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KV, DH, D = 16, 8, 128, 2048
SEQ, BATCH = 1024, 4
QKV_OUT = (H + 2 * KV) * DH
PAMM_K = BATCH * SEQ // 512          # attn.qkv=pamm(r=1/512)
SLOTS, PAGE, N_PAGES = 8, 16, 288
BLOCKS = (512 + 32) // PAGE + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _flash_fwd():
    from repro.kernels.flash_attention import flash_attention_fwd

    bf = jnp.bfloat16
    return (lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
            [((1, SEQ, H, DH), bf), ((1, SEQ, KV, DH), bf),
             ((1, SEQ, KV, DH), bf)])


def _flash_bwd():
    from repro.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False))

    f32 = jnp.float32
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((1, SEQ, H, DH), f32), ((1, SEQ, KV, DH), f32),
             ((1, SEQ, KV, DH), f32)])


def _pamm_compress():
    from repro.kernels.pamm_compress import csim_argmax

    return (lambda x, c: csim_argmax(x, c, interpret=False),
            [((BATCH * SEQ, D), jnp.float32), ((PAMM_K, D), jnp.float32)])


def _pamm_apply():
    from repro.kernels.pamm_apply import segment_matmul

    return (lambda f, a, g: segment_matmul(f, a, g, PAMM_K, interpret=False),
            [((BATCH * SEQ,), jnp.int32), ((BATCH * SEQ,), jnp.float32),
             ((BATCH * SEQ, QKV_OUT), jnp.float32)])


def _paged(lq: int):
    from repro.kernels.flash_decode import flash_paged_decode_kernel

    bf = jnp.bfloat16
    return (lambda q, k, v, qp, bt, pp: flash_paged_decode_kernel(
                q, k, v, qp, bt, pp, interpret=False),
            [((SLOTS, lq, H, DH), bf), ((N_PAGES, PAGE, KV, DH), bf),
             ((N_PAGES, PAGE, KV, DH), bf), ((SLOTS, lq), jnp.int32),
             ((SLOTS, BLOCKS), jnp.int32), ((N_PAGES, PAGE), jnp.int32)])


def _paged_quant(bits: int, ngr: int):
    from repro.kernels.flash_decode import flash_paged_decode_quant_kernel

    dq = DH if bits == 8 else DH // 2
    return (lambda q, k, v, ks, vs, qp, bt, pp:
            flash_paged_decode_quant_kernel(q, k, v, ks, vs, qp, bt, pp,
                                            interpret=False),
            [((SLOTS, 1, H, DH), jnp.bfloat16),
             ((N_PAGES, PAGE, KV, dq), jnp.int8),
             ((N_PAGES, PAGE, KV, dq), jnp.int8),
             ((N_PAGES, PAGE, KV, ngr), jnp.float32),
             ((N_PAGES, PAGE, KV, ngr), jnp.float32),
             ((SLOTS,), jnp.int32), ((SLOTS, BLOCKS), jnp.int32),
             ((N_PAGES, PAGE), jnp.int32)])


def _dense_decode():
    from repro.kernels.flash_decode import flash_decode_kernel

    bf = jnp.bfloat16
    S = PAGE * BLOCKS
    return (lambda q, k, v, qp, sp: flash_decode_kernel(
                q, k, v, qp, sp, interpret=False),
            [((SLOTS, 1, H, DH), bf), ((SLOTS, S, KV, DH), bf),
             ((SLOTS, S, KV, DH), bf), ((SLOTS,), jnp.int32),
             ((SLOTS, S), jnp.int32)])


CASES = {
    # case: (kernel name the program must hold, builder)
    "flash_fwd": ("flash_fwd", _flash_fwd),
    "flash_dq": ("flash_dq", _flash_bwd),
    "flash_dkv": ("flash_dkv", _flash_bwd),
    "pamm_compress": ("pamm_compress", _pamm_compress),
    "pamm_apply": ("pamm_apply", _pamm_apply),
    "paged_decode_fp": ("paged_decode", lambda: _paged(1)),
    "paged_decode_verify4": ("paged_decode", lambda: _paged(4)),
    "paged_decode_int8": ("paged_decode_quant", lambda: _paged_quant(8, 1)),
    "paged_decode_int4_group64": ("paged_decode_quant",
                                  lambda: _paged_quant(4, 2)),
    "dense_decode": ("dense_decode", _dense_decode),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    kernel, build = CASES[case]
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert kernel in re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
    compiled = lowered.compile()   # raises what the chip's compiler refuses
    assert "tpu_custom_call" in compiled.as_text()
