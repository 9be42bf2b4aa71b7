"""FLOPs and HBM bytes of one call of ``flash_dq``, the query-gradient half
of the flash backward: per kept (query, key) pair it recomputes Q K^T, forms
dO V^T and accumulates dS K, three products of dh.

Bytes: q, k, v, dO read, dq written, and the float32 log-sum-exp and delta
rows read."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "flash_fwd_cost", pathlib.Path(__file__).with_name("flash_fwd.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def cost(B, Lq, Lk, H, KV, dh, causal=True, itemsize=2):
    flops = 6 * B * H * dh * _fwd.pairs(Lq, Lk, causal)
    byts = itemsize * B * (3 * Lq * H * dh + 2 * Lk * KV * dh) + 8 * B * H * Lq
    return flops, byts
