"""FLOPs and HBM bytes of one call of the Pallas kernel ``flash_fwd``:
causal (or full) attention forward, q (B, Lq, H, dh), k/v (B, Lk, KV, dh).

FLOPs: 2 products of dh per (query, key) pair the mask keeps (Q K^T and
P V), 2 FLOPs each. Bytes: q, k, v read once, the output written, and the
float32 log-sum-exp row written."""


def pairs(Lq: int, Lk: int, causal: bool) -> int:
    if not causal:
        return Lq * Lk
    # query i (aligned to the end of the keys) sees keys 0 .. Lk - Lq + i
    off = Lk - Lq
    return sum(min(Lk, off + i + 1) for i in range(Lq)) if off else Lq * (Lq + 1) // 2


def cost(B, Lq, Lk, H, KV, dh, causal=True, itemsize=2):
    flops = 4 * B * H * dh * pairs(Lq, Lk, causal)
    byts = itemsize * B * (2 * Lq * H * dh + 2 * Lk * KV * dh) + 4 * B * H * Lq
    return flops, byts
