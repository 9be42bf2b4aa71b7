"""FLOPs and HBM bytes of one call of ``paged_decode``: single-query
attention of each decoding slot over the keys and values of its context,
read from the page pool.

A call is described by its query shape and the context length of every
slot it decodes (``ctx``, a list). FLOPs: 4 H dh per context token (Q K^T
and P V). Bytes: the context's keys and values, the query and the output."""


def cost(ctx, H, KV, dh, Lq=1, itemsize=2):
    t = sum(ctx)
    flops = 4 * Lq * H * dh * t
    byts = itemsize * (2 * KV * dh * t + 2 * len(ctx) * Lq * H * dh) + 4 * t
    return flops, byts
