"""FLOPs and HBM bytes of one call of ``pamm_apply``: the segment sum
Btilde_j = sum over rows i with f(i) = j of alpha_i dZ_i, for b rows of width
m onto k generators.

FLOPs: 2 b m (a scaled add per element). Bytes: dZ read, alpha and the
index read, Btilde (float32) written."""


def cost(b, m, k, itemsize=2):
    return 2 * b * m, itemsize * b * m + 8 * b + 4 * k * m
