"""FLOPs and HBM bytes of one call of ``pamm_compress``: for b rows of width
n, the dot products with k generators and the row norms.

FLOPs: 2 b k n (dots) + 2 b n (squared norms). Bytes: the rows and the
generators read, and per row the float32 similarity, the int32 index and
the float32 norm written."""


def cost(b, n, k, itemsize=2):
    return 2 * b * k * n + 2 * b * n, itemsize * (b * n + k * n) + 12 * b
