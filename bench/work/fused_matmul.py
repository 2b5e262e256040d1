"""Work of one fused packed matmul call, from its shapes.

(M, K) activation times a (K, N) HiF4 weight. Operations: 2*M*K*N.
Bytes, the least the algorithm moves: the packed weight at 0.5625 B/value
(4-bit codes and a 32-bit record per 64 values), the absorbed int8
activation with one f32 scale per 64 values (1.0625 B/value), and the
output at 2 B/value (bfloat16, the type the model keeps).
"""

WEIGHT_BYTES_PER_VALUE = 0.5625
ACTIVATION_BYTES_PER_VALUE = 1.0 + 4.0 / 64
OUTPUT_BYTES_PER_VALUE = 2.0


def cost(m: int, k: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    ops = 2.0 * m * k * n
    nbytes = (WEIGHT_BYTES_PER_VALUE * k * n + ACTIVATION_BYTES_PER_VALUE * m * k
              + OUTPUT_BYTES_PER_VALUE * m * n)
    return ops, nbytes


def layer_shapes(sizes) -> list[tuple[int, int]]:
    """(K, N) of the packed matmuls of one decoder layer, in call order."""
    q, kv = sizes.heads * sizes.d_head, sizes.kv_heads * sizes.d_head
    d, f = sizes.d, sizes.ff
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]
