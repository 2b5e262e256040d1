"""Work of paged decode attention over the HiF4 KV cache, from shapes.

One query token of one sequence against ``length`` cached tokens, in one
layer. Operations: 4*H*length*Dh (scores and the weighted sum of values).
Bytes, the least the algorithm moves: the packed K and V of the ``length``
valid tokens at 0.5625 B/value, the bf16 query in and the bf16 output out.
Empty slots, masked pages and the unused part of a page are not work.
"""

KV_BYTES_PER_VALUE = 0.5625


def kv_bytes(sizes, length: int) -> float:
    """Packed K and V of ``length`` tokens in one layer."""
    return 2 * KV_BYTES_PER_VALUE * length * sizes.kv_heads * sizes.d_head


def cost(sizes, length: int) -> tuple[float, float]:
    """(operations, bytes) of one sequence's decode attention in one layer."""
    h, dh = sizes.heads, sizes.d_head
    ops = 4.0 * h * length * dh
    return ops, kv_bytes(sizes, length) + 2 * 2.0 * h * dh


def request_cost(sizes, prompt_len: int, new_tokens: int) -> tuple[float, float]:
    """Summed over every layer and every decode step of one request: the
    step that reads position p attends over p + 1 tokens (its own included);
    the first token comes from prefill, so new_tokens - 1 steps."""
    ops = nbytes = 0.0
    for p in range(prompt_len, prompt_len + new_tokens - 1):
        o, b = cost(sizes, p + 1)
        ops += o
        nbytes += b
    return ops * sizes.layers, nbytes * sizes.layers
