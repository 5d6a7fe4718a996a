"""Operations and bytes of ops/attention.py:_flash_kernel for ONE example
in ONE layer of a causal self-attention prefill: what the algorithm
needs, not what the program iterates. Only unmasked (query, key) pairs of
the example's unpadded tokens count (causal, and inside the window where
the layer has one); K and V are read once a K/V head however many query
heads share it; padding rows and padded batch rows need nothing."""


def pairs(length: int, window: int | None = None) -> int:
    """Unmasked (query, key) pairs of `length` tokens: query i sees keys
    max(0, i - window + 1) .. i."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def ops_and_bytes(*, length: int, heads: int, kv_heads: int, d_qk: int,
                  d_v: int, window: int | None = None,
                  dtype_bytes: int = 2) -> tuple[float, float]:
    """QK^T and PV over the unmasked pairs of every query head; q read,
    o written, K and V read once a K/V head."""
    flops = 2.0 * pairs(length, window) * (d_qk + d_v) * heads
    moved = float(dtype_bytes * length * (
        heads * (d_qk + d_v) + kv_heads * (d_qk + d_v)))
    return flops, moved
