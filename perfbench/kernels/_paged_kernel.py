"""Operations and bytes of one call of ops/attention.py:_paged_kernel:
decode attention of `q_rows` query rows per session over the KV pages
the sessions hold. What the algorithm needs is the pages that hold
tokens, not the slots times table width the program may iterate: a
kernel that walks empty table entries takes longer for the same need."""


def ops_and_bytes(*, pages: float, heads: int, page_tokens: int,
                  d_head: int, q_rows: int = 1,
                  dtype_bytes: int = 2) -> tuple[float, float]:
    """`pages` is the number of pages read in the call, over all
    sessions. A page holds K and V of `page_tokens` tokens for every
    head; each query row does QK^T and PV over them."""
    elements = pages * heads * page_tokens * d_head
    flops = 4.0 * q_rows * elements
    bytes_moved = 2.0 * elements * dtype_bytes
    return flops, bytes_moved
