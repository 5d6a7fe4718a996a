"""Operations and bytes of ops/ssm.py:_ssd_kernel for ONE example in ONE
state-space layer of a prefill: what the chunked scan (SSD) needs, not
what the program iterates. Only the example's unpadded tokens count, a
chunk at a time: the unmasked (row, earlier row) pairs of a chunk through
C B^T once (B and C are shared by the heads) and through every head's
decay-weighted product, the carried state into every row after the first
chunk, every row into the state's update. x is read and y written once,
B, C, dt and the cumulative log-decay read once, the state written once;
padding rows, padded batch rows and the chunks past the example's last
need nothing."""


def chunk_rows(length: int, chunk: int) -> list[int]:
    """Real rows of each chunk the example fills."""
    return [min(chunk, length - lo) for lo in range(0, length, chunk)]


def ops_and_bytes(*, length: int, heads: int, head_dim: int, state: int,
                  chunk: int, dtype_bytes: int = 2) -> tuple[float, float]:
    channels = heads * head_dim
    flops = 0.0
    for index, rows in enumerate(chunk_rows(length, chunk)):
        pairs = rows * (rows + 1) // 2
        flops += 2.0 * pairs * (state + channels)       # C B^T, M x
        flops += 2.0 * rows * state * channels          # rows into the state
        if index:
            flops += 2.0 * rows * state * channels      # the state into rows
    if not length:
        return 0.0, 0.0
    moved = float(length * (2 * channels * dtype_bytes      # x in, y out
                            + 2 * state * dtype_bytes       # B, C
                            + 2 * heads * 4)                # dt, cumulative
                  + 4 * state * channels)                   # the state out
    return flops, moved
