"""Operations and bytes of ops/ssm.py:_ssm_step_kernel for ONE example in
ONE state-space layer of ONE decode step: the float32 state (state x
channels) read once and written once where it lies, the token's x in and
y out, dt a head, B and C. Three operations an element for the update
(decay, input, sum), two for the read-out. A row that pads the batch
needs nothing."""


def ops_and_bytes(*, heads: int, head_dim: int, state: int,
                  dtype_bytes: int = 2) -> tuple[float, float]:
    channels = heads * head_dim
    flops = 5.0 * state * channels
    moved = float(2 * 4 * state * channels                  # the state
                  + channels * (dtype_bytes + 4)            # x in, y out
                  + 4 * heads + 2 * state * dtype_bytes)    # dt, B, C
    return flops, moved
