"""Operations and bytes of ops/kda.py:_kda_step_kernel for ONE example in
ONE KDA layer of ONE decode step: each head's float32 state (d_k x d_v)
read once and written once where it lies, the token's five float32 rows a
head in (the decay, k, q, v, beta on every lane) and o out. Seven
operations an element of the state: one for the decay, two each for what
the state says of k, for the correction and for the read-out. A row that
pads the batch needs nothing."""


def ops_and_bytes(*, heads: int, head_dim: int) -> tuple[float, float]:
    state = heads * head_dim * head_dim
    flops = 7.0 * state
    moved = float(2 * 4 * state                      # the state
                  + 4 * heads * head_dim * (5 + 1))  # five rows in, o out
    return flops, moved
