"""State-space (Mamba-2) sequence mixing: the chunked scan a prefill runs
and the one-token step a decode loop runs.

The recurrence, a head h of P channels and a state of N columns a
channel (one group: B and C are shared by every head):

    H_t = exp(dt_t[h] A[h]) H_{t-1} + dt_t[h] x_t (x) B_t
    y_t = H_t C_t + D[h] x_t

Layouts, the same in every form: x and y (B, S, H * P), channel h * P + p
of head h; dt (B, S, H) float32, already softplus'd; A and D (H,); B and
C (B, S, N); the state (B, N, H * P) float32 (a state's column on the
sublanes, the channels on the lanes: the per-channel decay and input of a
step are rows, and a head's channels are a lane slice). Positions at or
past an example's length take dt = 0: the state passes them unchanged,
so the state handed on is the state after the last real token.

Three forms of the scan, one arithmetic:
 * `ssd_reference`  the recurrence token by token (a `lax.scan` over
   time), all float32: the definition, and the tests' yardstick;
 * `ssd_chunked`    plain jnp over chunks of `chunk` rows (SSD): within
   a chunk the masked, decay-weighted (C B^T) product, across chunks the
   carried state; what the CPU and other platforms run;
 * `ssd_scan`       the same as the Pallas kernel `_ssd_kernel`: a grid
   of (example, head group, chunk), the chunks of an example in order
   with its state resident, the chunks past its length skipped.
and two of the step: `ssm_step_reference` (jnp) and `ssm_step_kernel`
(Pallas, `_ssm_step_kernel`, the state updated in place). `ssd` and
`ssm_step` dispatch behind the same gate as ops/attention.py.

Precision: dt, the cumulative log-decays, every exp, the state and its
update in float32; the operands of the matrix products in x's dtype
(bfloat16 as served) with float32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MASKED = -1e30   # a log-decay no pair above the diagonal survives
_SSD_VMEM_BYTES = 48 << 20
_STEP_CHANNELS = 2048   # channels of a state a step's grid cell takes


def _masked_dt(dt: jax.Array, lengths: jax.Array | None) -> jax.Array:
    dt = dt.astype(jnp.float32)
    if lengths is None:
        return dt
    real = jnp.arange(dt.shape[1])[None, :] < lengths[:, None]
    return jnp.where(real[..., None], dt, 0.0)


def _per_channel(per_head: jax.Array, channels: int) -> jax.Array:
    """(..., H) -> (..., H * P): a head's figure on each of its channels."""
    return jnp.repeat(per_head, channels // per_head.shape[-1], axis=-1)


# -- the definition ----------------------------------------------------------


def ssd_reference(x, dt, a, bm, cm, d, lengths=None):
    """Token by token. -> (y (B, S, H * P) float32, state (B, N, H * P))."""
    b, s, ch = x.shape
    n = bm.shape[-1]
    dt = _masked_dt(dt, lengths)
    xf = x.astype(jnp.float32)
    decay = _per_channel(jnp.exp(dt * a.astype(jnp.float32)), ch)
    dtx = _per_channel(dt, ch) * xf

    def one(h, at):
        decay_t, dtx_t, b_t, c_t = at
        h = (decay_t[:, None, :] * h
             + b_t.astype(jnp.float32)[:, :, None] * dtx_t[:, None, :])
        return h, jnp.sum(h * c_t.astype(jnp.float32)[:, :, None], axis=1)

    time_major = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    state, y = jax.lax.scan(
        one, jnp.zeros((b, n, ch), jnp.float32),
        (time_major(decay), time_major(dtx), time_major(bm), time_major(cm)))
    return (time_major(y) + _per_channel(d.astype(jnp.float32), ch) * xf,
            state)


# -- chunked, plain jnp ------------------------------------------------------


def ssd_chunked(x, dt, a, bm, cm, d, lengths=None, *, chunk: int = 256):
    """SSD in plain jnp, a chunk at a time with the state carried; only
    the chunks up to the batch's longest example run. -> (y (B, S, H * P)
    in x's dtype, state (B, N, H * P) float32, rows the scan ran for each
    example (B,))."""
    b, s, ch = x.shape
    heads, n = dt.shape[-1], bm.shape[-1]
    p = ch // heads
    q = min(chunk, s)
    pad = (-s) % q
    dt = _masked_dt(dt, lengths)
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (x, dt, bm, cm))
    longest = s if lengths is None else jnp.max(lengths, initial=0)
    run = (longest + q - 1) // q
    a = a.astype(jnp.float32)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def one(i, carry):
        y, h = carry
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, i * q, q, 1)  # noqa: E731
        xc, dtc, bc, cc = cut(x), cut(dt), cut(bm), cut(cm)
        cs = jnp.cumsum(dtc * a, axis=1)                       # (B, Q, H)
        g = jnp.einsum("bqn,bkn->bqk", cc, bc,
                       preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            cs[:, :, None, :] - cs[:, None, :, :], _MASKED))   # (B, Q, K, H)
        m = (g[..., None] * decay * dtc[:, None, :, :]).astype(x.dtype)
        xh = xc.reshape(b, q, heads, p)
        within = jnp.einsum("bqkh,bkhp->bqhp", m, xh,
                            preferred_element_type=jnp.float32)
        carried = jnp.einsum("bqn,bnc->bqc", cc, h.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        yc = (within.reshape(b, q, ch)
              + _per_channel(jnp.exp(cs), ch) * carried
              + _per_channel(d.astype(jnp.float32), ch)
              * xc.astype(jnp.float32))
        last = cs[:, -1:, :]
        weighted = (xc.astype(jnp.float32)
                    * _per_channel(dtc * jnp.exp(last - cs), ch))
        h = (_per_channel(jnp.exp(last), ch) * h
             + jnp.einsum("bkn,bkc->bnc", bc, weighted.astype(x.dtype),
                          preferred_element_type=jnp.float32))
        return (jax.lax.dynamic_update_slice_in_dim(
            y, yc.astype(x.dtype), i * q, 1), h)

    y, h = jax.lax.fori_loop(
        0, run, one, (jnp.zeros(x.shape, x.dtype),
                      jnp.zeros((b, n, ch), jnp.float32)))
    return y[:, :s], h, jnp.full((b,), run * q, jnp.int32)


# -- chunked, the Pallas kernel ----------------------------------------------


def _ssd_kernel(len_ref, x_ref, b_ref, c_ref, cs_col_ref, dt_col_ref,
                cs_row_ref, dt_row_ref, d_ref, y_ref, h_ref, *, chunk: int,
                head_dim: int, group_heads: int):
    """One (example, head group, chunk) grid cell; the chunks of an
    example run in order and `h_ref`, the group's state (N, channels of
    the group), stays resident across them.

    Refs: len_ref (B,) SMEM; x (Q, C) the group's channels; B and C (Q,
    N); the within-chunk cumulative log-decay and dt twice, positions on
    the sublanes with every head on the lanes (Q, H) and the group's
    heads on the sublanes with positions on the lanes (G, Q), because a
    head's decay matrix needs its figures both as a column and as a row;
    D a channel (1, C); y (Q, C). A lane tile of 128 channels holds
    128 / P heads: each head's (Q, Q) decay-weighted C B^T multiplies the
    whole tile (the MXU is as wide) and keeps its own lanes; the carried
    state's part and the state's update are one product a tile."""
    example, group, index = (pl.program_id(0), pl.program_id(1),
                             pl.program_id(2))
    length = len_ref[example]

    @pl.when(index == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(index * chunk < length)
    def _():
        bm, cm = b_ref[...], c_ref[...]
        dtype = x_ref.dtype
        g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        causal = (jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, g.shape, 1))
        cs_cols, dt_cols = cs_col_ref[...], dt_col_ref[...]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, cs_cols.shape, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        per_tile = _LANES // head_dim

        def column(cols, head):
            return jnp.sum(jnp.where(head_lane == head, cols, 0.0), axis=1,
                           keepdims=True)                          # (Q, 1)

        def tile(t, _):
            at = pl.ds(pl.multiple_of(t * _LANES, _LANES), _LANES)
            xt = x_ref[:, at]                                      # (Q, 128)
            y = jnp.zeros(xt.shape, jnp.float32)
            grown = jnp.zeros(xt.shape, jnp.float32)   # exp(cs) a row
            weight = jnp.zeros(xt.shape, jnp.float32)  # dt exp(last - cs)
            kept = jnp.zeros((1, _LANES), jnp.float32)  # exp(last)
            for j in range(per_tile):
                local = t * per_tile + j
                head = group * group_heads + local
                cs_col, dt_col = column(cs_cols, head), column(dt_cols, head)
                cs_row = cs_row_ref[pl.ds(local, 1), :]            # (1, Q)
                dt_row = dt_row_ref[pl.ds(local, 1), :]
                decay = jnp.exp(jnp.where(causal, cs_col - cs_row, _MASKED))
                m = (g * decay * dt_row).astype(dtype)
                mine = lane // head_dim == j
                y = jnp.where(mine, jnp.dot(
                    m, xt, preferred_element_type=jnp.float32), y)
                last = cs_col[chunk - 1:chunk, :]                  # (1, 1)
                grown = jnp.where(mine, jnp.exp(cs_col), grown)
                weight = jnp.where(mine, dt_col * jnp.exp(last - cs_col),
                                   weight)
                kept = jnp.where(mine, jnp.exp(last), kept)
            h = h_ref[:, at]                                       # (N, 128)
            y = y + grown * jnp.dot(cm, h.astype(dtype),
                                    preferred_element_type=jnp.float32)
            xf = xt.astype(jnp.float32)
            y_ref[:, at] = (y + d_ref[:, at] * xf).astype(y_ref.dtype)
            h_ref[:, at] = kept * h + jax.lax.dot_general(
                bm, (xf * weight).astype(dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, group_heads * head_dim // _LANES, tile, 0)


def _ssd_group_heads(heads: int, head_dim: int) -> int:
    """Heads a grid cell takes: a quarter of them where that keeps the
    group's channels on whole lane tiles and its heads on whole sublane
    tiles, else all (a block that spans its array's dim is always
    allowed)."""
    quarter = heads // 4
    if quarter % 8 == 0 and quarter * head_dim % _LANES == 0:
        return quarter
    return heads


def _ssd_kernel_applies(x: jax.Array, dt: jax.Array, chunk: int) -> bool:
    """The shapes `_ssd_kernel` is written for: a head's channels divide a
    lane tile, the channels fill whole tiles, a chunk is whole lane
    tiles of positions."""
    ch, heads = x.shape[-1], dt.shape[-1]
    p = ch // heads
    return _LANES % p == 0 and ch % _LANES == 0 and chunk % _LANES == 0


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bm, cm, d, lengths=None, *, chunk: int = 256,
             interpret: bool = False):
    """`ssd_chunked` as the Pallas kernel: every example runs its own
    chunks and no more. Same results, same return."""
    b, s, ch = x.shape
    heads, n = dt.shape[-1], bm.shape[-1]
    p = ch // heads
    pad = (-s) % chunk
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    dt = _masked_dt(dt, lengths)
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (x, dt, bm, cm))
    sp = s + pad
    chunks = sp // chunk
    cs = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(
        b, chunks, chunk, heads), axis=2).reshape(b, sp, heads)
    group = _ssd_group_heads(heads, p)
    width = group * p

    def chunk_of(e, i, len_ref):
        # a chunk past the example's last names the last: nothing moves
        return jnp.minimum(i, jnp.maximum(
            (len_ref[e] + chunk - 1) // chunk - 1, 0))

    rows = lambda e, g, i, len_ref: (e, chunk_of(e, i, len_ref), g)  # noqa: E731
    shared = lambda e, g, i, len_ref: (e, chunk_of(e, i, len_ref), 0)  # noqa: E731
    by_head = lambda e, g, i, len_ref: (e, g, chunk_of(e, i, len_ref))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, heads // group, chunks),
        in_specs=[
            pl.BlockSpec((None, chunk, width), rows),
            pl.BlockSpec((None, chunk, n), shared),
            pl.BlockSpec((None, chunk, n), shared),
            pl.BlockSpec((None, chunk, heads), shared),
            pl.BlockSpec((None, chunk, heads), shared),
            pl.BlockSpec((None, group, chunk), by_head),
            pl.BlockSpec((None, group, chunk), by_head),
            pl.BlockSpec((1, width), lambda e, g, i, len_ref: (0, g)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, width), rows),
            pl.BlockSpec((None, n, width),
                         lambda e, g, i, len_ref: (e, 0, g)),
        ],
    )
    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, head_dim=p,
                          group_heads=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, sp, ch), x.dtype),
                   jax.ShapeDtypeStruct((b, n, ch), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SSD_VMEM_BYTES),
        interpret=interpret,
        name="_ssd_kernel",  # the device-trace reduction finds it by name
    )(lengths, x, bm, cm, cs, dt, jnp.swapaxes(cs, 1, 2),
      jnp.swapaxes(dt, 1, 2),
      _per_channel(d.astype(jnp.float32), ch)[None, :])
    return y[:, :s], h, (lengths + chunk - 1) // chunk * chunk


# -- one token ---------------------------------------------------------------


def _step_operands(x, dt, a, d):
    """The per-channel rows of a step: the decay, dt x and D x, float32."""
    ch = x.shape[-1]
    dt = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    return (_per_channel(jnp.exp(dt * a.astype(jnp.float32)), ch),
            _per_channel(dt, ch) * xf,
            _per_channel(d.astype(jnp.float32), ch) * xf)


def ssm_step_reference(state, x, dt, a, bm, cm, d, owned=None):
    """One token: state (B, N, H * P) float32, x (B, H * P), dt (B, H),
    B and C (B, N); `owned` (B,) bool, None for every row: a row that is
    not owned keeps its state and gives y = 0.
    -> (state', y (B, H * P) float32)."""
    decay, dtx, dx = _step_operands(x, dt, a, d)
    moved = (decay[:, None, :] * state
             + bm.astype(jnp.float32)[:, :, None] * dtx[:, None, :])
    y = jnp.sum(moved * cm.astype(jnp.float32)[:, :, None], axis=1) + dx
    if owned is None:
        return moved, y
    return (jnp.where(owned[:, None, None], moved, state),
            jnp.where(owned[:, None], y, 0.0))


def _ssm_step_kernel(row_ref, count_ref, h_ref, rows_ref, b_ref, c_ref,
                     ho_ref, y_ref):
    """One (owned row, channel block) grid cell: h (N, C) read, updated
    and written where it lies; rows (8, C): the decay, dt x and D x on
    the first three; B and C as columns (N, 1); y (1, C). row_ref (B,)
    and count_ref (1,) in SMEM: the owned rows' indices first, and how
    many they are. A cell past the count names the last real cell's
    blocks (`ssm_step_kernel`): nothing was fetched for it, it does
    nothing, and nothing is written after it."""
    count = count_ref[0]

    @pl.when(pl.program_id(0) < count)
    def _():
        h = (rows_ref[0:1, :] * h_ref[...] + b_ref[...] * rows_ref[1:2, :])
        ho_ref[...] = h
        y_ref[...] = (jnp.sum(h * c_ref[...], axis=0, keepdims=True)
                      + rows_ref[2:3, :])

    # no row owned: every cell names ONE block, which the pipeline still
    # fetches and writes back: it goes back as it came
    @pl.when(count == 0)
    def _():
        ho_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step_kernel(state, x, dt, a, bm, cm, d, owned=None, *,
                    interpret: bool = False):
    """`ssm_step_reference` as the Pallas kernel, the state written in
    place (the input's buffer is the output's). The grid walks the OWNED
    rows, compacted to the front of a prefetched list; the cells behind
    them name the block the last real cell named, so the pipeline moves
    nothing for them: a row that is not owned is neither fetched nor
    written, and keeps its bytes."""
    b, n, ch = state.shape
    block = min(_STEP_CHANNELS, ch)
    blocks = ch // block
    if owned is None:
        owned = jnp.ones((b,), jnp.bool_)
    row_of = jnp.argsort(jnp.logical_not(owned), stable=True).astype(
        jnp.int32)
    count = jnp.sum(owned, dtype=jnp.int32)[None]
    decay, dtx, dx = _step_operands(x, dt, a, d)
    rows = jnp.stack([decay, dtx, dx], axis=1)
    rows = jnp.pad(rows, ((0, 0), (0, 8 - rows.shape[1]), (0, 0)))
    column = lambda v: v.astype(jnp.float32)[:, :, None]  # noqa: E731

    def row(i, row_ref, count_ref):
        return row_ref[jnp.maximum(jnp.minimum(i, count_ref[0] - 1), 0)]

    def by_channels(i, c, row_ref, count_ref):
        return (row(i, row_ref, count_ref), 0,
                jnp.where(i < count_ref[0], c, blocks - 1))

    def whole(i, c, row_ref, count_ref):
        return (row(i, row_ref, count_ref), 0, 0)

    state, y = pl.pallas_call(
        _ssm_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, blocks),
            in_specs=[
                pl.BlockSpec((None, n, block), by_channels),
                pl.BlockSpec((None, 8, block), by_channels),
                pl.BlockSpec((None, n, 1), whole),
                pl.BlockSpec((None, n, 1), whole),
            ],
            out_specs=[
                pl.BlockSpec((None, n, block), by_channels),
                pl.BlockSpec((None, 1, block), by_channels),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, ch), jnp.float32)],
        # operand 2: the two prefetched scalars come first
        input_output_aliases={2: 0},
        # a cell past the count revisits a block: no dimension is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="_ssm_step_kernel",  # the device-trace reduction finds it
    )(row_of, count, state, rows, column(bm), column(cm))
    # a row no cell wrote holds whatever the buffer held
    return state, jnp.where(owned[:, None], y[:, 0, :], 0.0)


def _step_kernel_applies(state: jax.Array) -> bool:
    n, ch = state.shape[1:]
    return (n % 8 == 0 and ch % _LANES == 0
            and ch % min(_STEP_CHANNELS, ch) == 0)


# -- dispatch ----------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssd(x, dt, a, bm, cm, d, lengths=None, *, chunk: int = 256):
    """The chunked scan: the Pallas kernel on the TPU for the shapes it is
    written for, plain jnp otherwise. -> (y, state, rows the scan ran for
    each example)."""
    if _on_tpu() and _ssd_kernel_applies(x, dt, chunk):
        return ssd_scan(x, dt, a, bm, cm, d, lengths, chunk=chunk)
    return ssd_chunked(x, dt, a, bm, cm, d, lengths, chunk=chunk)


def ssm_step(state, x, dt, a, bm, cm, d, owned=None):
    """One token through the state of the rows that are `owned` ((B,)
    bool; None: every row): -> (state', y (B, H * P) float32). A row that
    is not owned keeps its state, untouched, and gives y = 0."""
    if _on_tpu() and _step_kernel_applies(state):
        return ssm_step_kernel(state, x, dt, a, bm, cm, d, owned)
    return ssm_step_reference(state, x, dt, a, bm, cm, d, owned)
