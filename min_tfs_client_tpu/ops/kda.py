"""Delta-rule linear attention with a per-channel decay (KDA, Kimi Delta
Attention, arXiv:2510.26692): the chunked form a prefill runs and the
one-token step a decode loop runs, each in plain jnp and as a kernel.

The recurrence, a head of d_k key channels and d_v value channels, its
state S (d_k, d_v) float32:

    S'  = Diag(exp(g_t)) S_{t-1}                 the decay, one a KEY CHANNEL
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     the delta rule's correction
    o_t = S_t^T q_t

Layouts, the same in every form: q and k (B, S, H, d_k), v and o (B, S,
H, d_v), g (B, S, H, d_k) float32 the LOG decay (<= 0), beta (B, S, H)
float32; the state (B, H, d_k, d_v) float32 (a key channel on the
sublanes, the value channels on the lanes: the decay, k and q of a step
are columns, v and o are rows). Positions at or past an example's length
take g = 0 and beta = 0: the state passes them unchanged, so the state
handed on is the state after the last real token.

Three forms of the prefill, one arithmetic:
 * `kda_reference`  the recurrence token by token (a `lax.scan` over
   time): the definition, and the tests' yardstick;
 * `kda_chunked`    plain jnp over chunks of `chunk` rows with the state
   carried, only the chunks up to the batch's longest example run. Within
   a chunk, with G the cumulative sum of g from the chunk's first row and
   u_t = v_t - S'_t^T k_t the corrections,

       (I + A Diag(beta)) U = V - (K exp(G)) S_0,
       A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])   for i < t,

   a unit lower-triangular system, SOLVED (`solve_triangular`), and
   o = (Q exp(G)) S_0 + Aq (beta U) with Aq as A from q, i <= t. A per-
   channel decay does not factor out of the products as a scalar one
   does (ops/ssm.py), and exp(-G_i) alone overflows (g reaches -5 a
   token: -320 a chunk of 64): A is computed in sub-blocks of 16 rows,
   each against its OWN reference, G at the sub-block's first row, so
   that every exponent is at most 15 x 5 = 75 (float32 holds exp(88)).
   What the CPU and other platforms run, and the tests' second yardstick;
 * `kda_chunk_kernel`  the same as the Pallas kernel `_kda_chunk_kernel`:
   a grid of (example, chunk), the chunks of an example in order with its
   heads' states resident, the chunks past ITS length neither fetched nor
   run; every operand read where it lies (q, k, v (B, S, H, d): a head's
   rows a strided load; g and o rows of every head's channels: a head a
   lane slice). The system is solved exactly: forward substitution over
   the sub-blocks, each diagonal sub-block inverted as the finite series
   of a nilpotent matrix (`_inverse_of_one_plus`). What the chip runs:
   `kda_prefill` dispatches behind a gate that reads shapes.
and two of the step: `kda_step_reference` (jnp) and `kda_step_kernel`
(Pallas, `_kda_step_kernel`, the state updated where it lies).
`kda_step` dispatches behind the same gate as ops/attention.py.

Precision: everything float32; the products of the chunked form at
"highest" matmul precision (the sub-blocks' operands span 30 orders of
magnitude, and the triangular solve feeds every product back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 16          # rows of a sub-block: 15 x |g|max = 75 < log(float32 max)
_STEP_HEADS = 8    # heads of a state a step's grid cell takes
_HIGHEST = jax.lax.Precision.HIGHEST


def _masked(g: jax.Array, beta: jax.Array, lengths: jax.Array | None):
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if lengths is None:
        return g, beta
    real = jnp.arange(g.shape[1])[None, :] < lengths[:, None]
    return (jnp.where(real[..., None, None], g, 0.0),
            jnp.where(real[..., None], beta, 0.0))


# -- the definition ----------------------------------------------------------


def kda_reference(q, k, v, g, beta, lengths=None):
    """Token by token (`kda_step_reference` under a `lax.scan` over time).
    -> (o (B, S, H, d_v) float32, state (B, H, d_k, d_v) float32)."""
    b, _, h, dk = q.shape
    g, beta = _masked(g, beta, lengths)
    time_major = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    state, o = jax.lax.scan(
        lambda state, at: kda_step_reference(state, *at),
        jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(time_major(x) for x in (q, k, v, g, beta)))
    return time_major(o), state


# -- chunked, plain jnp ------------------------------------------------------


def kda_chunked(q, k, v, g, beta, lengths=None, *, chunk: int = 64):
    """The chunked form in plain jnp, a chunk at a time with the state
    carried; only the chunks up to the batch's longest example run. ->
    (o (B, S, H, d_v) float32, state (B, H, d_k, d_v) float32, rows the
    chunks ran for each example (B,))."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    sub = min(_SUB, c)
    if c % sub:
        raise ValueError(f"a chunk of {c} rows is not whole sub-blocks")
    blocks = c // sub
    g, beta = _masked(g, beta, lengths)
    pad = (-s) % c
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    longest = s if lengths is None else jnp.max(lengths, initial=0)
    run = (longest + c - 1) // c
    row = jnp.arange(c)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]
    # column i is seen from sub-block I only where it lies in I or before
    reach = row[None, :] < (jnp.arange(blocks)[:, None] + 1) * sub
    dot = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    def one(i, carry):
        o, state = carry

        def cut(x):                                    # -> (B, H, C, ...)
            rows = jax.lax.dynamic_slice_in_dim(x, i * c, c, 1)
            return jnp.moveaxis(rows.astype(jnp.float32), 1, 2)

        qc, kc, vc, gc = cut(q), cut(k), cut(v), cut(g)
        bc = cut(beta[..., None])[..., 0]                      # (B, H, C)
        cum = jnp.cumsum(gc, axis=2)                           # G
        ref = cum[:, :, ::sub]                                 # (B, H, I, dk)
        from_ref = jnp.exp(cum - jnp.repeat(ref, sub, axis=2))  # <= 1
        k_neg = kc[:, :, None] * jnp.exp(jnp.where(
            reach[None, None, :, :, None],
            ref[:, :, :, None] - cum[:, :, None], -jnp.inf))   # (B,H,I,C,dk)

        def pairs(rows):
            blocked = (rows * from_ref).reshape(b, h, blocks, sub, dk)
            return dot("bhirc,bhijc->bhirj", blocked, k_neg).reshape(
                b, h, c, c)

        a = jnp.where(below, pairs(kc), 0.0) * bc[:, :, None, :]
        aq = jnp.where(upto, pairs(qc), 0.0) * bc[:, :, None, :]
        grown = jnp.exp(cum)
        solved = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c), jnp.concatenate([vc, kc * grown], axis=-1),
            lower=True, unit_diagonal=True)
        u = solved[..., :dv] - dot("bhtk,bhkv->bhtv", solved[..., dv:], state)
        oc = dot("bhtk,bhkv->bhtv", qc * grown, state) \
            + dot("bhti,bhiv->bhtv", aq, u)
        last = cum[:, :, -1:]
        state = jnp.exp(last)[:, :, 0, :, None] * state + dot(
            "bhtk,bhtv->bhkv", kc * jnp.exp(last - cum), bc[..., None] * u)
        return (jax.lax.dynamic_update_slice_in_dim(
            o, jnp.moveaxis(oc, 2, 1), i * c, 1), state)

    o, state = jax.lax.fori_loop(
        0, run, one, (jnp.zeros((b, s + pad, h, dv), jnp.float32),
                      jnp.zeros((b, h, dk, dv), jnp.float32)))
    return o[:, :s], state, jnp.full((b,), run * c, jnp.int32)


# -- chunked, the Pallas kernel ----------------------------------------------


def _inverse_of_one_plus(n: jax.Array, eye: jax.Array, dot) -> jax.Array:
    """(I + n)^-1 for n strictly lower triangular in diagonal blocks of
    `_SUB` rows, so n^16 = 0: the finite series I - n + n^2 - ... as the
    product (I - n)(I + n^2)(I + n^4)(I + n^8), which is exact. The next
    power and the next factor come out of ONE product, their left sides
    stacked: [n^2p; x n^p] = [n^p; x] n^p."""
    rows = n.shape[0]
    inverse, power = eye - n, dot(n, n)
    for _ in range(_SUB.bit_length() - 3):
        both = dot(jnp.concatenate([power, inverse]), power)
        power, inverse = both[:rows], inverse + both[rows:]
    return inverse + dot(inverse, power)


def _kda_chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                      s_ref, *, chunk: int):
    """One (example, chunk) grid cell; the chunks of an example run in
    order and `s_ref`, its states (H, d_k, d_v), stays resident across
    them: zeroed at the first, written out after the last. A chunk at or
    past the example's length is not run (its blocks name the example's
    last chunk, so nothing was fetched for it) and its rows of o are
    zeros.

    Refs: len_ref (B,) SMEM; q and k (C * H, d_k) and v (C * H, d_v), row
    t * H + h the position t of head h, as (B, S, H, d) lies: a head's
    rows are a load with a stride of H sublanes; g (C, H * d_k) and o (C,
    H * d_v), a head a slice of whole lane tiles, as rows of every head's
    channels lie; beta (C, H) every head's on the lanes. A head's
    arithmetic is `kda_chunked`'s with the rows' beta on the LEFT of the
    system,

        (I + Diag(beta) A) W = Diag(beta) (V - (K exp(G)) S_0),  W = beta U,

    solved exactly by forward substitution over the sub-blocks of 16
    rows, each diagonal sub-block inverted as the finite series of a
    nilpotent matrix (`_inverse_of_one_plus`)."""
    example, index = pl.program_id(0), pl.program_id(1)
    length = len_ref[example]
    heads, dk, dv = s_ref.shape
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=_HIGHEST,
                            preferred_element_type=f32)
    sub_blocks = [slice(lo, lo + _SUB) for lo in range(0, chunk, _SUB)]

    def lanes(j, width):
        return pl.ds(pl.multiple_of(j * width, width), width)

    @pl.when(index == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(index * chunk >= length)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(index * chunk < length)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        real = index * chunk + row < length
        eye = (row == col).astype(f32)
        same_block = row // _SUB == col // _SUB
        betas = jnp.where(real, beta_ref[...].astype(f32), 0.0)   # (C, H)
        head_lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)

        def head(j, _):
            mine = pl.ds(j, chunk, stride=heads)
            q, k, v = q_ref[mine, :], k_ref[mine, :], v_ref[mine, :]
            beta = jnp.sum(jnp.where(head_lane == j, betas, 0.0), axis=1,
                           keepdims=True)
            cum = jnp.where(real, g_ref[:, lanes(j, dk)], 0.0)     # -> G
            for shift in (1 << i for i in range((chunk - 1).bit_length())):
                cum = cum + jnp.where(row >= shift,
                                      pltpu.roll(cum, shift, 0), 0.0)
            refs = [cum[at][:1] for at in sub_blocks]
            from_ref = jnp.exp(cum - jnp.concatenate(
                [jnp.broadcast_to(ref, (_SUB, dk)) for ref in refs]))  # <= 1
            k_own, q_own = k * from_ref, q * from_ref
            k_pairs, q_pairs = [], []
            for at, ref in zip(sub_blocks, refs):
                # the rows of k in this sub-block or before it, seen from
                # its reference: exponents at most 15 x 5
                k_neg = k[:at.stop] * jnp.exp(ref - cum[:at.stop])
                pairs = jnp.pad(jax.lax.dot_general(
                    jnp.concatenate([k_own[at], q_own[at]]), k_neg,
                    (((1,), (1,)), ((), ())), precision=_HIGHEST,
                    preferred_element_type=f32),
                    ((0, 0), (0, chunk - at.stop)))                # (2 SUB, C)
                k_pairs.append(pairs[:_SUB])
                q_pairs.append(pairs[_SUB:])
            a = jnp.where(row > col, jnp.concatenate(k_pairs), 0.0) * beta
            aq = jnp.where(row >= col, jnp.concatenate(q_pairs), 0.0)
            within = _inverse_of_one_plus(jnp.where(same_block, a, 0.0), eye,
                                          dot)
            grown = jnp.exp(cum)
            last = cum[chunk - 1:chunk]
            state = s_ref[j]
            carried = dot(jnp.concatenate([k * grown, q * grown]), state)
            rhs = beta * (v - carried[:chunk])
            solved = []                                            # beta U
            for at in sub_blocks:
                mine_rhs = rhs[at]
                if solved:
                    mine_rhs = mine_rhs - dot(a[at, :at.start],
                                              jnp.concatenate(solved))
                solved.append(dot(within[at, at], mine_rhs))
            w = jnp.concatenate(solved)
            o_ref[:, lanes(j, dv)] = carried[chunk:] + dot(aq, w)
            # exp(last) a KEY channel: a column of the state. The row
            # (1, d_k) is spread over the lanes and turned
            kept = jnp.broadcast_to(jnp.exp(last), (dv, dk)).T
            s_ref[j] = kept * state + jax.lax.dot_general(
                k * jnp.exp(last - cum), w, (((0,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=f32)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)


def _chunk_vmem_bytes(chunk: int, heads: int, dk: int, dv: int) -> int:
    """What a call of `_kda_chunk_kernel` keeps in VMEM: two buffers of
    each block (q, k, g, v, o float32; beta a lane tile wide) and of the
    states."""
    return 2 * 4 * (chunk * heads * (3 * dk + 2 * dv) + chunk * 128
                    + heads * dk * dv)


def _chunk_kernel_applies(q, k, v, g, chunk: int) -> bool:
    """The shapes `_kda_chunk_kernel` is written for: a head's key and
    value channels whole lane tiles, a chunk whole sub-blocks of 16 rows,
    the heads whole sublane tiles (a head's rows are a strided load of
    the rows as they lie), every operand float32 (a row of a narrower
    type shares its sublane), and its account of VMEM with the 4 MiB the
    call adds inside 48 MiB."""
    _, _, h, dk = q.shape
    dv = v.shape[-1]
    return (dk % 128 == 0 and dv % 128 == 0 and chunk % _SUB == 0
            and h % 8 == 0
            and all(x.dtype == jnp.float32 for x in (q, k, v, g))
            and _chunk_vmem_bytes(chunk, h, dk, dv) <= 44 << 20)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_chunk_kernel(q, k, v, g, beta, lengths=None, *, chunk: int = 64,
                     interpret: bool = False):
    """`kda_chunked` as the Pallas kernel: every example runs its own
    chunks and no more. Same results, same return (the rows the chunks
    ran are each example's own)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    sp = s + pad
    # q, k, v as the heads' own arithmetic leaves them: (B, S x H, d), the
    # same bytes, a head every H-th row; g and o as the projections' rows
    # have them: (B, S, H x d), a head a lane slice
    q, k, v = (x.reshape(b, sp * h, -1) for x in (q, k, v))
    g = g.reshape(b, sp, h * dk)

    def chunk_of(e, i, len_ref):
        # a chunk past the example's last names the last: nothing moves
        return (e, jnp.minimum(i, jnp.maximum(
            (len_ref[e] + chunk - 1) // chunk - 1, 0)), 0)

    o, state = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, sp // chunk),
            in_specs=[
                pl.BlockSpec((None, chunk * h, dk), chunk_of),
                pl.BlockSpec((None, chunk * h, dk), chunk_of),
                pl.BlockSpec((None, chunk * h, dv), chunk_of),
                pl.BlockSpec((None, chunk, h * dk), chunk_of),
                pl.BlockSpec((None, chunk, h), chunk_of),
            ],
            out_specs=[
                # every chunk's rows of o are written: zeros where not run
                pl.BlockSpec((None, chunk, h * dv),
                             lambda e, i, len_ref: (e, i, 0)),
                pl.BlockSpec((None, h, dk, dv),
                             lambda e, i, len_ref: (e, 0, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, sp, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_chunk_vmem_bytes(chunk, h, dk, dv) + (4 << 20)),
        interpret=interpret,
        name="_kda_chunk_kernel",  # the device-trace reduction finds it
    )(lengths, q, k, v, g, beta)
    return (o[:, :s].reshape(b, s, h, dv), state,
            (lengths + chunk - 1) // chunk * chunk)


# -- one token ---------------------------------------------------------------


def kda_step_reference(state, q, k, v, g, beta, owned=None):
    """One token: state (B, H, d_k, d_v) float32, q, k and g (B, H, d_k),
    v (B, H, d_v), beta (B, H); `owned` (B,) bool, None for every row: a
    row that is not owned keeps its state and gives o = 0.
    -> (state', o (B, H, d_v) float32)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    decayed = jnp.exp(g.astype(jnp.float32))[..., None] * state
    seen = jnp.sum(decayed * k[..., None], axis=-2)
    moved = decayed + k[..., None] * (
        beta.astype(jnp.float32)[..., None] * (v - seen))[..., None, :]
    o = jnp.sum(moved * q[..., None], axis=-2)
    if owned is None:
        return moved, o
    return (jnp.where(owned[:, None, None, None], moved, state),
            jnp.where(owned[:, None, None], o, 0.0))


def _kda_step_kernel(row_ref, count_ref, s_ref, rows_ref, so_ref, o_ref):
    """One (owned row, head group) grid cell: the group's states (G, d_k,
    d_v) read, decayed, corrected and written where they lie; rows (G, 8,
    lanes): the decay exp(g), k, q, v and (on every lane) beta on the
    first five; o (G, 1, d_v). The decay, k and q act on the state's KEY
    channels, its sublanes: each is spread over the lanes and turned
    (`_as_columns`), so that no operand comes in one lane wide. row_ref
    (B,) and count_ref (1,) in SMEM: the owned rows' indices first, and
    how many they are. A cell past the count names the last real cell's
    blocks (`kda_step_kernel`): nothing was fetched for it, it does
    nothing, and nothing is written after it."""
    count = count_ref[0]
    dk, dv = s_ref.shape[1:]

    def as_columns(row):
        """(1, d_k) -> (d_k, d_v), the row's figure k on every lane of
        sublane k."""
        return jnp.broadcast_to(row[:, :dk], (dv, dk)).T

    @pl.when(pl.program_id(0) < count)
    def _():
        for j in range(s_ref.shape[0]):
            rows = rows_ref[j]
            k = as_columns(rows[1:2])
            decayed = as_columns(rows[0:1]) * s_ref[j]
            seen = jnp.sum(decayed * k, axis=0, keepdims=True)     # (1, d_v)
            moved = decayed + k * (rows[4:5, :dv]
                                   * (rows[3:4, :dv] - seen))
            so_ref[j] = moved
            o_ref[j] = jnp.sum(moved * as_columns(rows[2:3]), axis=0,
                               keepdims=True)

    # no row owned: every cell names ONE block, which the pipeline still
    # fetches and writes back: it goes back as it came
    @pl.when(count == 0)
    def _():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step_kernel(state, q, k, v, g, beta, owned=None, *,
                    interpret: bool = False):
    """`kda_step_reference` as the Pallas kernel, the state written in
    place (the input's buffer is the output's). The grid walks the OWNED
    rows, compacted to the front of a prefetched list, as
    ops/ssm.py:ssm_step_kernel's: a row that is not owned is neither
    fetched nor written, and keeps its bytes."""
    b, h, dk, dv = state.shape
    group = min(_STEP_HEADS, h)
    groups = h // group
    lanes = max(dk, dv)
    if owned is None:
        owned = jnp.ones((b,), jnp.bool_)
    row_of = jnp.argsort(jnp.logical_not(owned), stable=True).astype(
        jnp.int32)
    count = jnp.sum(owned, dtype=jnp.int32)[None]
    wide = lambda x: jnp.pad(x.astype(jnp.float32), (  # noqa: E731
        (0, 0), (0, 0), (0, lanes - x.shape[-1])))
    rows = jnp.stack([
        wide(jnp.exp(g.astype(jnp.float32))), wide(k), wide(q), wide(v),
        jnp.broadcast_to(beta.astype(jnp.float32)[..., None],
                         (b, h, lanes))], axis=2)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, 8 - rows.shape[2]), (0, 0)))

    def cell(i, j, row_ref, count_ref):
        live = i < count_ref[0]
        row = row_ref[jnp.maximum(jnp.minimum(i, count_ref[0] - 1), 0)]
        return (row, jnp.where(live, j, groups - 1), 0, 0)

    state, o = pl.pallas_call(
        _kda_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[
                pl.BlockSpec((None, group, dk, dv), cell),
                pl.BlockSpec((None, group, 8, lanes), cell),
            ],
            out_specs=[
                pl.BlockSpec((None, group, dk, dv), cell),
                pl.BlockSpec((None, group, 1, dv), cell),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1, dv), jnp.float32)],
        # operand 2: the two prefetched scalars come first
        input_output_aliases={2: 0},
        # a cell past the count revisits a block: no dimension is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="_kda_step_kernel",  # the device-trace reduction finds it
    )(row_of, count, state, rows)
    # a row no cell wrote holds whatever the buffer held
    return state, jnp.where(owned[:, None, None], o[:, :, 0, :], 0.0)


def _step_kernel_applies(state: jax.Array) -> bool:
    _, h, dk, dv = state.shape
    # the turn of a row into columns is of whole (lanes, lanes) tiles
    return dk % 128 == 0 and dv % 128 == 0 and h % min(_STEP_HEADS, h) == 0


# -- dispatch ----------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kda_step(state, q, k, v, g, beta, owned=None):
    """One token through the state of the rows that are `owned` ((B,)
    bool; None: every row): -> (state', o (B, H, d_v) float32). A row
    that is not owned keeps its state, untouched, and gives o = 0."""
    if _on_tpu() and _step_kernel_applies(state):
        return kda_step_kernel(state, q, k, v, g, beta, owned)
    return kda_step_reference(state, q, k, v, g, beta, owned)


def kda_prefill(q, k, v, g, beta, lengths=None, *, chunk: int = 64):
    """The chunked delta rule over a prefill's examples: -> (o (B, S, H,
    d_v) float32, state (B, H, d_k, d_v) float32, rows the chunks ran for
    each example (B,))."""
    if _on_tpu() and _chunk_kernel_applies(q, k, v, g, chunk):
        return kda_chunk_kernel(q, k, v, g, beta, lengths, chunk=chunk)
    return kda_chunked(q, k, v, g, beta, lengths, chunk=chunk)
