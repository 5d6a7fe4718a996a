"""Attention ops: Pallas TPU flash attention + pure-JAX reference.

The reference serving stack has no attention anywhere (SURVEY.md §2.11 —
its kernels layer is tensorflow/core/kernels/, CPU/CUDA); attention here is
the hot op of the model families this framework serves (BERT, USE, T5), so
it gets the framework's one hand-written TPU kernel:

 * `flash_attention` — blocked online-softmax attention in a single Pallas
   kernel: Q tiles stream through VMEM, K/V live in VMEM per (batch, head),
   scores never materialise in HBM. Operands are upcast to f32 inside the
   kernel. Supports causal masking (decoder) and per-example key lengths
   (padded serving batches).
 * `attention_reference` — the jnp semantics oracle: used on CPU backends,
   for shapes the kernel is not written for, and when an additive bias is
   supplied (T5's relative position bias).

`attention()` picks the kernel on a TPU for every shape its gate admits —
the gate states what the kernel compiles for, and nothing catches a
failure behind it; all model code calls it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

NEG_INF = -1e30  # finite -inf stand-in: keeps masked softmax NaN-free

# Pallas block sizes. Q is tiled; K/V stream through in chunks of _BLOCK_KV.
_BLOCK_Q = 128
_BLOCK_KV = 128


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal_offset: Optional[int] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    queries_ragged: bool = False,
) -> jax.Array:
    """Plain softmax(q k^T / sqrt(d) + bias) v.

    Shapes: q (B, H, Sq, D); k (B, Hkv, Skv, D) and v (B, Hkv, Skv, Dv)
    with H a multiple of Hkv (query head h reads K/V head h // (H //
    Hkv)) and Dv free; lengths (B,) int32 valid key counts; bias
    broadcastable to (B, H, Sq, Skv). Returns (B, H, Sq, Dv) in q.dtype;
    softmax runs in f32. `causal_offset` is query row 0's absolute key
    position (default Skv-Sq: right-aligned, the KV-cache decode
    convention; pass 0 for cache prefill). `window` (causal only) keeps
    the keys with query position - key position < window. `sink` (H,) is
    one more logit a head, in the softmax's denominator and with no
    value: a row's weights sum to less than one. `queries_ragged` says
    the batch is self-attention over padded rows, so `lengths` bounds the
    QUERY rows too: a row at or past its example's length gives zeros.
    """
    _, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[-2]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if window is not None and not causal:
        raise ValueError("a window is a causal window")
    if h != hkv:
        # The oracle repeats the shared heads; the kernel does not.
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    offset = skv - sq if causal_offset is None else causal_offset
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(skv)[None, :]
    if causal:
        s = jnp.where(qi >= ki, s, NEG_INF)
    if window is not None:
        s = jnp.where(qi - ki < window, s, NEG_INF)
    if lengths is not None:
        s = jnp.where(ki[None, None] < lengths[:, None, None, None],
                      s, NEG_INF)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        sink = sink.astype(jnp.float32).reshape(1, h, 1, 1)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
        e = jnp.exp(s - m)
        p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))
    # Fully-masked rows -> zeros (not a uniform mean over masked V),
    # matching the flash kernel's row_valid semantics.
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= NEG_INF * 0.5,
                  0.0, p)
    if queries_ragged and lengths is not None:
        p = jnp.where(qi[None, None] < lengths[:, None, None, None], p, 0.0)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32).astype(q.dtype)


def _flash_kernel(len_ref, *refs, scale: float, causal: bool, block_kv: int,
                  kv_seq_len: int, q_offset: int, heads: int,
                  window: Optional[int], has_sink: bool,
                  queries_ragged: bool):
    """One (batch*head, q-block) grid cell.

    Refs: len_ref (B*H,) SMEM int32; with a sink, sink_ref (H,) SMEM
    f32; q_ref (block_q, D); k_ref (kv_seq_len, D) and v_ref
    (kv_seq_len, Dv) of the K/V head this query head reads; o_ref
    (block_q, Dv). Online softmax over KV chunks with f32 running (max,
    denom, acc) carried through a fori_loop; the MXU takes the operands
    in their own dtype. The loop runs only the KV chunks that hold a key
    some row of this block may see: none above the diagonal, none wholly
    outside the window, none past the example's length.
    """
    if has_sink:
        sink_ref, q_ref, k_ref, v_ref, o_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref = refs
    block_q = q_ref.shape[0]
    d_v = v_ref.shape[-1]
    q = q_ref[...]
    valid_len = len_ref[pl.program_id(0)]
    q_start = q_offset + pl.program_id(1) * block_q   # absolute first row

    n_kv = kv_seq_len // block_kv

    def body(i, carry):
        m_prev, l_prev, acc = carry
        kv_start = i * block_kv
        k_blk = k_ref[pl.ds(kv_start, block_kv), :]
        v_blk = v_ref[pl.ds(kv_start, block_kv), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_kv)

        ki = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ki < valid_len
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, qi >= ki)
            if window is not None:
                mask = jnp.logical_and(mask, qi - ki < window)
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    if has_sink:
        # The sink is a key with no value: the running maximum starts at
        # its logit and the denominator at exp(0).
        sink = sink_ref[pl.program_id(0) % heads]
        m0 = jnp.full((block_q, 1), sink, jnp.float32)
        l0 = jnp.ones((block_q, 1), jnp.float32)
    else:
        m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d_v), jnp.float32)
    n_run = jnp.minimum(n_kv, (valid_len + block_kv - 1) // block_kv)
    first = 0
    if causal:
        # Skip KV blocks strictly above this Q block's diagonal.
        n_run = jnp.minimum(n_run, (q_start + block_q + block_kv - 1)
                            // block_kv)
        if window is not None:
            first = jnp.maximum(q_start - window + 1, 0) // block_kv
    if queries_ragged:
        n_run = jnp.where(q_start >= valid_len, 0, n_run)
    m, l, acc = jax.lax.fori_loop(first, n_run, body, (m0, l0, acc0))
    # Fully-masked rows (valid_len 0, or the skips ran zero blocks) must
    # return zeros: m never left NEG_INF there (exp(s-m)=1 would otherwise
    # leak a mean over masked V rows into acc). With a sink the
    # denominator is never 0 and acc is 0 there already.
    row_valid = m > NEG_INF * 0.5
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(row_valid, acc / l, 0.0)
    if queries_ragged:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        out = jnp.where(rows < valid_len, out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_call(q, k, v, lengths, sink, *, causal, scale, interpret,
                q_offset, window, queries_ragged):
    """The pallas_call over the shapes one device sees."""
    b, h, sq, d = q.shape
    hkv, d_v = k.shape[1], v.shape[-1]
    group = h // hkv
    block_q = min(_BLOCK_Q, max(8, 1 << (sq - 1).bit_length()))
    q_p = _pad_to(q, 2, block_q)
    k_p = _pad_to(k, 2, _BLOCK_KV)
    v_p = _pad_to(v, 2, _BLOCK_KV)
    sq_p, skv_p = q_p.shape[2], k_p.shape[2]

    # Fold heads into the batch grid dim; lengths replicate per head.
    # K and V keep their own (fewer) heads: a query head's grid cells
    # name the block of the K/V head it reads, so no K/V row is repeated
    # in memory, and consecutive cells of one group fetch it once.
    q_f = q_p.reshape(b * h, sq_p, d)
    k_f = k_p.reshape(b * hkv, skv_p, d)
    v_f = v_p.reshape(b * hkv, skv_p, d_v)
    len_f = jnp.repeat(lengths, h)  # (b*h,) in SMEM

    def kv_index(bh, i):
        return ((bh // h) * hkv + (bh % h) // group, 0, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_kv=_BLOCK_KV,
        kv_seq_len=skv_p, q_offset=q_offset, heads=h, window=window,
        has_sink=sink is not None, queries_ragged=queries_ragged)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # a whole vector
    scalars = (len_f,) if sink is None else (len_f, sink.astype(jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq_p // block_q),
        in_specs=[smem] * len(scalars) + [
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, skv_p, d), kv_index),
            pl.BlockSpec((None, skv_p, d_v), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, d_v),
                               lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d_v), q.dtype),
        interpret=interpret,
        name="_flash_kernel",  # the device-trace reduction finds it by name
    )(*scalars, q_f, k_f, v_f)
    return out.reshape(b, h, sq_p, d_v)[:, :, :sq, :]


def _auto_mesh_axes() -> set:
    """Axes of the ambient mesh that XLA, not an enclosing shard_map,
    partitions over; empty on one device."""
    mesh = jax.sharding.get_abstract_mesh()
    return set(mesh.axis_names) - set(mesh.manual_axes)


def _flash_over_mesh(call, q, k, v, lengths, sink):
    """XLA cannot split a Mosaic kernel over a mesh by itself ("Mosaic
    kernels cannot be automatically partitioned"), so under a serving mesh
    (servables/servable.py runs meshed signatures inside `jax.set_mesh`)
    the kernel says how: batch and heads are independent grid cells and
    shard over the data and model axes (heads only where the axis divides
    the K/V heads too, so a query head and its K/V head stay together),
    sequence and head dim stay whole on every device, and each device
    runs `call` on its own shard."""
    from min_tfs_client_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    mesh = jax.sharding.get_abstract_mesh()
    # Every axis not already manual (an enclosing shard_map's) goes
    # manual here; a dim an axis does not divide is replicated over it.
    auto = _auto_mesh_axes()
    if not auto:
        return call(q, k, v, lengths, sink)

    def axis_for(name, *dims):
        return name if (name in auto and all(
            dim % mesh.shape[name] == 0 for dim in dims)) else None

    batch_axis = axis_for(DATA_AXIS, q.shape[0])
    head_axis = axis_for(MODEL_AXIS, q.shape[1], k.shape[1])
    qkv = PartitionSpec(batch_axis, head_axis, None, None)
    operands = [q, k, v, lengths]
    specs = [qkv, qkv, qkv, PartitionSpec(batch_axis)]
    if sink is not None:
        operands.append(sink)
        specs.append(PartitionSpec(head_axis))
    return jax.shard_map(
        lambda q, k, v, n, sink=None: call(q, k, v, n, sink),
        in_specs=tuple(specs), out_specs=qkv, axis_names=auto,
        check_vma=False)(*operands)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "interpret", "causal_offset",
                              "window", "queries_ragged"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    causal_offset: Optional[int] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    queries_ragged: bool = False,
) -> jax.Array:
    """Pallas flash attention. Same contract as attention_reference
    (minus bias). Sequence dims are padded to block multiples internally;
    padded keys are masked via `lengths`, padded queries sliced off."""
    b, _, sq, d = q.shape
    skv = k.shape[-2]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if window is not None and not causal:
        raise ValueError("a window is a causal window")
    if lengths is None:
        lengths = jnp.full((b,), skv, jnp.int32)
    # Right-align causal masking when decoding with a KV cache, unless
    # the caller pins query row 0's absolute position (cache prefill).
    q_offset = ((skv - sq if causal_offset is None else causal_offset)
                if causal else 0)
    call = functools.partial(_flash_call, causal=causal, scale=scale,
                             interpret=interpret, q_offset=q_offset,
                             window=window, queries_ragged=queries_ragged)
    return _flash_over_mesh(call, q, k, v, lengths.astype(jnp.int32), sink)


# -- ragged paged attention (block-table KV) ---------------------------------
#
# The decode KV store (servables/decode_sessions.PagedSlotPool) keeps each
# session's cache as block_size-token pages scattered through a shared HBM
# arena, addressed by a per-session block table. The arena's shape is ONE
# decision, owned by `PagedKV.arena`: `(num_pages + 1, block_size, F)`,
# token-major and lane-dense. A page is block_size token rows; a row is
# every head of one token, contiguous (F = H * D for attention K/V); the
# last page is the trash page that absorbs masked writes. The append's
# scatter (its scattered dims major, the row minor), the layout the runtime
# gives a donated argument (row-major once the minor dim fills its 128
# lanes) and the Pallas operand (row-major) all agree on it, so a tick
# writes its rows in place and the kernel reads the pages as they lie: no
# program copies an arena. (The old (pages, H, block_size, D) unit cost
# three relayouts of every arena a tick at D = 64: PERF.md, PR 28.)
# Attention over it has two equivalent forms:
#
#  * `paged_attention_reference` — the jnp semantics oracle: gather the
#    table's pages back into a contiguous (B, H, P*bs, D) view sized by the
#    table width (true used tokens, NOT max length) and run masked dense
#    attention. This is the CPU path and the token-exactness yardstick.
#  * `paged_flash_attention` — Pallas kernel over a (slot, head group,
#    table entry) grid: the block table and the lengths ride as
#    scalar-prefetch operands, and a step's BlockSpecs fetch ONE page of
#    K and of V, the group's heads side by side on its lanes, plus that
#    page's bias tile, online-softmax accumulated in VMEM scratch. A step
#    past a slot's last page names the block already resident, so nothing
#    is fetched for it, and its body does not run: a slot of length 0 (one
#    that does not ride this tick) costs its steps' bare overhead. Pages
#    never materialize contiguously.
#
# `paged_attention()` dispatches between them behind the same `_on_tpu()`
# gate as the dense kernel (arXiv:2604.15464's ragged paged attention,
# collapsed to the single-arena/one-table layout the pool uses).


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array,
                    num_heads: int) -> jax.Array:
    """(num_pages, bs, H*D) arena + (B, P) int32 tables -> (B, H, P*bs, D).

    Entries past a sequence's allocated pages may name ANY in-range page
    (the pool points them at its trash page); callers mask by length."""
    b, p = block_tables.shape
    _, bs, f = pages.shape
    g = pages[block_tables]  # (B, P, bs, F): token rows already in order
    return g.reshape(b, p * bs, num_heads, f // num_heads).transpose(
        0, 2, 1, 3)


def paged_attention_reference(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    q_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Oracle: gather pages per true sequence length, then masked dense
    attention. q (B, H, Sq, D) holds Sq consecutive positions; the arenas
    are `PagedKV.arena`s of (H, D) tokens; lengths (B,) counts valid keys
    INCLUDING the query rows' own (already-written) K/V. `q_start` (B,) is
    query row 0's absolute position — default lengths - Sq
    (right-aligned, the KV-cache decode/verify convention); a chunked
    prefill passes its chunk's start explicitly so a partial final chunk
    (valid rows < Sq) still masks per true row position. Query row r
    attends keys < min(lengths, q_start + r + 1), so Sq=1 reduces to pure
    lengths masking and Sq>1 is causal within the block. `bias`
    broadcastable to (B, H, Sq, P*block_size) is added after scaling
    (T5's relative position bias over the gathered key positions).
    Returns (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if q_start is None:
        q_start = lengths - sq
    k = gather_kv_pages(k_pages, block_tables, h)
    v = gather_kv_pages(v_pages, block_tables, h)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    ki = jnp.arange(k.shape[-2])[None, None, None, :]
    row_limit = jnp.minimum(
        lengths[:, None, None, None],
        q_start[:, None, None, None]
        + (jnp.arange(sq) + 1)[None, None, :, None])
    s = jnp.where(ki < row_limit, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    all_masked = jnp.max(s, axis=-1, keepdims=True) <= NEG_INF * 0.5
    p = jnp.where(all_masked, 0.0, p)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32).astype(q.dtype)


def _paged_kernel(tbl_ref, len_ref, qstart_ref, *rest,
                  scale: float, block_size: int, sq: int, has_bias: bool):
    """One (slot, head group, table entry) grid cell. The index_maps
    already routed this cell's K/V refs at the table's page, the group's
    heads side by side on its lanes; here we accumulate online softmax
    across the table's entries in VMEM scratch and emit on the last one.
    Entries past the slot's valid keys do nothing."""
    if has_bias:
        bias_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        bias_ref = None
    heads, _, d = q_ref.shape
    slot = pl.program_id(0)
    page = pl.program_id(2)
    valid_len = len_ref[slot]

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def heads_of(page_ref):
        """The page's heads off its lanes: (block_size, heads * D) ->
        (heads, block_size, D). (Of three bodies timed on the chip this
        one, then the head-batched matmuls, was quickest: PERF.md, PR 28.)"""
        return jnp.stack([page_ref[:, h * d:(h + 1) * d]
                          for h in range(heads)]).astype(jnp.float32)

    @pl.when(page * block_size < valid_len)
    def _accumulate():
        q = q_ref[...].astype(jnp.float32) * scale  # (heads, Sq_p, D)
        k = heads_of(k_ref)
        v = heads_of(v_ref)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        if bias_ref is not None:
            s = s + bias_ref[...].astype(jnp.float32)
        ki = (page * block_size
              + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
        # Query row r sits at absolute position q_start + r: it attends
        # keys < min(valid_len, q_start + r + 1). Padded rows (r >= sq)
        # mask everything and emit zeros.
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_limit = jnp.minimum(valid_len, qstart_ref[slot] + qi + 1)
        row_limit = jnp.where(qi < sq, row_limit, 0)
        s = jnp.where(ki < row_limit, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = correction * l_ref[...] + jnp.sum(
            p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(page == pl.num_programs(2) - 1)
    def _emit():
        # Rows that met no valid key (length 0, padded rows) never left
        # NEG_INF: zeros, not a mean over masked V.
        row_valid = m_ref[...] > NEG_INF * 0.5
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = jnp.where(row_valid, acc_ref[...] / denom,
                               0.0).astype(o_ref.dtype)


def _query_rows(sq: int) -> int:
    """Query rows as the kernel sees them: padded to a sublane tile."""
    return max(8, 1 << (sq - 1).bit_length())


def _paged_step_vmem_bytes(heads: int, sq: int, d: int, block_size: int,
                           itemsize: int) -> int:
    """VMEM one grid step of `_paged_kernel` needs with `heads` heads in
    it: the K and V pages (the heads side by side on the lanes), the bias,
    Q and O blocks double-buffered, the softmax scratch, and the float32
    temporaries of the body; lanes padded to 128, rows to the dtype's
    sublane tile."""
    sq_p = _query_rows(sq)
    lanes = lambda n: -(-n // 128) * 128
    rows = lambda n: -(-n * itemsize // 32) * 32 // itemsize
    kv = 2 * rows(block_size) * lanes(heads * d) * itemsize
    qo = 2 * rows(sq_p) * lanes(d) * itemsize
    bias = sq_p * lanes(block_size) * 4
    scratch = sq_p * (2 * 128 + lanes(d)) * 4
    temps = ((sq_p + 2 * block_size) * lanes(d)
             + 3 * sq_p * lanes(block_size)) * 4
    return 2 * kv + heads * (2 * (qo + bias) + scratch + temps)


def _paged_head_group(h: int, sq: int, d: int, block_size: int,
                      itemsize: int) -> int:
    """Heads a grid step carries: the largest divisor of `h` whose lanes
    of a page are whole 128-lane tiles (or the whole row) and whose step
    fits `_PAGED_STEP_VMEM_BYTES`; 0 when there is none."""
    return next(
        (g for g in range(h, 0, -1) if h % g == 0
         and (g == h or g * d % 128 == 0)
         and _paged_step_vmem_bytes(g, sq, d, block_size, itemsize)
         <= _PAGED_STEP_VMEM_BYTES), 0)


def paged_flash_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    q_start: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas ragged paged attention. Same contract as
    paged_attention_reference; the block table, lengths, and q_start ride
    as scalar-prefetch operands so each grid step's BlockSpec index_map
    picks the right arena page — gathered pages never materialize in HBM.
    The grid is (slot, head group, table entry): one step holds a page of
    K and of V as it lies in the arena, (block_size, heads * D), for all
    heads of its group (all of H wherever a step fits VMEM,
    `_paged_head_group`); the body takes each head off its lanes. Table
    entries past a slot's ceil(length / block_size) pages are never read:
    their steps name the slot's last page again, which Pallas does not
    fetch twice, and skip the body; a slot of length 0 yields zeros.
    `bias` (broadcastable to (B, H, Sq, P*block_size)) streams one
    (heads, Sq, block_size) tile per page alongside the K/V pages."""
    b, h, sq, d = q.shape
    _, block_size, _ = k_pages.shape
    _, max_pages = block_tables.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if q_start is None:
        q_start = lengths - sq

    sq_p = _query_rows(sq)
    q_p = _pad_to(q, 2, sq_p)
    # (The gate refuses a shape with no group that fits; interpret mode
    # takes any.)
    hg = _paged_head_group(h, sq, d, block_size,
                           k_pages.dtype.itemsize) or h
    # The table rides in SMEM flat: a 2-D SMEM array pads every row to 128
    # words, so a (b, P) table would cost b * 512 bytes however narrow.
    tbl = block_tables.astype(jnp.int32).reshape(-1)  # (b * P,)

    def entry(slot, p, lens):
        """Table entry `p`, held at the slot's last page past its keys."""
        used = (lens[slot] + block_size - 1) // block_size
        return jnp.minimum(p, jnp.maximum(used - 1, 0))

    def page_index(slot, g, p, tbl, lens, qs):
        return (tbl[slot * max_pages + entry(slot, p, lens)], 0, g)

    def head_index(slot, g, p, tbl, lens, qs):
        return (slot, g, 0, 0)

    in_specs = [
        pl.BlockSpec((None, hg, sq_p, d), head_index),
        pl.BlockSpec((None, block_size, hg * d), page_index),
        pl.BlockSpec((None, block_size, hg * d), page_index),
    ]
    operands = [q_p, k_pages, v_pages]
    if bias is not None:
        # One (heads, Sq_p, block_size) tile per page, laid out (b, P, h,
        # Sq_p, bs) so the block's last two dims ARE the array's: Mosaic
        # takes any page size that way, where a (Sq_p, bs) window into a
        # (Sq_p, P*bs) row needs bs % 128 == 0 as soon as P > 1.
        bias_f = jnp.broadcast_to(
            bias.astype(jnp.float32),
            (b, h, sq, max_pages * block_size))
        bias_f = _pad_to(bias_f, 2, sq_p).reshape(
            b, h, sq_p, max_pages, block_size).transpose(0, 3, 1, 2, 4)
        in_specs.insert(0, pl.BlockSpec(
            (None, None, hg, sq_p, block_size),
            lambda slot, g, p, tbl, lens, qs:
            (slot, entry(slot, p, lens), g, 0, 0)))
        operands.insert(0, bias_f)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block tables, lengths, q_start
        grid=(b, h // hg, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hg, sq_p, d), head_index),
        scratch_shapes=[
            pltpu.VMEM((hg, sq_p, 1), jnp.float32),
            pltpu.VMEM((hg, sq_p, 1), jnp.float32),
            pltpu.VMEM((hg, sq_p, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=block_size, sq=sq,
        has_bias=bias is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="_paged_kernel",  # the device-trace reduction finds it by name
    )(tbl, lengths.astype(jnp.int32), q_start.astype(jnp.int32), *operands)
    return out[:, :, :sq, :]


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    q_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Dispatch: Pallas ragged kernel on TPU for every shape it is
    written for (`_paged_kernel_applies`), gather-based jnp reference
    otherwise. Sq>1 (speculative verify blocks, chunked prefill) routes
    through the same kernel — the query rows pad to the MXU sublane floor
    and mask per row. Semantics identical; the paged-decode suites assert
    token-exactness of both against the dense path."""
    if _on_tpu() and _paged_kernel_applies(q, k_pages, block_tables):
        return paged_flash_attention(q, k_pages, v_pages, block_tables,
                                     lengths, scale=scale, bias=bias,
                                     q_start=q_start)
    return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     lengths, scale=scale, bias=bias,
                                     q_start=q_start)


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    chunk_start: jax.Array,
    chunk_lens: jax.Array,
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked-prefill entry: q (B, Sq, ...) holds a fixed-size chunk of
    prompt positions starting at `chunk_start` (B,), of which only the
    first `chunk_lens` (B,) rows are real (a non-divisible prompt's final
    chunk is short; padded rows attend nothing real and their K/V rows
    must have been routed to the trash page by the caller's append).
    Valid keys = chunk_start + chunk_lens: the chunk's own already-written
    rows included, later garbage excluded. Row r attends keys
    < min(chunk_start + chunk_lens, chunk_start + r + 1)."""
    return paged_attention(q, k_pages, v_pages, block_tables,
                           chunk_start + chunk_lens, scale=scale, bias=bias,
                           q_start=chunk_start)


class PagedKV:
    """Block-table KV handle for paging-aware decode steps.

    The value a PagedSlotPool (servables/decode_sessions.py) hands a
    model's paged step contract, and the layout paged speculative decode
    builds internally: per KV leaf one page arena (`PagedKV.arena`, the
    only place that spells its shape), one shared `(B, W)` int32 block
    table, and per-sequence token counts. Purely functional — `append`
    returns a new handle with updated arenas; the model never sees a
    gathered dense cache.

    Fields:
      arenas     {key: arena}; key is caller-chosen (the pool uses the
                 leaf's pytree path, e.g. ("caches", 0, "self", "k"))
      tables     (B, W) int32; entries past a sequence's pages may name
                 any in-range page (the pool points them at trash)
      lengths    (B,) int32 tokens written BEFORE this step/chunk
      active     (B,) bool or None (None = all rows live)
      block_size, trash  static ints
    """

    __slots__ = ("arenas", "tables", "lengths", "active", "block_size",
                 "trash")

    def __init__(self, arenas: dict, tables: jax.Array, lengths: jax.Array,
                 *, block_size: int, trash: int,
                 active: Optional[jax.Array] = None):
        self.arenas = dict(arenas)
        self.tables = tables
        self.lengths = lengths
        self.active = active
        self.block_size = int(block_size)
        self.trash = int(trash)

    @staticmethod
    def arena_shape(num_pages: int, block_size: int,
                    token_shape: tuple) -> tuple:
        """`(num_pages + 1, block_size, F)`: a page is `block_size` token
        rows, a row the `token_shape` values of one token flattened in
        their order (attention K/V: (H, D) -> H * D lanes, head-major);
        page `num_pages`, the last, is the trash page."""
        return (int(num_pages) + 1, int(block_size),
                int(np.prod(token_shape, dtype=np.int64)))

    @classmethod
    def arena(cls, num_pages: int, block_size: int, token_shape: tuple,
              dtype) -> jax.Array:
        """A zeroed arena of `num_pages` pages and the trash page."""
        return jnp.zeros(cls.arena_shape(num_pages, block_size, token_shape),
                         dtype)

    def append(self, updates: dict, *,
               row_valid: Optional[jax.Array] = None) -> "PagedKV":
        """Scatter this step's new rows into the arenas at positions
        lengths .. lengths+Sq-1. updates: {key: rows} with rows
        (B, Sq, *token_shape) or already (B, Sq, F) — one arena row a
        token. Rows of inactive sequences, and rows at or past
        `row_valid` (B,) (a partial final prefill chunk), land on the
        trash page. Returns the updated handle."""
        first = next(iter(updates.values()))
        b, sq = first.shape[:2]
        pos = self.lengths[:, None] + jnp.arange(sq)[None, :]     # (B, Sq)
        page = jnp.take_along_axis(
            self.tables, pos // self.block_size, axis=1)
        keep = jnp.ones((b, sq), bool)
        if self.active is not None:
            keep = jnp.logical_and(keep, self.active[:, None])
        if row_valid is not None:
            keep = jnp.logical_and(keep,
                                   jnp.arange(sq)[None, :] < row_valid[:, None])
        page = jnp.where(keep, page, self.trash).reshape(-1)
        off = (pos % self.block_size).reshape(-1)
        arenas = dict(self.arenas)
        for key, rows in updates.items():
            arena = arenas[key]
            # Scattered dims (page, row) major, the written window minor:
            # XLA scatters into the donated arena in place.
            arenas[key] = arena.at[page, off].set(
                rows.reshape(b * sq, arena.shape[-1]).astype(arena.dtype))
        return PagedKV(arenas, self.tables, self.lengths,
                       block_size=self.block_size, trash=self.trash,
                       active=self.active)

    def attend(self, q: jax.Array, k_key, v_key, *,
               scale: Optional[float] = None,
               bias: Optional[jax.Array] = None,
               lengths: Optional[jax.Array] = None,
               q_start: Optional[jax.Array] = None) -> jax.Array:
        """paged_attention over this handle's arenas. Default convention:
        the Sq query rows are the block just appended — valid keys =
        lengths + Sq, q_start = lengths. A partial prefill chunk passes
        explicit lengths (= chunk_start + chunk_lens) and q_start."""
        sq = q.shape[2]
        if lengths is None:
            lengths = self.lengths + sq
        if q_start is None:
            q_start = self.lengths
        if self.active is not None:
            # A slot that does not ride reads nothing: its rows went to
            # the trash page and its output is never used.
            lengths = jnp.where(self.active, lengths, 0)
        return paged_attention(q, self.arenas[k_key], self.arenas[v_key],
                               self.tables, lengths, scale=scale, bias=bias,
                               q_start=q_start)


# -- a few query rows over dense K/V rows ------------------------------------
#
# A whole generation (models/t5.py) attends the same K and V at every decode
# step: cross-attention the encoder's output projected ONCE, self-attention
# the rows the steps before it wrote. Both are kept as the projection leaves
# them, (B, S, H * D) rows with the heads side by side on the lanes, like an
# arena's pages, every layer's in one array, (L, B, S, H * D): `layer=`.
# One query row a step is no work for the MXU as `attention()` sees it
# (M = 1: XLA widens K and V to float32 and multiplies on the VPU, over all
# S rows whatever the lengths), so these rows get a read of their own:
#
#  * `rows_flash_attention` — Pallas kernel. K and V stay in HBM; a grid
#    step copies into VMEM itself what its examples' lengths need, the NEXT
#    step's while this one's are multiplied, once, in their own dtype, and
#    an example of length 0 reads nothing. What a step copies follows what
#    the call says of its lengths:
#      - each example a length of its own (no `q_start`: cross-attention):
#        one example a grid step, its ceil(length / block) blocks of
#        `_ROWS_BLOCK` rows of K and of V, a copy a block;
#      - one length for all (`q_start`: the cache behind a decode step or
#        a verify block, where no row sees a key at or past q_start + Sq):
#        `_rows_group` examples a grid step, and of each the first
#        ceil((q_start + Sq) / 16) tiles of `_ROWS_TILE` rows and no more.
#        A copy's size is static, so the tiles go as whole blocks and then
#        one copy of 64, 32 and 16 rows for each bit of what is left; a
#        copy takes its rows of ALL the group's examples (one strided
#        copy: the cache is (L, B, S, F)), so a group costs the copies one
#        example would, all started before the first is waited for.
#    The online softmax advances by blocks of `_ROWS_BLOCK` rows under
#    the mask either way, block by block, the group's examples side by
#    side in one batched product a side (each example's own arithmetic;
#    the MXU's passes of one example behind the other's, not each
#    waiting for its own result). Over a cache the last block's two
#    products take the copied tiles alone (a static size a case, 16 to
#    112 rows): a key past them would weigh zero, so the result is the
#    whole block's to the bit, and the MXU, which takes a block as its
#    weights whatever the rows that count (16 passes an example a block:
#    as long as the block's bytes take at 819 GB/s), is not handed the
#    rows nobody wrote. The heads never leave their lanes: the query rows
#    go in block-diagonal, (H * Sq, H * D) with head h's query on head
#    h's lanes and zeros elsewhere, so ONE matmul against a block gives
#    every head's scores and one against V every head's output (a zero
#    adds nothing, in any precision), and the MXU takes K and V as they
#    are.
#  * elsewhere `attention_reference` over the same rows split into heads.
#
# `attention_rows()` dispatches on the shapes it sees.

_ROWS_BLOCK = 128  # key rows a product takes, and the most a copy moves
_ROWS_TILE = 16    # the fewest a copy moves: a tile of bfloat16 rows
_ROWS_GROUP = 4    # the most examples a grid step over a cache holds


def rows_block(seq_len: int) -> int:
    """Key rows `attention_rows` reads at a time at this sequence length
    (what a model counts its cross-attention's reads in)."""
    return min(_ROWS_BLOCK, seq_len)


def rows_copied(length, seq_len: int):
    """Key rows `attention_rows` copies of an example's `seq_len` for a
    read over a cache (`q_start`) whose last query row sees `length` keys:
    whole tiles (what a model counts its self-attention's reads in).
    `length` an int or an array of them."""
    return np.minimum(-(-length // _ROWS_TILE) * _ROWS_TILE, seq_len)


def _rows_tail_sizes(block: int) -> list[int]:
    """The copy sizes that make up any whole number of tiles under a
    block, largest first: 64, 32, 16 rows at a block of 128."""
    return [_ROWS_TILE << bit for bit in reversed(range(
        (block // _ROWS_TILE - 1).bit_length()))]


def _rows_kernel(len_ref, qstart_ref, layer_ref, *refs, scale: float,
                 heads: int, block: int, sq: int, batch: int,
                 has_bias: bool, causal: bool):
    """A group of examples a grid cell (one without `causal`): online
    softmax over the blocks their lengths need, every head and every
    example of the group at once, block by block. Row h * Sq + r of the
    scratch is head h's query row r. Refs: lengths (B,), q_start (1,) and
    the layer (1,) in SMEM; bias (H * Sq, S) float32, the same for every
    example, where there is one; q (G, Sq, H * D); K and V whole in HBM;
    o (G, Sq, H * D); two slots of a group's K and V rows, their copies'
    semaphores, and for each example of the group its block-diagonal
    query rows and the softmax's running (max, denominator, weighted
    sum)."""
    if has_bias:
        bias_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, qd_ref, m_ref, l_ref,
     acc_ref) = refs
    step, slot = pl.program_id(0), pl.program_id(0) % 2
    _, group, s, _ = kbuf.shape
    _, rows, f = acc_ref.shape
    sq_p = rows // heads
    layer = layer_ref[0]
    tail_sizes = _rows_tail_sizes(block)
    # With `causal` no query row sees a key at or past q_start + Sq,
    # whatever the lengths say: the rows a copy need bring and a product
    # take, in tiles: `whole` blocks and `rest` rows of the next.
    seen = jnp.minimum(qstart_ref[0] + sq, s)
    tiles = (seen + _ROWS_TILE - 1) // _ROWS_TILE * _ROWS_TILE
    whole, rest = tiles // block, tiles % block

    def copies(of, into, at, piece):
        """[(whether group `of` is that, K's and V's copy)] of rows `at`
        of every example of the group, one strided copy a side: the
        group is whole, or the batch's last few."""
        return [(ours, [pltpu.make_async_copy(
                            hbm.at[layer, pl.ds(of * group, count), at],
                            buf.at[into, pl.ds(0, count), at],
                            sem.at[side, into, piece])
                        for side, (hbm, buf) in enumerate(
                            ((k_hbm, kbuf), (v_hbm, vbuf)))])
                for count, ours in ((group, of < batch // group),
                                    (batch % group, of == batch // group))
                if count]

    def block_copies(of, into, blk):
        return copies(of, into, pl.ds(pl.multiple_of(blk * block, block),
                                      block), blk)

    def tail_copies(of, into, size):
        """The `size` rows that are one bit of `rest`."""
        return copies(of, into, pl.ds(pl.multiple_of(
            whole * block + rest // (2 * size) * (2 * size), size), size),
            s // block + tail_sizes.index(size))

    def each(pairs, do, needed=True):
        for ours, pair in pairs:
            @pl.when(jnp.logical_and(ours, needed))
            def _():
                for copy in pair:
                    do(copy)

    def begin(copy):
        copy.start()

    def wait(copy):
        copy.wait()  # servelint: blocks a DMA's semaphore on the device

    def start(of, into):
        """What group `of` needs: with `causal` the first `tiles` rows of
        each example, else (a group of one) the example's own blocks."""
        for blk in range(s // block):
            each(block_copies(of, into, blk), begin,
                 blk < whole if causal else blk * block < len_ref[of])
        if causal:
            for size in tail_sizes:
                each(tail_copies(of, into, size), begin,
                     (rest & size) != 0)

    @pl.when(step == 0)
    def _first():
        start(0, 0)

    @pl.when(step + 1 < pl.num_programs(0))
    def _ahead():
        start(step + 1, 1 - slot)

    row = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
    # Whether a lane belongs to the head of a query row.
    first = (row // sq_p) * (f // heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, rows, f), 2)
    own = jnp.logical_and(lane >= first, lane < first + f // heads)
    # The keys a query row may see: its example's, and with `causal` none
    # past its own position (row r of a block sits at q_start + r).
    held = jax.lax.broadcasted_iota(jnp.int32, (group, 1, 1), 0)
    limit = sum(jnp.where(held == g, len_ref[step * group + g], 0)
                for g in range(group))
    if causal:
        limit = jnp.minimum(limit, qstart_ref[0] + row % sq_p + 1)

    # Every example of the group at once from here on, (G, ...): a
    # product a side for each, one after the other on the MXU.
    q = q_ref[...].astype(jnp.float32)                       # (G, Sq, H * D)
    q = (jnp.broadcast_to(q, (group, rows, f)) if sq_p == 1
         else jnp.concatenate([q] * heads, axis=1))
    qd_ref[...] = jnp.where(own, q, 0.0).astype(qd_ref.dtype)  # block-diagonal
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(blk, size, last=False):
        """One step of the online softmax, over the first `size` rows of
        block `blk`; the `last` one writes the output."""
        at = pl.ds(pl.multiple_of(blk * block, block), size)
        scores = jax.lax.dot_general(
            qd_ref[...], kbuf[slot, :, at, :], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (G, rows, size)
        if has_bias:
            scores = scores + bias_ref[:, at]
        ki = blk * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, size), 2)
        scores = jnp.where(ki < limit, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=2, keepdims=True))
        p = jnp.exp(scores - m_new)
        correction = jnp.exp(m_prev - m_new)
        l = correction * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot, :, at, :],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (G, rows, H * D)
        if last:
            finish(m_new, l, acc)
        else:
            m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

    def finish(m, l, acc):
        # A row that met no key (length 0) never left NEG_INF: zeros. Each
        # lane keeps its own head's row.
        out = jnp.where(jnp.logical_and(own, m > NEG_INF * 0.5),
                        acc / jnp.where(l == 0.0, 1.0, l), 0.0)
        out = (jnp.sum(out, axis=1, keepdims=True) if sq_p == 1
               else jnp.sum(out.reshape(group, heads, sq_p, f), axis=1))
        o_ref[...] = out.astype(o_ref.dtype)

    def from_scratch():
        finish(m_ref[...], l_ref[...], acc_ref[...])

    def whole_block(blk, _):
        each(block_copies(step, slot, blk), wait)
        accumulate(blk, block)

    if not causal:
        jax.lax.fori_loop(0, (len_ref[step] + block - 1) // block,
                          whole_block, None)
        from_scratch()
        return
    # Every example's keys end at `seen`: the blocks that are whole, then
    # the `rest` rows of the last, a product of just those rows (a masked
    # key weighs zero, in any precision: what the whole block would give,
    # to the bit) that goes on to the output.
    jax.lax.fori_loop(0, whole, whole_block, None)
    for tail in range(_ROWS_TILE, block, _ROWS_TILE):
        @pl.when(rest == tail)
        def _(tail=tail):
            for size in tail_sizes:
                if tail & size:
                    each(tail_copies(step, slot, size), wait)
            accumulate(whole, tail, last=True)

    pl.when(rest == 0)(from_scratch)


def _rows_query_rows(sq: int) -> int:
    """Query rows as `_rows_kernel` sees them: one, or whole sublane
    tiles."""
    return 1 if sq == 1 else -(-sq // 8) * 8


def _rows_step_bytes(group: int, sq: int, s: int, f: int, itemsize: int,
                     num_heads: int, has_bias: bool) -> int:
    """VMEM a grid step of `_rows_kernel` takes with `group` examples:
    two slots of their K and V rows; for each example its block-diagonal
    query rows, the float32 (max, denominator, weighted sum) and the
    body's temporaries, each as wide as those query rows; the bias."""
    rows = num_heads * _rows_query_rows(sq)
    return (4 * group * s * f * itemsize
            + group * rows * (f * (itemsize + 4 * 4)
                              + 4 * (3 * max(rows_block(s), 128) + 2 * 128))
            + (2 * 4 * rows * s if has_bias else 0))


def _rows_group(b: int, sq: int, s: int, f: int, itemsize: int,
                num_heads: int, has_bias: bool) -> int:
    """Examples a grid step of a read over a cache holds: as many as
    `_ROWS_GROUP`, the batch and the step's VMEM allow, and one at the
    least (what `_rows_kernel_applies` admits)."""
    return max([1] + [
        group for group in range(2, min(_ROWS_GROUP, b) + 1)
        if _rows_step_bytes(group, sq, s, f, itemsize, num_heads, has_bias)
        <= _ROWS_GROUP_VMEM_BYTES])


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "scale", "block", "interpret"))
def rows_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    num_heads: int,
    scale: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    q_start: Optional[jax.Array] = None,
    block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas attention of a few query rows over dense rows: q (B, Sq,
    H * D), k and v (B, S, H * D) — or, with `layer`, that layer's (B, S,
    H * D) of a stack (L, B, S, H * D), picked by the kernel's own copies
    and never sliced out (a traced scalar, so that a model's layers share
    ONE traced and lowered kernel) — with S a multiple of `block` (default
    `rows_block(S)`), lengths (B,) valid key counts. `bias` (1, H, Sq, S)
    is added after scaling, the same for every example (T5's relative
    position bias). Without `q_start` every query row sees the example's
    `length` keys (cross-attention); with it, a scalar, row r sits at
    position q_start + r and sees keys < min(length, q_start + r + 1)
    (a decode step or a verify block over the cache behind it), and no
    row of K or V at or past the tile that holds q_start + Sq - 1 is
    read. Returns (B, Sq, H * D) in q.dtype; an example of length 0 gives
    zeros."""
    b, sq, f = q.shape
    if layer is None:
        k, v, layer = k[None], v[None], 0
    s = k.shape[2]
    if scale is None:
        scale = 1.0 / float(np.sqrt(f // num_heads))
    if block is None:
        block = rows_block(s)
    sq_p = _rows_query_rows(sq)
    rows = num_heads * sq_p
    group = 1 if q_start is None else _rows_group(
        b, sq, s, f, k.dtype.itemsize, num_heads, bias is not None)
    steps = -(-b // group)

    def q_index(step, lens, start, of_layer):
        return (step, 0, 0)

    in_specs = [pl.BlockSpec((group, sq_p, f), q_index),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    # (query rows and lengths of whole groups: what the last group's
    # spare examples give is cut off below)
    operands = [_pad_to(_pad_to(q, 1, sq_p), 0, steps * group), k, v]
    if bias is not None:
        # Rows in the scratch's order, (head, query row); whole in VMEM
        # at every step, so it is fetched once.
        bias_f = _pad_to(bias.astype(jnp.float32).reshape(num_heads, sq, s),
                         1, sq_p).reshape(rows, s)
        in_specs.insert(0, pl.BlockSpec((rows, s),
                                        lambda step, *scalars: (0, 0)))
        operands.insert(0, bias_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # lengths, q_start, layer
        grid=(steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((group, sq_p, f), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, group, s, f), k.dtype),
            pltpu.VMEM((2, group, s, f), v.dtype),
            pltpu.SemaphoreType.DMA(
                (2, 2, s // block + len(_rows_tail_sizes(block)))),
            pltpu.VMEM((group, rows, f), k.dtype),
            pltpu.VMEM((group, rows, 1), jnp.float32),
            pltpu.VMEM((group, rows, 1), jnp.float32),
            pltpu.VMEM((group, rows, f), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _rows_kernel, scale=scale, heads=num_heads, block=block, sq=sq,
            batch=b, has_bias=bias is not None, causal=q_start is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((steps * group, sq_p, f), q.dtype),
        # in order: a step starts the copies the next one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_ROWS_VMEM_LIMIT_BYTES if group > 1 else None),
        interpret=interpret,
        name="_rows_kernel",  # the device-trace reduction finds it by name
    )(_pad_to(lengths.astype(jnp.int32), 0, steps * group),
      jnp.reshape(0 if q_start is None else q_start, (1,)).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)
    return out[:b, :sq, :]


def _rows_kernel_applies(q: jax.Array, k: jax.Array, num_heads: int,
                         bias: Optional[jax.Array] = None) -> bool:
    """The shapes `_rows_kernel` compiles for: rows of whole 128-lane
    tiles, key rows in whole blocks of whole tiles, a bias (if any) that
    every example shares, and a step of one example inside VMEM
    (`_rows_step_bytes`; a read over a cache then takes as many examples
    a step as `_rows_group` finds room for). One device only, as the
    paged read."""
    _, sq, f = q.shape
    s = k.shape[-2]
    block = rows_block(s)
    return (f % 128 == 0
            and block % _ROWS_TILE == 0
            and s % block == 0
            and (bias is None or bias.shape[0] == 1)
            and _rows_step_bytes(1, sq, s, f, k.dtype.itemsize, num_heads,
                                 bias is not None) <= _PAGED_STEP_VMEM_BYTES
            and not _auto_mesh_axes())


def attention_rows(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    num_heads: int,
    scale: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    q_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention of q's few query rows (B, Sq, H * D) over K and V kept
    as rows (B, S, H * D) — with `layer`, that layer's of a stack (L, B,
    S, H * D) — each example over its first `lengths` keys, with `q_start`
    causally from there (`rows_flash_attention` says how): the Pallas read
    on a TPU for every shape it is written for (`_rows_kernel_applies`),
    `attention_reference` over the same rows split into heads otherwise.
    Returns (B, Sq, H * D)."""
    if _on_tpu() and _rows_kernel_applies(q, k, num_heads, bias):
        return rows_flash_attention(
            q, k, v, lengths, num_heads=num_heads, scale=scale, layer=layer,
            bias=bias, q_start=q_start)
    if layer is not None:
        k, v = k[layer], v[layer]

    def heads(x):
        b, s, f = x.shape
        return x.reshape(b, s, num_heads, f // num_heads).transpose(
            0, 2, 1, 3)

    out = attention_reference(
        heads(q), heads(k), heads(v), lengths=lengths, bias=bias,
        scale=scale, causal=q_start is not None, causal_offset=q_start)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


# -- one query row a head over a latent cache, written where it lies ----------
#
# A decode step of latent attention (models/latent.py:absorbed_attention)
# attends ONE K/V head whose keys are the cache's rows (rank + rope lanes)
# and whose values are those rows' first `rank` lanes, every query head
# over the same rows: the heads are the M side of one product a block.
# The step also writes the token's own row, at the example's own position.
# Left to XLA, the row's scatter and a read of all S rows whatever the
# lengths were half of a decode step with a cache at every layer, 3.4 ms
# of read and in some programs 2.7 of whole-cache copies where the bytes
# need 2.2 (PERF.md section 6, PR 55).
# `latent_step_attention` — Pallas kernel — owns the cache for the call:
#
#  * the cache stays in HBM (`pl.ANY`) and IS the output
#    (`input_output_aliases`): XLA neither copies nor stages it;
#  * a grid step is one example: its ceil(length / block) blocks of
#    `_LATENT_BLOCK` rows and no others come into VMEM by hand, a copy a
#    block, in groups of `_LATENT_GROUP` blocks that lie side by side in
#    one of two slots: the next group (or the next example's first) on its
#    way while this one's two products run; an example of length 0 (a row
#    that pads the batch) reads nothing, writes nothing and gives zeros;
#  * the step's row (B, 1, width) comes in through VMEM. It belongs at
#    position length - 1, in the example's LAST block: once that block is
#    in VMEM the row is put into the 16-row tile that holds the position
#    (bfloat16 packs two rows a sublane, so one row is no aligned copy),
#    the attention reads it from there, and that one tile goes back to
#    HBM: the cache changes in that row and no other;
#  * a group is read once for both products: scores = q (heads, width)
#    against its rows, values = the weights against their first `rank`
#    lanes. Scores, the online softmax and the accumulation in float32,
#    the weights cast to the cache's dtype for the value product. One
#    softmax step a GROUP, not a block: a step is a chain (product, max,
#    exp, sum, product) that the next one waits for, 0.6 us whatever the
#    rows under it (PERF.md section 6, PR 55), so the rows of several
#    blocks go through it together; the last group's products take the
#    blocks that were copied and no others (a static size a case).

_LATENT_BLOCK = 128  # positions a copy moves: what a read by length is cut to
_LATENT_GROUP = 4    # the most blocks one step of the softmax takes


def latent_rows_copied(length, seq_len: int, block: int = _LATENT_BLOCK):
    """Cache rows `latent_step_attention` brings in for a step over
    `length` rows of an example's `seq_len` (the step's own row among
    them): whole blocks (what a model counts its latent reads in).
    `length` an int or an array of them, traced or not."""
    return jnp.minimum(-(-length // block) * block, seq_len)


def _latent_step_kernel(len_ref, q_ref, row_ref, cache_hbm, o_ref, cache_out,
                        buf, sem, back_sem, first_ref, *, scale: float,
                        rank: int, block: int, group: int, batch: int):
    """One example a grid step. Refs: lengths (B,) in SMEM (the rows the
    query sees, its own among them; 0: a row nobody owns); q (heads,
    width); the step's row (1, width); the cache (B, 1, S, width) in HBM,
    in and out the same buffer; o (heads, rank); two slots of a group's
    blocks, a semaphore a block and the tile's on its way back; the slot
    this example's first group is in (SMEM, carried from step to step:
    an example's groups alternate from there)."""
    b = pl.program_id(0)
    n = len_ref[b]
    blocks = (n + block - 1) // block
    ahead = jnp.minimum(b + 1, batch - 1)
    blocks_ahead = jnp.where(b + 1 < batch,
                             (len_ref[ahead] + block - 1) // block, 0)

    def copies(example, of_blocks, grp, slot):
        """[(whether the example has that block, its copy)] of group
        `grp` of an example of `of_blocks` blocks, into `slot`."""
        return [(grp * group + j < of_blocks, pltpu.make_async_copy(
                    cache_hbm.at[example, 0, pl.ds(pl.multiple_of(
                        (grp * group + j) * block, block), block)],
                    buf.at[slot, pl.ds(j * block, block)], sem.at[slot, j]))
                for j in range(group)]

    def each(pairs, do):
        for there, copy in pairs:
            pl.when(there)(functools.partial(do, copy))

    def begin(copy):
        copy.start()

    def wait(copy):
        copy.wait()  # servelint: blocks a DMA's semaphore on the device

    @pl.when(b == 0)
    def _():
        first_ref[0] = 0
        each(copies(0, blocks, 0, 0), begin)

    first = first_ref[0]
    q = q_ref[...]

    def attend(slot, carry, count, limit=None):
        """One step of the online softmax over the first `count` blocks
        in `slot`, their first `limit` rows where one is given."""
        m_prev, l_prev, acc = carry
        rows = buf[slot, :count * block, :]
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (heads, rows)
        if limit is not None:
            at = jax.lax.broadcasted_iota(jnp.int32, (1, count * block), 1)
            scores = jnp.where(at < limit, scores, NEG_INF)
        m = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m)
        correction = jnp.exp(m_prev - m)
        return (m, correction * l_prev + jnp.sum(p, axis=1, keepdims=True),
                acc * correction + jnp.dot(
                    p.astype(rows.dtype), rows[:, :rank],
                    preferred_element_type=jnp.float32))

    def whole_group(grp, carry):
        slot = (first + grp) % 2
        each(copies(b, blocks, grp + 1, 1 - slot), begin)
        for _, copy in copies(b, blocks, grp, slot):
            wait(copy)
        return attend(slot, carry, group)

    @pl.when(n == 0)
    def _():
        each(copies(ahead, blocks_ahead, 0, first), begin)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _():
        heads = q.shape[0]
        last = (blocks - 1) // group       # the group of the last block
        carry = jax.lax.fori_loop(0, last, whole_group, (
            jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, rank), jnp.float32)))
        slot = (first + last) % 2
        each(copies(ahead, blocks_ahead, 0, 1 - slot), begin)
        each(copies(b, blocks, last, slot), wait)
        # The step's row into the tile that holds its position, in VMEM,
        # and that tile back to the cache.
        at = n - 1 - last * group * block
        tile = pl.multiple_of(at // _ROWS_TILE * _ROWS_TILE, _ROWS_TILE)
        held = buf[slot, pl.ds(tile, _ROWS_TILE), :]
        mine = jax.lax.broadcasted_iota(
            jnp.int32, (_ROWS_TILE, 1), 0) == at - tile
        buf[slot, pl.ds(tile, _ROWS_TILE), :] = jnp.where(
            mine, row_ref[...].astype(jnp.float32),
            held.astype(jnp.float32)).astype(held.dtype)
        back = pltpu.make_async_copy(
            buf.at[slot, pl.ds(tile, _ROWS_TILE)],
            cache_out.at[b, 0, pl.ds(pl.multiple_of(
                last * group * block + tile, _ROWS_TILE), _ROWS_TILE)],
            back_sem)
        back.start()
        for count in range(1, group + 1):
            @pl.when(blocks - last * group == count)
            def _(count=count):
                m, l, acc = attend(slot, carry, count, limit=at + 1)
                o_ref[...] = (acc / l).astype(o_ref.dtype)

        wait(back)
        first_ref[0] = 1 - slot


def _latent_step_vmem_bytes(heads: int, width: int, rank: int, rows: int,
                            itemsize: int) -> int:
    """VMEM `_latent_step_kernel` takes with `rows` positions a group:
    two slots of them; q, the step's row and o in the two buffers a
    call's operands get; the float32 scores, weights and weighted sum
    and what the body keeps beside them."""
    return (2 * rows * width * itemsize
            + 2 * (heads * width + 16 * width + heads * rank) * itemsize
            + 4 * heads * (3 * rank + 4 * max(rows, 128))
            + 4 * rows * width)


@functools.partial(jax.jit, static_argnames=(
    "rank", "scale", "block", "interpret"))
def latent_step_attention(q: jax.Array, row: jax.Array, cache: jax.Array,
                          lengths: jax.Array, *, rank: int, scale: float,
                          block: int = _LATENT_BLOCK,
                          interpret: bool = False):
    """A decode step's latent attention as ONE Pallas call: q (B, heads,
    width) the absorbed queries, row (B, width) the step's own latent
    rows, cache (B, 1, S, width) with S whole blocks, lengths (B,) the
    rows each query sees, its own (written at lengths - 1) the last of
    them; 0 for a row nobody owns; `block` the positions a copy takes
    (whole tiles of 16). -> (o (B, heads, rank) in q's dtype:
    softmax(scale q . rows) over the rows' first `rank` lanes, zeros at
    length 0; the cache with each row written, in the input's buffer)."""
    b, heads, width = q.shape
    s = cache.shape[2]
    group = _LATENT_GROUP
    by_example = lambda i, lens: (i, 0, 0)  # noqa: E731
    o, cache = pl.pallas_call(
        functools.partial(_latent_step_kernel, scale=scale, rank=rank,
                          block=block, group=group, batch=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # lengths
            grid=(b,),
            in_specs=[pl.BlockSpec((None, heads, width), by_example),
                      pl.BlockSpec((None, 1, width), by_example),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, heads, rank), by_example),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((2, group * block, width), cache.dtype),
                pltpu.SemaphoreType.DMA((2, group)),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        # operand 3: the prefetched lengths, q and the row come first
        input_output_aliases={3: 1},
        # in order: a step starts the copies the next one waits for. The
        # limit is the kernel's own account and 4 MiB for what Mosaic
        # keeps: XLA plans what IT moves into VMEM around a call by the
        # limit the call names (parallel/moe.py:expert_walk_kernel)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_latent_step_vmem_bytes(
                heads, width, rank, group * block, cache.dtype.itemsize)
            + (4 << 20)),
        interpret=interpret,
        name="_latent_step_kernel",  # the device-trace reduction finds it
    )(jnp.minimum(lengths.astype(jnp.int32), s), q,
      row.astype(cache.dtype)[:, None], cache)
    return o, cache


def _latent_step_applies(q: jax.Array, cache: jax.Array, rank: int) -> bool:
    """The shapes `_latent_step_kernel` compiles for, read from the
    shapes alone: one K/V head, rows and their first `rank` lanes (the
    values) both whole 128-lane tiles (a copy takes a row at tile
    borders only), the cache's positions whole blocks, whole sublane
    tiles of heads, one dtype, and a step inside VMEM. One device only,
    as the other reads."""
    _, heads, width = q.shape
    block = _LATENT_BLOCK
    return (cache.ndim == 4 and cache.shape[1] == 1
            and cache.shape[3] == width and cache.dtype == q.dtype
            and width % 128 == 0 and rank % 128 == 0 and rank <= width
            and heads % 8 == 0
            and cache.shape[2] % block == 0
            and _latent_step_vmem_bytes(
                heads, width, rank, _LATENT_GROUP * block,
                cache.dtype.itemsize) <= _PAGED_STEP_VMEM_BYTES
            and not _auto_mesh_axes())


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Scoped on-chip memory the kernels are written against (TPU v5e: 1 MiB
# of SMEM less what the program itself uses; half of the 16 MiB scoped
# VMEM for resident K/V or for one paged step, the rest for Q/O blocks,
# f32 temporaries and what the compiler keeps). Both kernels AOT-compile
# for "TPU v5 lite" at these bounds.
_PAGED_SMEM_BYTES = (1 << 20) - (16 << 10)
_FLASH_KV_VMEM_BYTES = 8 << 20
_PAGED_STEP_VMEM_BYTES = 8 << 20
# A read over a cache (`_rows_kernel` with `q_start`) holds several
# examples a step: an account of its own, half of the limit its call names.
_ROWS_GROUP_VMEM_BYTES = 12 << 20
_ROWS_VMEM_LIMIT_BYTES = 2 * _ROWS_GROUP_VMEM_BYTES


def _paged_kernel_applies(q: jax.Array, k_pages: jax.Array,
                          block_tables: jax.Array) -> bool:
    """The shapes `_paged_kernel` compiles for, with or without bias, at
    any table width: an arena whose rows are the query's H * D lanes,
    head dim and page rows on sublane multiples (any such page size — the
    Q and bias blocks span their arrays' last two dims, the K/V block a
    whole page's rows), a head group whose lanes are whole 128-lane tiles
    or the whole row with its step inside VMEM (`_paged_head_group` folds
    as many heads into a step as fit), and the scalar-prefetched operands
    — the flat (B * P) table plus lengths and q_start — inside SMEM. One
    device only: decode pools carry no mesh, and a paged read under a
    serving mesh (speculative verify in a sharded export) takes the
    reference."""
    b, max_pages = block_tables.shape
    _, h, sq, d = q.shape
    _, block_size, f = k_pages.shape
    return (f == h * d
            and d % 8 == 0
            and block_size % 8 == 0
            and _paged_head_group(h, sq, d, block_size,
                                  k_pages.dtype.itemsize) > 0
            and (b * max_pages + 2 * b) * 4 <= _PAGED_SMEM_BYTES
            and not _auto_mesh_axes())


def _flash_kernel_applies(q: jax.Array, k: jax.Array,
                          v: Optional[jax.Array] = None) -> bool:
    """The shapes `_flash_kernel` compiles for: head dims on a sublane
    multiple, query heads a multiple of the K/V heads, at least one
    sublane tile of query rows, and one (batch, K/V head)'s whole K and
    V — double-buffered, lanes padded to 128 — resident in VMEM."""
    d, d_v = q.shape[-1], (k if v is None else v).shape[-1]
    skv_p = -(-k.shape[-2] // _BLOCK_KV) * _BLOCK_KV
    lanes = -(-d // 128) * 128 + -(-d_v // 128) * 128
    kv_bytes = 2 * skv_p * lanes * k.dtype.itemsize
    return (d % 8 == 0 and d_v % 8 == 0 and q.shape[-2] >= 8
            and q.shape[1] % k.shape[1] == 0
            and kv_bytes <= _FLASH_KV_VMEM_BYTES)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal_offset: Optional[int] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    queries_ragged: bool = False,
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU for every shape it is written for
    (`_flash_kernel_applies`, no additive bias), jnp reference otherwise.
    Semantics identical."""
    use_pallas = (
        _on_tpu()
        and bias is None
        and _flash_kernel_applies(q, k, v)
        # The kernel takes causal_offset as a static arg; a traced offset
        # (speculative verify blocks at a dynamic step) uses the
        # reference path.
        and isinstance(causal_offset, (int, type(None)))
    )
    if use_pallas:
        return flash_attention(
            q, k, v, causal=causal, lengths=lengths, scale=scale,
            causal_offset=causal_offset, window=window, sink=sink,
            queries_ragged=queries_ragged)
    return attention_reference(
        q, k, v, causal=causal, lengths=lengths, bias=bias, scale=scale,
        causal_offset=causal_offset, window=window, sink=sink,
        queries_ragged=queries_ragged)
