"""Ring attention: sequence-parallel exact attention over the ICI ring.

Long-context capability the reference lacks entirely (SURVEY.md §2.11:
SP/CP row "Absent" — its longest dimension machinery is batch padding).
Sequences longer than one chip's HBM budget are sharded along the sequence
axis of the mesh; each device holds one Q/K/V block and the K/V blocks
rotate around the ring with `lax.ppermute` (one ICI hop per step) while a
blockwise online softmax accumulates exact attention — compute and
communication overlap naturally under XLA's async collective scheduling.

This is the shard_map/ppermute formulation of Ring Attention (Liu et al.;
see PAPERS.md) — the TPU-idiomatic replacement for NCCL P2P send/recv the
CUDA implementations use.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from min_tfs_client_tpu.ops.attention import NEG_INF
from min_tfs_client_tpu.parallel.mesh import SEQ_AXIS


def shard_map(fn, **kw):
    """jax.shard_map with the varying-manual-axes check off: the fori_loop
    carry mixes axis-varying (rotating K/V) and invariant arrays."""
    return jax.shard_map(fn, check_vma=False, **kw)


def _block_update(q, k_blk, v_blk, o, m, l, q_pos, k_pos, *, scale,
                  causal, lengths):
    """One online-softmax accumulation step against a rotated K/V block.

    q (B,H,Sq,D); k_blk/v_blk (B,H,Sk,D); o (B,H,Sq,D) f32 accumulator;
    m/l (B,H,Sq) f32 running max / normalizer; q_pos (Sq,), k_pos (Sk,)
    global positions of the local queries and the currently-held keys.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    if lengths is not None:
        # lengths (B,): global valid key count per example.
        keep = k_pos[None, :] < lengths[:, None]          # (B, Sk)
        s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    s = jnp.where(mask[None, None], s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Guard fully-masked history: exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new[..., None]))
    alpha = jnp.where(m <= NEG_INF * 0.5, 0.0, jnp.exp(m - m_new))
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def _ring_shard_fn(q, k, v, lengths, *, axis_name, axis_size, causal, scale):
    """Per-device body under shard_map: local blocks (B,H,S/n,D)."""
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_pos = my * s_local + jnp.arange(s_local)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3], jnp.float32)

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry
        # After i rotations device `my` holds block (my - i) mod n.
        kv_idx = jax.lax.rem(my - i + axis_size, axis_size)
        k_pos = kv_idx * s_local + jnp.arange(s_local)
        o, m, l = _block_update(q, k_blk, v_blk, o, m, l, q_pos, k_pos,
                                scale=scale, causal=causal, lengths=lengths)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk

    o, m, l, _, _ = jax.lax.fori_loop(0, axis_size, body, (o, m, l, k, v))
    return (o / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention with Q/K/V sharded on the sequence dim of `mesh`.

    Shapes: q, k, v (B, H, S, D) with S divisible by mesh.shape[axis_name];
    lengths (B,) int32 global valid key counts (padded serving batches).
    Matches ops.attention.attention_reference numerically.
    """
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by mesh axis "
            f"{axis_name!r} size {n}")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))

    fn = functools.partial(
        _ring_shard_fn, axis_name=axis_name, axis_size=n, causal=causal,
        scale=scale)
    qkv_spec = P(None, None, axis_name, None)
    if lengths is None:
        body = lambda q, k, v: fn(q, k, v, None)
        in_specs = (qkv_spec, qkv_spec, qkv_spec)
        args = (q, k, v)
    else:
        body = fn
        in_specs = (qkv_spec, qkv_spec, qkv_spec, P())
        args = (q, k, v, lengths)
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=qkv_spec)(*args)
