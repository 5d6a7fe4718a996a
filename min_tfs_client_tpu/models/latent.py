"""Latent attention (MLA), once, for every decoder that runs it
(models/ling_hybrid.py, models/xing.py): keys and values compressed to
ONE latent a position beside one rotary key all heads share, which is
all a cache holds. What is here is the mechanism and takes its sizes and
its `scale=` as arguments; a model file keeps what is its own (its query
projection, its gate if it has one, its out-projection).

Two forms of one arithmetic, held to each other by a test
(tests/unit/test_latent.py): `decompressed_attention` (the prefill: K and
V decompressed from a chunk's latents, then ops/attention.py) and
`absorbed_attention` (a decode step: the up-projection's key half folded
into the query, which attends IN THE LATENT SPACE and never decompresses
the cache).

The rotary takes its frequencies as a law, `inv_freq(half) -> (half,)`:
`plain_frequencies(theta)` or `yarn_frequencies(...)`.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.ops.attention import (
    _latent_step_applies,
    _on_tpu,
    attention,
    latent_rows_copied,
    latent_step_attention,
)


# -- rotary -------------------------------------------------------------------


def plain_frequencies(theta: float) -> Callable:
    """theta ** (-i / half) for pair i of `half`."""
    return lambda half: theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)


def yarn_frequencies(theta: float, *, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> Callable:
    """YaRN's blend (DeepSeek-V3's reading): a pair that turns more than
    `beta_fast` times over the `original` positions keeps its frequency,
    one that turns fewer than `beta_slow` times has it divided by
    `factor`, and between the two correction dimensions a linear ramp
    mixes them. Computed once, in numpy: a constant of the program."""

    def correction_dim(turns: float, dim: int) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    def inv_freq(half: int) -> jax.Array:
        dim = 2 * half
        plain = theta ** (-np.arange(half, dtype=np.float64) / half)
        low = max(math.floor(correction_dim(beta_fast, dim)), 0)
        high = min(math.ceil(correction_dim(beta_slow, dim)), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / max(high - low, 0.001), 0.0, 1.0)
        return jnp.asarray(plain / factor * ramp + plain * (1.0 - ramp),
                           jnp.float32)

    return inv_freq


def yarn_mscale(factor: float, mscale: float) -> float:
    """What YaRN multiplies the attention's temperature by (1 where
    nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x: jax.Array, positions: jax.Array, inv_freq: Callable) -> jax.Array:
    """Rotary embedding over ALL of x's last dim, INTERLEAVED pairs (dim
    2i with dim 2i + 1). x (T, ..., R) float32; positions (T,);
    `inv_freq(R // 2)` the pairs' inverse frequencies."""
    half = x.shape[-1] // 2
    inv = inv_freq(half)
    angle = positions.astype(jnp.float32).reshape(
        -1, *(1,) * (x.ndim - 1)) * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pair = x.reshape(*x.shape[:-1], half, 2)
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def rotate_query(q: jax.Array, positions: jax.Array, inv_freq: Callable,
                 nope: int) -> jax.Array:
    """q (T, heads, nope + rope): its rope lanes rotated."""
    return jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, inv_freq)], axis=-1)


def cache_width(width: int) -> int:
    """Lanes a cached position takes for its `width` = rank + rope values:
    whole 128-lane tiles, zeros past the values (576 -> 640). The chip
    holds an array's rows in whole tiles whatever their width, so the
    padding is what a cache of 576 lanes already took of its memory and
    of a read; said in the shape, a block of rows is a copy a kernel can
    make (Mosaic slices a row at tile borders only)."""
    return -(-width // 128) * 128


def latent_row(kva: jax.Array, kv_norm: dict, positions: jax.Array,
               inv_freq: Callable, *, rank: int, eps: float) -> jax.Array:
    """What the cache holds of a position: kva (T, rank + rope), the
    down-projection's output -> the latent after its norm and the ONE
    rotated key all heads share, side by side, then zeros to whole lane
    tiles: (T, cache_width(rank + rope))."""
    width = kva.shape[-1]
    return jnp.concatenate([
        nn.rms_norm(kv_norm, kva[:, :rank], eps=eps),
        rope(kva[:, rank:], positions, inv_freq),
        jnp.zeros((kva.shape[0], cache_width(width) - width), jnp.float32)],
        axis=-1)


# -- the two forms ------------------------------------------------------------


def decompressed_attention(kvb: jax.Array, q: jax.Array, rows: jax.Array,
                           lengths: jax.Array, *, nope: int, v_head_dim: int,
                           scale: float) -> jax.Array:
    """Latent attention as the prefill runs it: q (b, S, heads, nope +
    rope) and the latent rows (b, S, rank + rope, or wider: `latent_row`)
    of `lengths` real positions; K and V decompressed by `kvb` (rank,
    heads x (nope + v)), causal attention over them (ops/attention.py).
    -> (b, S, heads x d_v)."""
    b, s, h, dk = q.shape
    rank = kvb.shape[0]
    kv = nn.mm(rows[..., :rank], kvb, rows.dtype).reshape(
        b, s, h, nope + v_head_dim)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        rows[:, :, None, rank:rank + dk - nope], (b, s, h, dk - nope))],
        axis=-1)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    out = attention(heads_first(q), heads_first(k),
                    heads_first(kv[..., nope:]), causal=True,
                    lengths=lengths, causal_offset=0,
                    scale=scale, queries_ragged=True)
    return heads_first(out).reshape(b, s, -1)


def absorbed_attention(kvb: jax.Array, q: jax.Array, cache: jax.Array,
                       row: jax.Array, position: jax.Array,
                       owned: jax.Array, *, nope: int, v_head_dim: int,
                       scale: float):
    """Latent attention as a decode step runs it: q (B, heads, nope +
    rope) over the latent cache (B, 1, S, width) once the step's own
    `row` (B, width; `latent_row`) lies at each example's `position`
    (B,); `owned` (B,) bool, the rows a request owns. The up-projection's
    key half is absorbed into the query, (B, heads, rank + rope, then
    zeros to the cache's width), which attends the cache as ONE K/V head
    whose values are the keys' first `rank` lanes over the positions up
    to its own; the value half takes the result to the heads' value
    channels. Two bodies of that arithmetic: on a TPU, where
    `_latent_step_applies` admits the shapes, ONE kernel that owns the
    cache (ops/attention.py:latent_step_attention: the row written where
    it lies, an example's own blocks read and no others, nothing for a
    row nobody owns, which gives zeros); elsewhere the row's scatter and
    `layers.attend_cache` over every position. -> (out (B, heads x d_v)
    float32, the cache as it now is, the cache rows brought in for each
    example (B,): whole blocks, or all S)."""
    b, h, rank = q.shape[0], q.shape[1], kvb.shape[0]
    s, width = cache.shape[2:]
    up = kvb.reshape(rank, h, nope + v_head_dim)
    absorbed = jnp.einsum("bhd,rhd->bhr", q[..., :nope], up[..., :nope],
                          preferred_element_type=jnp.float32)
    rope_lanes = q.shape[-1] - nope
    query = jnp.concatenate([
        absorbed.astype(q.dtype), q[..., nope:],
        jnp.zeros((b, h, width - rank - rope_lanes), q.dtype)], axis=-1)
    if _on_tpu() and _latent_step_applies(query, cache, rank):
        lengths = jnp.where(owned, position + 1, 0)
        mixed, cache = latent_step_attention(
            query, row, cache, lengths, rank=rank, scale=scale)
        copied = latent_rows_copied(lengths, s)
    else:
        cache = cache.at[jnp.arange(b), 0, position].set(row)
        seen = jnp.arange(s)[None, :] <= position[:, None]
        mixed = nn.attend_cache(
            query, {"k": cache, "v": cache[..., :rank]}, seen, None,
            scale=scale)
        copied = jnp.full((b,), s, jnp.int32)
    out = jnp.einsum("bhr,rhd->bhd", mixed.reshape(-1, h, rank),
                     up[..., nope:], preferred_element_type=jnp.float32)
    return out.reshape(out.shape[0], -1), cache, copied
