"""Shared neural-net building blocks for the served model families.

Pure-JAX pytree modules (params are nested dicts of jax.Array), designed
for the MXU: matmuls stay large and batched, compute dtype is bfloat16 with
float32 accumulation/normalisation, and every function is jit/pjit-safe
(no Python control flow on traced values). Attention dispatches to the
Pallas flash kernel (ops/attention.py) on TPU.

The reference serves opaque GraphDefs (SURVEY.md §2.6); this framework
additionally ships first-class model families (BERT, T5, ResNet, USE) built
from these blocks, exported as "jax"-platform servables.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.ops.attention import NEG_INF, attention, attention_rows

COMPUTE_DTYPE = jnp.bfloat16


def _split(rng, n):
    return jax.random.split(rng, n)


# -- primitive layers --------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, *, use_bias: bool = True,
               stddev: Optional[float] = None) -> dict:
    if stddev is None:
        stddev = 1.0 / np.sqrt(d_in)
    params = {"kernel": (jax.random.normal(rng, (d_in, d_out), jnp.float32)
                         * stddev)}
    if use_bias:
        params["bias"] = jnp.zeros((d_out,), jnp.float32)
    return params


def dense(params: dict, x: jax.Array) -> jax.Array:
    y = x.astype(COMPUTE_DTYPE) @ params["kernel"].astype(COMPUTE_DTYPE)
    if "bias" in params:
        y = y + params["bias"].astype(COMPUTE_DTYPE)
    return y


def layer_norm_init(dim: int) -> dict:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layer_norm(params: dict, x: jax.Array, *, eps: float = 1e-12) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def rms_norm_init(dim: int) -> dict:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rms_norm(params: dict, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["scale"]).astype(x.dtype)


def embed_init(rng, vocab: int, dim: int, *, stddev: float = 0.02) -> dict:
    return {"embedding": jax.random.normal(rng, (vocab, dim), jnp.float32)
            * stddev}


def embed(params: dict, ids: jax.Array) -> jax.Array:
    return params["embedding"].astype(COMPUTE_DTYPE)[ids]


# -- multi-head attention ----------------------------------------------------


def mha_init(rng, d_model: int, num_heads: int, *, d_kv: Optional[int] = None,
             use_bias: bool = True) -> dict:
    d_head = (d_kv or d_model // num_heads)
    d_inner = num_heads * d_head
    rq, rk, rv, ro = _split(rng, 4)
    return {
        "query": dense_init(rq, d_model, d_inner, use_bias=use_bias),
        "key": dense_init(rk, d_model, d_inner, use_bias=use_bias),
        "value": dense_init(rv, d_model, d_inner, use_bias=use_bias),
        "out": dense_init(ro, d_inner, d_model, use_bias=use_bias),
    }


def heads(x: jax.Array, num_heads: int) -> jax.Array:
    b, s, d = x.shape
    return x.reshape(b, s, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def unheads(x: jax.Array) -> jax.Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def mha(
    params: dict,
    x: jax.Array,
    *,
    num_heads: int,
    kv: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
    causal: bool = False,
    bias: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    seq_mesh=None,
) -> tuple[jax.Array, Optional[dict]]:
    """Multi-head attention over x (self) or x->kv (cross).

    With `cache` ({"k","v"} of (B, H, S_max, D)) and `cache_index`, the new
    K/V rows are written at cache_index and attention runs over the whole
    cache with unwritten slots masked via lengths. Cache modes, all
    jit-safe:
     * prefill: x is the prompt, cache_index 0 — full causal prompt
       attention with queries at absolute positions 0..S;
     * decode: x is one token (S=1), cache_index is its absolute position —
       the single query is the newest position, so masking unwritten slots
       subsumes causality;
     * verify block: x is S>1 tokens at a (possibly traced) cache_index —
       causal within the block at absolute offset cache_index, attending
       the cache behind it (speculative decoding's target pass).
    Returns (output, updated_cache).
    """
    q = heads(dense(params["query"], x), num_heads)
    src = x if kv is None else kv
    k = heads(dense(params["key"], src), num_heads)
    v = heads(dense(params["value"], src), num_heads)

    causal_offset = None
    if cache is not None:
        assert cache_index is not None
        k = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, cache_index, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, cache_index, 0))
        cache = {"k": k, "v": v}
        written = cache_index + x.shape[1]
        if lengths is None:
            lengths = jnp.full((x.shape[0],), written, jnp.int32)
        else:
            lengths = jnp.minimum(lengths, written)
        if x.shape[1] > 1:
            # Prefill (cache_index 0) and speculative verify blocks
            # (cache_index = step): queries sit at absolute positions
            # cache_index .. cache_index + S.
            causal_offset = cache_index
        else:
            causal = False  # decode: lengths masking subsumes causality

    if seq_mesh is not None:
        # Sequence-parallel exact attention: Q/K/V shard on the seq axis
        # of `seq_mesh`, K/V rotate over the ICI ring (ring_attention).
        # Unsupported together with caches/bias (decode uses caches; T5
        # carries a bias) — long-context encoders are the target.
        if cache is not None or bias is not None:
            raise ValueError(
                "seq_mesh attention does not combine with KV caches or "
                "additive bias")
        from min_tfs_client_tpu.parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, mesh=seq_mesh, causal=causal,
                             lengths=lengths, scale=scale)
        return dense(params["out"], unheads(out)), cache
    out = attention(q, k, v, causal=causal, lengths=lengths, bias=bias,
                    scale=scale, causal_offset=causal_offset)
    return dense(params["out"], unheads(out)), cache


def cross_rows(blocks: list[dict], kv: jax.Array) -> dict:
    """K and V of `kv` (B, S, d_model) under each of a stack of attention
    blocks' projections, made once and kept as the projections leave
    them: {"key", "value"} of (L, B, S, H * D) rows, the heads side by
    side on the lanes and never split (split into heads, XLA lays every
    leaf out again), the layers in ONE array each, by one product (a
    leaf a layer is small enough for XLA to stage whole in its fast
    memory ahead of each read, every row of it, whatever the lengths)."""
    def stacked(name):
        rows = jnp.einsum(
            "bsd,ldf->lbsf", kv.astype(COMPUTE_DTYPE),
            jnp.stack([p[name]["kernel"].astype(COMPUTE_DTYPE)
                       for p in blocks]))
        if "bias" in blocks[0][name]:
            rows = rows + jnp.stack(
                [p[name]["bias"].astype(COMPUTE_DTYPE)
                 for p in blocks])[:, None, None, :]
        return rows

    return {"key": stacked("key"), "value": stacked("value")}


def mha_rows(params: dict, x: jax.Array, rows: dict, layer: int, *,
             num_heads: int, lengths: Optional[jax.Array] = None,
             bias: Optional[jax.Array] = None,
             cache_index: Optional[jax.Array] = None,
             scale: Optional[float] = None) -> tuple[jax.Array, dict]:
    """`mha` for a loop that attends the same rows at every step (a whole
    generation's decode steps), over layer `layer` of `rows`, read by
    ops/attention.attention_rows: each example's keys once, in their own
    dtype, by length. Cross-attention: `rows = cross_rows(blocks, kv)`
    and `lengths`, as `mha(kv=, lengths=)`. Self-attention over a cache:
    `rows = init_rows_cache(...)` and `cache_index`, as `mha(cache=,
    cache_index=, causal=True)`: x's K and V rows are written at
    cache_index and each query row sees the rows up to its own. Returns
    (output, rows as they now are)."""
    q_start = None
    if cache_index is not None:
        rows = {name: jax.lax.dynamic_update_slice(
                    rows[name],
                    dense(params[name], x)[None].astype(rows[name].dtype),
                    (layer, 0, cache_index, 0))
                for name in ("key", "value")}
        lengths = jnp.full((x.shape[0],), cache_index + x.shape[1], jnp.int32)
        q_start = cache_index
    out = attention_rows(dense(params["query"], x), rows["key"],
                         rows["value"], lengths, num_heads=num_heads,
                         scale=scale, layer=layer, bias=bias, q_start=q_start)
    return dense(params["out"], out), rows


def init_rows_cache(layers: int, batch: int, max_len: int, width: int,
                    dtype=COMPUTE_DTYPE) -> dict:
    """An empty self-attention cache for `mha_rows`: every layer's K and
    V rows in one array each, (L, B, max_len, H * D). One array, because
    a step writes one row a layer into it where it lies; a dense cache a
    layer that a loop carries and updates, XLA keeps in its fast memory
    and writes back WHOLE at every step (3.3 ms of a 7.4 ms step on the
    v5e: PERF.md section 6, PR 44)."""
    return {name: jnp.zeros((layers, batch, max_len, width), dtype)
            for name in ("key", "value")}


def init_cache(batch: int, num_heads: int, max_len: int, d_head: int,
               dtype=COMPUTE_DTYPE) -> dict:
    return {"k": jnp.zeros((batch, num_heads, max_len, d_head), dtype),
            "v": jnp.zeros((batch, num_heads, max_len, d_head), dtype)}


# -- feed-forward ------------------------------------------------------------


def mlp_init(rng, d_model: int, d_ff: int, *, use_bias: bool = True,
             gated: bool = False) -> dict:
    r1, r2, r3 = _split(rng, 3)
    params = {"wi": dense_init(r1, d_model, d_ff, use_bias=use_bias),
              "wo": dense_init(r2, d_ff, d_model, use_bias=use_bias)}
    if gated:
        params["wg"] = dense_init(r3, d_model, d_ff, use_bias=use_bias)
    return params


def mlp(params: dict, x: jax.Array, *, activation=jax.nn.gelu) -> jax.Array:
    h = activation(dense(params["wi"], x))
    if "wg" in params:
        h = h * dense(params["wg"], x)
    return dense(params["wo"], h)


def lengths_from_mask(mask: jax.Array) -> jax.Array:
    """(B, S) 0/1 attention mask -> (B,) valid lengths. Serving batches are
    right-padded, so a row sum is exact; the flash kernel takes lengths."""
    return jnp.sum(mask.astype(jnp.int32), axis=-1)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


# -- what the decoders served as whole generations share ----------------------


def mm(x: jax.Array, kernel: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """x @ kernel with the operands in the kernel's dtype and float32
    accumulation."""
    return jnp.dot(x.astype(kernel.dtype), kernel,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def attend_cache(q: jax.Array, cache: dict, seen: jax.Array,
                 sink: jax.Array | None,
                 scale: float | None = None) -> jax.Array:
    """One query row a head over a cache, in plain jnp: q (B, H, dk),
    cache k (B, kv, S, dk) / v (B, kv, S, dv), `seen` (B, S) bool the
    rows this example's query may read; scores times `scale` (dk ** -0.5
    where none is given). -> (B, H * dv) in q's dtype."""
    b, h, dk = q.shape
    if scale is None:
        scale = dk ** -0.5
    kv = cache["k"].shape[1]
    scores = jnp.einsum("bngd,bnsd->bngs", q.reshape(b, kv, h // kv, dk),
                        cache["k"], preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None, None, :], scores * scale, NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, kv, h // kv, 1)
        top = jnp.maximum(top, sink)
    weights = jnp.exp(scores - top)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - top)
    weights = (weights / total).astype(cache["v"].dtype)
    out = jnp.einsum("bngs,bnsd->bngd", weights, cache["v"],
                     preferred_element_type=jnp.float32)
    return out.reshape(b, -1).astype(q.dtype)
