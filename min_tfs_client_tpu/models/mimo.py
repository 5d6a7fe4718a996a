"""A decoder with no encoder, of the MiMo-V2 kind: pre-RMSNorm blocks
whose attention layers are of two kinds in one stack (full causal
attention, and a sliding window with a learned sink logit a head, each
kind with its own number of K/V heads and its own rotary base; fused
q/k/v projection, query/key heads wider than value heads, rotary on the
leading part of a head), and whose feed-forward layers are SwiGLU, dense
in the leading layers and a sigmoid-routed top-k expert layer after
(parallel/moe.py: this chip's share of the experts, dropless).

Served as whole generations on `serving_default` through the
whole-generation front (servables/decode_signatures.generation_signature)
over the decode contract, `prefill(params, ids) -> state` and `step(params,
state) -> (state', token)`, both written over models/packed.py. The state
carries two kinds of cache through one loop: a full-length K/V cache for
each full layer, a ring of `window` rows for each window layer (a position
is written at position mod window), with each example's own length.

Numerics: matrices and their operands in the parameters' dtype
(bfloat16 as served) with float32 accumulation; the residual stream, the
norms, the softmax, the router's scores and the logits in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.models import packed
from min_tfs_client_tpu.ops.attention import attention
from min_tfs_client_tpu.parallel.moe import HeldExperts, held_experts_ffn


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_layers: int = 48
    num_heads: int = 64
    head_dim: int = 192            # of a query and of a key
    v_head_dim: int = 128
    num_kv_heads: int = 4          # full-attention layers
    swa_num_kv_heads: int = 8      # window layers
    # One entry a layer (longer lists are cut to num_layers): 1 = window
    # attention, 0 = full; 1 = expert layer, 0 = dense SwiGLU.
    layer_pattern: tuple = (0, 1, 1, 1, 1, 0)
    moe_pattern: tuple = (0, 1, 1, 1, 1, 1)
    window: int = 128
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256    # the router's width
    experts_held: int = 256        # this chip's share of them ...
    expert_offset: int = 0         # ... starting at this expert
    top_k: int = 8
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    value_scale: float = 0.707
    eps: float = 1e-5
    pad_id: int = 0
    eos_id: int = 1
    dtype: str = "bfloat16"
    # Examples the prefill takes through the stack at a time: bounds its
    # activations (the dense layer's gate/up rows above all).
    prefill_rows: int = 8

    def __post_init__(self):
        for name in ("layer_pattern", "moe_pattern"):
            object.__setattr__(self, name, tuple(
                int(v) for v in getattr(self, name))[:self.num_layers])
            if len(getattr(self, name)) != self.num_layers:
                raise ValueError(f"{name} has fewer entries than layers")
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError("the held experts lie outside the router")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    def kv_heads(self, layer: int) -> int:
        return (self.swa_num_kv_heads if self.layer_pattern[layer]
                else self.num_kv_heads)


# -- parameters ---------------------------------------------------------------


def init_params(rng: jax.Array, config: MimoConfig) -> dict:
    """Leaves in `config.dtype` (the small float32 ones apart: norm
    scales, sinks, the router and its bias). Every projection has unit
    gain (std fan_in ** -0.5), so scores and logits are of unit scale;
    the experts' down projections are sqrt(top_k) times that, so that a
    token's top_k experts, each entering with a weight near 1 / top_k,
    together add what a dense layer adds; the embedding is N(0, 1)."""
    dtype = jnp.dtype(config.dtype)
    d, h = config.hidden_size, config.num_heads

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    keys = iter(jax.random.split(rng, 8 * config.num_layers + 2))
    layers = []
    for index in range(config.num_layers):
        kv = config.kv_heads(index)
        fused = h * config.head_dim + kv * (config.head_dim
                                            + config.v_head_dim)
        attn = {"qkv": {"kernel": normal(next(keys), (d, fused), d ** -0.5)},
                "out": {"kernel": normal(next(keys), (h * config.v_head_dim,
                                                      d),
                                         (h * config.v_head_dim) ** -0.5)}}
        if config.layer_pattern[index]:
            attn["sink"] = jax.random.normal(next(keys), (h,), jnp.float32)
        layer = {"attn_norm": nn.rms_norm_init(d), "attn": attn,
                 "ffn_norm": nn.rms_norm_init(d)}
        if config.moe_pattern[index]:
            f, held = config.moe_intermediate_size, config.experts_held
            layer["moe"] = {
                "router": jax.random.normal(
                    next(keys), (d, config.n_routed_experts),
                    jnp.float32) * d ** -0.5,
                "bias": jax.random.normal(
                    next(keys), (config.n_routed_experts,),
                    jnp.float32) * 0.02,
                "w_in": normal(next(keys), (held, d, 2 * f), d ** -0.5),
                "w_out": normal(next(keys), (held, f, d),
                                (config.top_k / f) ** 0.5)}
        else:
            f = config.intermediate_size
            layer["mlp"] = {
                "wi": {"kernel": normal(next(keys), (d, 2 * f), d ** -0.5)},
                "wo": {"kernel": normal(next(keys), (f, d), f ** -0.5)}}
        layers.append(layer)
    return {"embed": {"embedding": normal(next(keys),
                                          (config.vocab_size, d), 1.0)},
            "layers": layers, "final_norm": nn.rms_norm_init(d),
            "head": {"kernel": normal(next(keys), (d, config.vocab_size),
                                      d ** -0.5)}}


# -- pieces -------------------------------------------------------------------


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          rot: int) -> jax.Array:
    """Rotary embedding on the first `rot` dims of a head, halves paired
    (dim i with dim i + rot / 2). x (..., H, D); positions (...)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, xf[..., rot:]],
        axis=-1).astype(x.dtype)


def _qkv(config: MimoConfig, layer: int, attn: dict, x: jax.Array,
         positions: jax.Array):
    """The fused projection of x (..., D), split into q (..., H, dk),
    k (..., kv, dk) (both rotated at `positions`) and v (..., kv, dv)
    (times the value scale), in the parameters' dtype."""
    h, dk, dv = config.num_heads, config.head_dim, config.v_head_dim
    kv = config.kv_heads(layer)
    dtype = attn["qkv"]["kernel"].dtype
    fused = nn.mm(x, attn["qkv"]["kernel"], dtype)
    lead = fused.shape[:-1]
    q = fused[..., :h * dk].reshape(*lead, h, dk)
    k = fused[..., h * dk:(h + kv) * dk].reshape(*lead, kv, dk)
    v = fused[..., (h + kv) * dk:].reshape(*lead, kv, dv)
    theta = (config.swa_rope_theta if config.layer_pattern[layer]
             else config.rope_theta)
    q = _rope(q, positions, theta, config.rotary_dim)
    k = _rope(k, positions, theta, config.rotary_dim)
    return q, k, (v.astype(jnp.float32) * config.value_scale).astype(dtype)


def _ffn(config: MimoConfig, layer: dict, x: jax.Array, **routing):
    """x (T, D) float32 (normed) -> (y (T, D) float32, Routed or None);
    `routing` is the expert layer's own (`valid`, `rows`, `onto`)."""
    if "mlp" in layer:
        wi, wo = layer["mlp"]["wi"]["kernel"], layer["mlp"]["wo"]["kernel"]
        f = wo.shape[0]
        hidden = nn.mm(x, wi, wi.dtype)
        hidden = (jax.nn.silu(hidden[:, :f].astype(jnp.float32))
                  * hidden[:, f:].astype(jnp.float32))
        return nn.mm(hidden, wo), None
    return held_experts_ffn(
        HeldExperts(**layer["moe"]), x, top_k=config.top_k,
        experts_held=config.experts_held,
        expert_offset=config.expert_offset, **routing)


def _norm(params: dict, x: jax.Array, config: MimoConfig) -> jax.Array:
    return nn.rms_norm(params, x, eps=config.eps)


def _logits(params: dict, config: MimoConfig, h: jax.Array) -> jax.Array:
    return nn.mm(_norm(params["final_norm"], h, config),
               params["head"]["kernel"])


# -- prefill ------------------------------------------------------------------


def _ring_of(rows: jax.Array, lengths: jax.Array, window: int) -> jax.Array:
    """rows (b, kv, S, d) of positions 0..S-1 -> the ring (b, kv, window,
    d) as decoding finds it: slot s holds the last position below the
    example's length that is s modulo the window (any row where there is
    none: the reader masks it)."""
    slots = jnp.arange(window)[None, :]
    last = lengths[:, None] - 1
    position = jnp.maximum(last - jnp.mod(last - slots, window), 0)
    return jnp.take_along_axis(rows, position[:, None, :, None], axis=2)


def _prefill_chunk(params: dict, config: MimoConfig, ids: jax.Array,
                   max_decode_len: int, row_block: int):
    """Some examples (b, S) through the whole stack, as
    `packed.prefill_by_chunks` takes them. The residual stream is PACKED
    (`packed.Packing`): what treats rows one by one (norms, projections,
    rotation, dense layer, router, residual sums) runs over the blocks
    the real tokens fill; attention alone sees the (example, position)
    grid (the kernel masks the rows behind an example's last) and its
    output is read back by row index."""
    p = packed.pack(ids, config.pad_id, row_block)
    b, s, t, cut, put = p.b, p.s, p.t, p.cut, p.put
    h = params["embed"]["embedding"][p.tokens].astype(jnp.float32)
    dtype = params["layers"][0]["attn"]["qkv"]["kernel"].dtype
    caches, held, loads = [], jnp.zeros((b,), jnp.int32), []
    for index, layer in enumerate(params["layers"]):
        windowed = bool(config.layer_pattern[index])
        attn, kv = layer["attn"], config.kv_heads(index)
        widths = (config.num_heads * config.head_dim, kv * config.head_dim,
                  kv * config.v_head_dim)

        def project(lo, qkv, h=h, index=index, layer=layer, attn=attn):
            parts = _qkv(config, index, attn,
                         _norm(layer["attn_norm"], cut(h, lo), config),
                         cut(p.position, lo))
            return tuple(put(all_, part.reshape(p.block, -1), lo)
                         for all_, part in zip(qkv, parts))

        # rows that no block writes stay zeros: masked positions read them
        qkv = p.over_blocks(project, tuple(jnp.zeros((t, w), dtype)
                                           for w in widths))
        q, k, v = (p.grid(x).reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
                   for x, heads in zip(qkv, (config.num_heads, kv, kv)))
        out = attention(
            q, k, v, causal=True, lengths=p.lengths, causal_offset=0,
            window=config.window if windowed else None,
            sink=attn.get("sink"), queries_ragged=True)
        out = out.transpose(0, 2, 1, 3).reshape(b * s, -1)
        dense = "mlp" in layer

        def mix(lo, carry, layer=layer, attn=attn, out=out, dense=dense):
            h, normed = carry
            rows = cut(h, lo) + nn.mm(out[cut(p.on_grid, lo)],
                                      attn["out"]["kernel"])
            x = _norm(layer["ffn_norm"], rows, config)
            if dense:
                return put(h, rows + _ffn(config, layer, x)[0], lo), normed
            return put(h, rows, lo), put(normed, x, lo)

        h, normed = p.over_blocks(mix, (h, None if dense else jnp.zeros(
            (t, config.hidden_size), jnp.float32)))
        if not dense:
            h, routed = _ffn(config, layer, normed, rows=p.total, onto=h)
            held = p.held_by_example(routed.held, onto=held)
            loads.append(routed.load)
        if windowed:
            caches.append({"k": _ring_of(k, p.lengths, config.window),
                           "v": _ring_of(v, p.lengths, config.window)})
        else:
            room = ((0, 0), (0, 0), (0, max_decode_len), (0, 0))
            caches.append({"k": jnp.pad(k, room), "v": jnp.pad(v, room)})
    logits = _logits(params, config, p.last_rows(h))
    load = (jnp.stack(loads) if loads
            else jnp.zeros((0, config.experts_held), jnp.int32))
    return caches, logits, held, load, p.blocks * p.block, {}


def prefill(params: dict, config: MimoConfig, input_ids: jax.Array, *,
            max_decode_len: int,
            row_block: int = packed.PREFILL_ROW_BLOCK) -> dict:
    """The prompts (B, seq_len), right-padded with pad_id -> the state a
    generation carries (models/packed.py), `config.prefill_rows` examples
    at a time, their real tokens taken `row_block` packed rows at a time.
    Its caches: per full layer K/V of seq_len + max_decode_len positions,
    per window layer a ring of `window` rows."""
    return packed.prefill_by_chunks(
        lambda chunk: _prefill_chunk(params, config, chunk, max_decode_len,
                                     row_block),
        input_ids, rows=config.prefill_rows, pad_id=config.pad_id)


# -- one decode step ----------------------------------------------------------


def step(params: dict, config: MimoConfig, state: dict):
    """(state) -> (state', token (B,)): each example's next token
    (`packed.choose`) through the stack at the example's own position,
    through both kinds of cache, to the logits of the token after it. A
    row that pads the batch is routed to no expert."""
    token, finished, position, owned = packed.choose(
        state, config.pad_id, config.eos_id)
    b = token.shape[0]
    each = jnp.arange(b)
    h = params["embed"]["embedding"][token].astype(jnp.float32)
    caches, held = [], jnp.zeros((b,), jnp.int32)
    hit = jnp.zeros((), jnp.int32)
    for index, (layer, cache) in enumerate(zip(params["layers"],
                                               state["caches"])):
        attn = layer["attn"]
        q, k, v = _qkv(config, index, attn,
                       _norm(layer["attn_norm"], h, config), position)
        rows = jnp.arange(cache["k"].shape[2])[None, :]
        if config.layer_pattern[index]:
            # The ring: this position's slot, and every slot whose last
            # writer is a position at or after 0.
            slot = jnp.mod(position, config.window)
            seen = (position[:, None]
                    - jnp.mod(position[:, None] - rows, config.window)) >= 0
        else:
            slot = position
            seen = rows <= position[:, None]
        cache = {"k": cache["k"].at[each, :, slot].set(k),
                 "v": cache["v"].at[each, :, slot].set(v)}
        caches.append(cache)
        h = h + nn.mm(nn.attend_cache(q, cache, seen, attn.get("sink")),
                      attn["out"]["kernel"])
        y, routed = _ffn(config, layer, _norm(layer["ffn_norm"], h, config),
                         valid=owned)
        h = h + y
        if routed is not None:
            held, hit = held + routed.held, hit + routed.hit
    return packed.advance(state, caches, _logits(params, config, h), token,
                          finished, held_decode=held, hit_decode=hit), token


# -- serving ------------------------------------------------------------------


def build_signatures(params: dict, config: MimoConfig, *, seq_len: int,
                     max_decode_len: int,
                     batch_buckets: tuple = (1, 4, 16, 32)) -> dict:
    """`serving_default` alone (generation_signature), with the expert
    layers' counts (`route_counts`, columns packed.ROUTE_COLUMNS). No
    session signatures yet (ROADMAP, Reach)."""
    from min_tfs_client_tpu.servables.decode_signatures import (
        generation_signature,
    )

    return {"serving_default": generation_signature(
        lambda p, ids: prefill(p, config, ids,
                               max_decode_len=max_decode_len),
        lambda p, state: step(p, config, state), params,
        seq_len=seq_len, max_decode_len=max_decode_len,
        vocab_size=config.vocab_size, pad_id=config.pad_id,
        batch_buckets=batch_buckets, tables=(packed.route_table(
            config.top_k * sum(config.moe_pattern)),))}
