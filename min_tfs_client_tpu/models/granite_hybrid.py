"""A decoder of the Granite-4.0-H kind: pre-RMSNorm blocks whose sequence
mixers are of two kinds in one stack, state-space (Mamba-2: a causal
convolution over 4 rows, then the selective scan of ops/ssm.py with a
float32 recurrent state, a gated RMSNorm and an out-projection) and, one
layer in ten, full causal attention with grouped K/V heads and no
positions at all; every layer followed by a softmax-top-k expert layer
(parallel/moe.py: this chip's share of the experts, dropless) beside a
shared expert that every token passes. The embedding is tied to the
head; the published multipliers scale the embedding, every residual
branch, the attention scores and the logits.

Served as whole generations on `serving_default` through the
whole-generation front (servables/decode_signatures.generation_signature)
over the decode contract, `prefill(params, ids) -> state` and `step(params,
state) -> (state', token)`, both written over models/packed.py. The state
carries two kinds of memory through one loop: for a state-space layer the
last `d_conv - 1` rows before the convolution and the recurrent state (N
x channels float32 a sequence, the same at any context), for an attention
layer a full-length K/V cache; with each example's own length.

Numerics: matrices and their operands in the parameters' dtype (bfloat16
as served) with float32 accumulation; the residual stream, the norms,
dt, the decays, the recurrent state and its update, the scores, the
softmax, the router and the logits in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.models import packed
from min_tfs_client_tpu.ops import ssm
from min_tfs_client_tpu.ops.attention import attention
from min_tfs_client_tpu.parallel.moe import HeldExperts, held_experts_ffn

# The columns of `state_counts`, one row an example: its prompt tokens,
# the rows the chunked scan ran for it in one state-space layer, the
# bytes of state it holds through the loop, its decode steps; and of its
# BATCH, on every row: the (row, state-space layer, step) recurrent states
# the decode steps held and those they moved (the rows the batch owns).
STATE_COLUMNS = ("prompt_tokens", "scan_rows", "state_bytes", "steps",
                 "state_rows_held", "state_rows_moved")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    # One entry a layer (longer lists are cut to num_layers).
    layer_types: tuple = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    intermediate_size: int = 768           # of one routed expert
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72            # the router's width
    experts_held: int = 72                 # this chip's share of them ...
    expert_offset: int = 0                 # ... starting at this expert
    top_k: int = 10
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    eps: float = 1e-5
    pad_id: int = 0
    eos_id: int = 1
    dtype: str = "bfloat16"
    # Examples the prefill takes through the stack at a time: bounds its
    # activations (the in-projection's rows above all; 8 at the published
    # widths take 11.2 GB beside the weights, the TPU compiler's account).
    prefill_rows: int = 4

    def __post_init__(self):
        kinds = tuple(str(v) for v in self.layer_types)[:self.num_layers]
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.num_layers:
            raise ValueError("layer_types has fewer entries than layers")
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"unknown layer types in {kinds}")
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C is what ops/ssm.py runs")
        if not 0 <= self.expert_offset <= \
                self.num_local_experts - self.experts_held:
            raise ValueError("the held experts lie outside the router")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def state_bytes(self) -> int:
        """Of one sequence, whatever its context: the float32 recurrent
        states and the convolution windows (in `dtype`) of the
        state-space layers."""
        layers = sum(kind == "mamba" for kind in self.layer_types)
        return layers * (4 * self.mamba_d_state * self.d_inner
                         + jnp.dtype(self.dtype).itemsize
                         * (self.mamba_d_conv - 1) * self.conv_dim)


# -- parameters ---------------------------------------------------------------

# Gains of the seeded weights (the configuration file's `assumed.weights`
# says why each): the branches' out-projections, q and k (the published
# score multiplier is 1 / head_dim, not its root), the B and C columns of
# the in-projection, the router.
BRANCH_GAIN = 3.0
BC_GAIN = 4.0
ROUTER_GAIN = 3.0
EMBED_STD = 0.25


def init_params(rng: jax.Array, config: GraniteHybridConfig) -> dict:
    """Leaves in `config.dtype` (the small float32 ones apart: norm
    scales, the convolution, dt_bias, A_log, D, the router). Seeded so
    that a random-weight generation is not degenerate: logits of unit
    scale (embedding std 0.25 under the published multipliers), each
    residual branch of RMS about 3 x 0.22 (the embedding's share of the
    stream stays near a half), scores of unit scale, a final norm scale
    of random signs (a tied head would otherwise hand every token its
    own id back), a peaked router (a near-tie between the 10th and 11th
    expert then moves a weight of about 0.01), Mamba-2's own A (1..16)
    and dt (0.001..0.1)."""
    dtype = jnp.dtype(config.dtype)
    d = config.hidden_size
    heads, hd, kv = config.num_heads, config.head_dim, config.num_kv_heads
    di, n, mh = config.d_inner, config.mamba_d_state, config.mamba_n_heads

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    keys = iter(jax.random.split(rng, 12 * config.num_layers + 2))
    layers = []
    for kind in config.layer_types:
        layer = {"norm": nn.rms_norm_init(d), "ffn_norm": nn.rms_norm_init(d)}
        if kind == "mamba":
            gain = jnp.concatenate([
                jnp.ones((di + di,)), jnp.full((2 * n,), BC_GAIN),
                jnp.ones((mh,))])
            dt = jnp.exp(jax.random.uniform(
                next(keys), (mh,), minval=np.log(1e-3), maxval=np.log(1e-1)))
            layer["mamba"] = {
                "in": {"kernel": (jax.random.normal(
                    next(keys), (d, 2 * di + 2 * n + mh), jnp.float32)
                    * d ** -0.5 * gain).astype(dtype)},
                "conv": jax.random.uniform(
                    next(keys), (config.mamba_d_conv, config.conv_dim),
                    minval=-0.5, maxval=0.5),
                "conv_bias": jax.random.uniform(
                    next(keys), (config.conv_dim,), minval=-0.5, maxval=0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (mh,), minval=1.0, maxval=16.0)),
                "d": jnp.ones((mh,), jnp.float32),
                "norm": nn.rms_norm_init(di),
                "out": {"kernel": normal(next(keys), (di, d),
                                         BRANCH_GAIN * di ** -0.5)}}
        else:
            sharp = hd ** 0.25   # scores q.k / head_dim of unit scale
            gain = jnp.concatenate([
                jnp.full(((heads + kv) * hd,), sharp), jnp.ones((kv * hd,))])
            layer["attn"] = {
                "qkv": {"kernel": (jax.random.normal(
                    next(keys), (d, (heads + 2 * kv) * hd), jnp.float32)
                    * d ** -0.5 * gain).astype(dtype)},
                "out": {"kernel": normal(next(keys), (heads * hd, d),
                                         BRANCH_GAIN * (heads * hd) ** -0.5)}}
        f, held = config.intermediate_size, config.experts_held
        fs = config.shared_intermediate_size
        layer["moe"] = {
            "router": jax.random.normal(
                next(keys), (d, config.num_local_experts),
                jnp.float32) * ROUTER_GAIN * d ** -0.5,
            "w_in": normal(next(keys), (held, d, 2 * f), d ** -0.5),
            "w_out": normal(next(keys), (held, f, d),
                            BRANCH_GAIN * (2.0 / f) ** 0.5)}
        layer["shared"] = {
            "w_in": normal(next(keys), (d, 2 * fs), d ** -0.5),
            "w_out": normal(next(keys), (fs, d),
                            BRANCH_GAIN * (2.0 / fs) ** 0.5)}
        layers.append(layer)
    signs = jnp.where(jax.random.bernoulli(next(keys), 0.5, (d,)), 1.0, -1.0)
    return {"embed": {"embedding": normal(next(keys),
                                          (config.vocab_size, d), EMBED_STD)},
            "layers": layers, "final_norm": {"scale": signs}}


# -- pieces -------------------------------------------------------------------


def _norm(params: dict, x: jax.Array, config: GraniteHybridConfig):
    return nn.rms_norm(params, x, eps=config.eps)


def _swiglu(p: dict, x: jax.Array) -> jax.Array:
    """x (T, D) float32 (normed) -> the shared expert's rows, float32."""
    f = p["w_out"].shape[0]
    hidden = nn.mm(x, p["w_in"], p["w_in"].dtype).astype(jnp.float32)
    return nn.mm(jax.nn.silu(hidden[:, :f]) * hidden[:, f:], p["w_out"])


def _experts(config: GraniteHybridConfig, layer: dict, x: jax.Array,
             **routing):
    """The held experts' rows times the residual multiplier (added onto
    `onto`, a caller's residual stream, where there is one)."""
    return held_experts_ffn(
        HeldExperts(bias=None, **layer["moe"]), x, top_k=config.top_k,
        experts_held=config.experts_held,
        expert_offset=config.expert_offset, routing="softmax_top_k",
        scale=config.residual_multiplier, **routing)


def _embed(params: dict, config: GraniteHybridConfig, ids: jax.Array):
    return (params["embed"]["embedding"][ids].astype(jnp.float32)
            * config.embedding_multiplier)


def _logits(params: dict, config: GraniteHybridConfig, h: jax.Array):
    table = params["embed"]["embedding"]
    normed = _norm(params["final_norm"], h, config)
    return jnp.dot(normed.astype(table.dtype), table.T,
                   preferred_element_type=jnp.float32) / config.logits_scaling


def _in_projection(config: GraniteHybridConfig, p: dict, x: jax.Array):
    """x (T, D) float32 (normed) -> z (T, d_inner) and the rows before
    the convolution (T, conv_dim), both in the parameters' dtype, and dt
    (T, heads) float32, softplus'd."""
    di, cd = config.d_inner, config.conv_dim
    dtype = p["in"]["kernel"].dtype
    proj = nn.mm(x, p["in"]["kernel"])
    dt = jax.nn.softplus(proj[:, di + cd:] + p["dt_bias"])
    return proj[:, :di].astype(dtype), proj[:, di:di + cd].astype(dtype), dt


def _gated_out(config: GraniteHybridConfig, p: dict, y: jax.Array,
               z: jax.Array) -> jax.Array:
    """RMSNorm(y * silu(z)) over all of d_inner, then the out-projection,
    times the residual multiplier. -> (T, D) float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return config.residual_multiplier * nn.mm(
        _norm(p["norm"], gated, config), p["out"]["kernel"])


def _qkv(config: GraniteHybridConfig, attn: dict, x: jax.Array):
    """x (..., D) -> q (..., H, d), k and v (..., kv, d), no positions."""
    h, kv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    fused = nn.mm(x, attn["qkv"]["kernel"], attn["qkv"]["kernel"].dtype)
    lead = fused.shape[:-1]
    return (fused[..., :h * hd].reshape(*lead, h, hd),
            fused[..., h * hd:(h + kv) * hd].reshape(*lead, kv, hd),
            fused[..., (h + kv) * hd:].reshape(*lead, kv, hd))


# -- prefill ------------------------------------------------------------------


def _prefill_chunk(params: dict, config: GraniteHybridConfig, ids: jax.Array,
                   max_decode_len: int, row_block: int):
    """Some examples (b, S) through the whole stack, as
    `packed.prefill_by_chunks` takes them, with the rows the scan ran as
    the model's own count. The residual stream is PACKED
    (`packed.Packing`): what treats rows one by one (norms, projections,
    the convolution, the gate, the shared expert, the router, residual
    sums) runs over the blocks the real tokens fill; the scan and
    attention see the (example, position) grid (of the rows behind an
    example's last the scan makes dt = 0, attention masks them) and
    their output is read back by row index."""
    pk = packed.pack(ids, config.pad_id, row_block)
    b, s, t, block, cut, put = pk.b, pk.s, pk.t, pk.block, pk.cut, pk.put
    lengths, ends = pk.lengths, pk.ends
    h = _embed(params, config, pk.tokens)
    dtype = params["embed"]["embedding"].dtype
    taps = config.mamba_d_conv

    caches, held, loads, scanned = [], jnp.zeros((b,), jnp.int32), [], None
    for kind, layer in zip(config.layer_types, params["layers"]):
        if kind == "mamba":
            p = layer["mamba"]

            def project(lo, carry, h=h, layer=layer, p=p):
                z, pre, mixed, dt, tail = carry
                z_, pre_, dt_ = _in_projection(
                    config, p, _norm(layer["norm"], cut(h, lo), config))
                # the causal convolution over the packed rows: a tap that
                # reaches before its example's first row reads nothing
                seen = jnp.concatenate([tail, pre_]).astype(jnp.float32)
                at = cut(pk.position, lo)[:, None]
                conv = p["conv_bias"] + sum(
                    jnp.where(at >= taps - 1 - k, seen[k:k + block], 0.0)
                    * p["conv"][k] for k in range(taps))
                return (put(z, z_, lo), put(pre, pre_, lo),
                        put(mixed, jax.nn.silu(conv).astype(dtype), lo),
                        put(dt, dt_, lo), pre_[block - (taps - 1):])

            z, pre, mixed, dt, _ = pk.over_blocks(project, (
                jnp.zeros((t, config.d_inner), dtype),
                jnp.zeros((t, config.conv_dim), dtype),
                jnp.zeros((t, config.conv_dim), dtype),
                jnp.zeros((t, config.mamba_n_heads), jnp.float32),
                jnp.zeros((taps - 1, config.conv_dim), dtype)))
            mixed = pk.grid(mixed)
            di, n = config.d_inner, config.mamba_d_state
            y, state, scanned = ssm.ssd(
                mixed[..., :di], pk.grid(dt), -jnp.exp(p["a_log"]),
                mixed[..., di:di + n], mixed[..., di + n:], p["d"], lengths,
                chunk=config.mamba_chunk_size)
            # the window decoding goes on from: the last rows before the
            # convolution of each example's REAL tokens
            back_by = jnp.arange(taps - 1)[None, :] - (taps - 1)
            window = jnp.where(
                (lengths[:, None] + back_by >= 0)[..., None],
                pre[jnp.clip(ends[:, None] + back_by, 0, t - 1)], 0)
            caches.append({"conv": window, "ssm": state})
            mixer_rows = y.reshape(b * s, -1)

            def mixer_out(lo, p=p, z=z, mixer_rows=mixer_rows):
                return _gated_out(config, p, pk.back(mixer_rows, lo),
                                  cut(z, lo))
        else:
            attn = layer["attn"]
            widths = (config.num_heads * config.head_dim,
                      config.num_kv_heads * config.head_dim,
                      config.num_kv_heads * config.head_dim)

            def project(lo, qkv, h=h, layer=layer, attn=attn):
                parts = _qkv(config, attn,
                             _norm(layer["norm"], cut(h, lo), config))
                return tuple(put(all_, part.reshape(block, -1), lo)
                             for all_, part in zip(qkv, parts))

            qkv = pk.over_blocks(project, tuple(jnp.zeros((t, w), dtype)
                                                for w in widths))
            q, k, v = (
                pk.grid(x).reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
                for x, heads in zip(qkv, (config.num_heads,
                                          config.num_kv_heads,
                                          config.num_kv_heads)))
            out = attention(q, k, v, causal=True, lengths=lengths,
                            causal_offset=0,
                            scale=config.attention_multiplier,
                            queries_ragged=True)
            room = ((0, 0), (0, 0), (0, max_decode_len), (0, 0))
            caches.append({"k": jnp.pad(k, room), "v": jnp.pad(v, room)})
            mixer_rows = out.transpose(0, 2, 1, 3).reshape(b * s, -1)

            def mixer_out(lo, attn=attn, mixer_rows=mixer_rows):
                return config.residual_multiplier * nn.mm(
                    pk.back(mixer_rows, lo), attn["out"]["kernel"])

        def mix(lo, carry, layer=layer, mixer_out=mixer_out):
            h, normed = carry
            rows = cut(h, lo) + mixer_out(lo)
            x = _norm(layer["ffn_norm"], rows, config)
            rows = rows + config.residual_multiplier * _swiglu(
                layer["shared"], x)
            return put(h, rows, lo), put(normed, x, lo)

        h, normed = pk.over_blocks(mix, (h, jnp.zeros(
            (t, config.hidden_size), jnp.float32)))
        h, routed = _experts(config, layer, normed, rows=pk.total, onto=h)
        held = pk.held_by_example(routed.held, onto=held)
        loads.append(routed.load)
    last = pk.last_rows(h)
    if scanned is None:
        scanned = jnp.zeros((b,), jnp.int32)
    return (caches, _logits(params, config, last), held, jnp.stack(loads),
            pk.blocks * pk.block, {"scan_rows": scanned})


def prefill(params: dict, config: GraniteHybridConfig, input_ids: jax.Array,
            *, max_decode_len: int,
            row_block: int = packed.PREFILL_ROW_BLOCK) -> dict:
    """The prompts (B, seq_len), right-padded with pad_id -> the state a
    generation carries (models/packed.py), `config.prefill_rows` examples
    at a time. Its caches: per state-space layer the convolution's
    window and the recurrent state AFTER EACH EXAMPLE'S LAST REAL TOKEN,
    per attention layer K/V of seq_len + max_decode_len positions. A row
    of length 0 (one that pads the batch) touches nothing: zero state,
    zero window."""
    return packed.prefill_by_chunks(
        lambda chunk: _prefill_chunk(params, config, chunk, max_decode_len,
                                     row_block),
        input_ids, rows=config.prefill_rows, pad_id=config.pad_id,
        extra_counts=("state_rows_held", "state_rows_moved"))


# -- one decode step ----------------------------------------------------------


def step(params: dict, config: GraniteHybridConfig, state: dict):
    """(state) -> (state', token (B,)): each example's next token
    (`packed.choose`) through the stack: a state-space layer shifts its
    window by the token's row and moves its recurrent state one step,
    where it lies; the attention layer writes its cache at the example's
    own position. A row that pads the batch is routed to no expert, and
    its recurrent states are neither read nor written."""
    token, finished, position, owned = packed.choose(
        state, config.pad_id, config.eos_id)
    b = token.shape[0]
    each = jnp.arange(b)
    h = _embed(params, config, token)
    caches, held = [], jnp.zeros((b,), jnp.int32)
    hit = jnp.zeros((), jnp.int32)
    states_held = states_moved = jnp.zeros((), jnp.int32)
    for kind, layer, cache in zip(config.layer_types, params["layers"],
                                  state["caches"]):
        x = _norm(layer["norm"], h, config)
        if kind == "mamba":
            p = layer["mamba"]
            di, n = config.d_inner, config.mamba_d_state
            z, pre, dt = _in_projection(config, p, x)
            seen = jnp.concatenate([cache["conv"], pre[:, None]], axis=1)
            mixed = jax.nn.silu(
                jnp.sum(seen.astype(jnp.float32) * p["conv"], axis=1)
                + p["conv_bias"]).astype(pre.dtype)
            moved, y = ssm.ssm_step(
                cache["ssm"], mixed[:, :di], dt, -jnp.exp(p["a_log"]),
                mixed[:, di:di + n], mixed[:, di + n:], p["d"], owned=owned)
            states_held = states_held + b
            states_moved = states_moved + jnp.sum(owned, dtype=jnp.int32)
            caches.append({"conv": seen[:, 1:], "ssm": moved})
            h = h + _gated_out(config, p, y, z)
        else:
            attn = layer["attn"]
            q, k, v = _qkv(config, attn, x)
            rows = jnp.arange(cache["k"].shape[2])[None, :]
            cache = {"k": cache["k"].at[each, :, position].set(k),
                     "v": cache["v"].at[each, :, position].set(v)}
            caches.append(cache)
            h = h + config.residual_multiplier * nn.mm(
                nn.attend_cache(q, cache, rows <= position[:, None], None,
                                scale=config.attention_multiplier),
                attn["out"]["kernel"])
        x = _norm(layer["ffn_norm"], h, config)
        y, routed = _experts(config, layer, x, valid=owned)
        h = h + y + config.residual_multiplier * _swiglu(layer["shared"], x)
        held, hit = held + routed.held, hit + routed.hit
    return packed.advance(
        state, caches, _logits(params, config, h), token, finished,
        held_decode=held, hit_decode=hit, state_rows_held=states_held,
        state_rows_moved=states_moved), token


# -- serving ------------------------------------------------------------------


def count_tables(config: GraniteHybridConfig) -> tuple:
    """The expert layers' table (every layer has one) and the state's.
    The batch's figures go as they are onto every rider's span and into
    every rider's counters: summed over the riders both grow alike, and
    `state_rows_moved` over `state_rows_held` is what is read."""
    from min_tfs_client_tpu.servables.decode_signatures import CountTable

    return (packed.route_table(config.top_k * config.num_layers),
            CountTable(
                output="state_counts", span="generate/state",
                section="state", columns=STATE_COLUMNS,
                derived={"state_bytes": (None, config.state_bytes)},
                batch=("state_rows_held", "state_rows_moved")))


def build_signatures(params: dict, config: GraniteHybridConfig, *,
                     seq_len: int, max_decode_len: int,
                     batch_buckets: tuple = (1, 4, 16, 32)) -> dict:
    """`serving_default` alone (generation_signature), with the expert
    layers' counts (`route_counts`) and the state's (`state_counts`,
    columns STATE_COLUMNS): no recurrent state outlives the loop."""
    from min_tfs_client_tpu.servables.decode_signatures import (
        generation_signature,
    )

    return {"serving_default": generation_signature(
        lambda p, ids: prefill(p, config, ids,
                               max_decode_len=max_decode_len),
        lambda p, state: step(p, config, state), params,
        seq_len=seq_len, max_decode_len=max_decode_len,
        vocab_size=config.vocab_size, pad_id=config.pad_id,
        batch_buckets=batch_buckets, tables=count_tables(config))}
