"""A decoder of the Xing4.0 (`xing4_0`) kind: latent attention (MLA,
models/latent.py) in EVERY layer, its query through a low rank with a
norm of its own and its rotary stretched by YaRN; the feed-forward layers
SwiGLU, dense in the leading layers and after them a sigmoid top-k expert
layer (parallel/moe.py: this chip's share of the experts, dropless)
beside a shared expert that every token passes; and under both a
HYPER-CONNECTED residual path (mHC, ops/mhc.py): a token's stream is
`hc_mult` streams of the hidden size, every sub-layer (a layer's
attention, then its feed-forward) reads a learned per-token mix of them
and writes back through a doubly stochastic matrix made by Sinkhorn's
rounds. The embedding and the head are two matrices. The
multi-token-prediction module of the published model is left out.

Served as whole generations on `serving_default` through the
whole-generation front (servables/decode_signatures.generation_signature)
over the decode contract, `prefill(params, ids) -> state` and `step(params,
state) -> (state', token)`, both written over models/packed.py. The state
carries ONE latent cache a layer (576 values a position) with each
example's own length; the streams do not outlive a token.

Numerics: matrices and their operands in the parameters' dtype (bfloat16
as served) with float32 accumulation; the streams, every norm, the maps'
input (at full float32 precision), the three maps and Sinkhorn's rounds,
the scores, the softmax, the router and the logits in float32; the latent
cache in the parameters' dtype.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from min_tfs_client_tpu.models import latent
from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.models import packed
from min_tfs_client_tpu.ops import mhc
from min_tfs_client_tpu.parallel.moe import HeldExperts, held_experts_ffn

# Of `latent_counts` (models/ling_hybrid.py's columns, so that one reader
# reads both), one row an example, summed over the layers: the cached
# positions its decode steps' attention read, those the latent caches
# held for it meanwhile, and the rows the steps brought in (whole blocks
# where the step's kernel ran, all it held elsewhere).
LATENT_COLUMNS = ("prompt_tokens", "steps", "latent_rows_read",
                  "latent_rows_held", "latent_rows_copied")
# Of `stream_counts`, one row an example: its rows (prompt tokens and
# decode steps) times the sub-layers whose streams were mixed, and
# Sinkhorn's rounds for them. The streams' bytes are `stream_rows` x 3 x
# hc_mult x hidden x 4 (read for the maps and the pre-mix, read and
# written by the post-mix): past an int32 column for one long example, so
# left to the reader.
STREAM_COLUMNS = ("prompt_tokens", "steps", "stream_rows", "sinkhorn_rounds")


@dataclasses.dataclass(frozen=True)
class XingConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    # One entry a layer (longer lists are cut to num_layers): "dense" or
    # "moe". None: the published pattern, dense in the two leading layers.
    ffn_types: tuple | None = None
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    # YaRN (rope_scaling): a factor of 1 leaves the rotary plain
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    intermediate_size: int = 9216          # of a dense layer
    moe_intermediate_size: int = 1024      # of one routed expert
    shared_intermediate_size: int = 1024
    num_experts: int = 64          # the router's width
    experts_held: int = 64         # this chip's share of them ...
    expert_offset: int = 0         # ... starting at this expert
    top_k: int = 4
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4               # streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    eps: float = 1e-6
    pad_id: int = 0
    eos_id: int = 1
    dtype: str = "bfloat16"
    # Examples the prefill takes through the stack at a time: bounds its
    # activations (the float32 streams of a chunk above all).
    prefill_rows: int = 4

    def __post_init__(self):
        n = self.num_layers
        ffns = self.ffn_types or tuple(
            "dense" if i < 2 else "moe" for i in range(n))
        ffns = tuple(str(v) for v in ffns)[:n]
        object.__setattr__(self, "ffn_types", ffns)
        if len(ffns) != n:
            raise ValueError("ffn_types has fewer entries than layers")
        if set(ffns) - {"dense", "moe"}:
            raise ValueError(f"unknown ffn_types in {ffns}")
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError("the held experts lie outside the router")
        if self.hc_mult < 1:
            raise ValueError("a token has at least one stream")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError("cos and sin scaled by mscale / mscale_all_dim "
                             "other than 1 are not implemented")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Of one cached position: the latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        return sum(kind == "moe" for kind in self.ffn_types)

    @property
    def maps_width(self) -> int:
        """Columns of a sub-layer's phi: pre, post, res."""
        return self.hc_mult * (self.hc_mult + 2)

    @property
    def attention_scale(self) -> float:
        """qk_head_dim^-1/2, times YaRN's temperature squared (its mscale
        over all dims; cos and sin carry mscale / mscale_all_dim, 1 as
        published and in every config this file accepts)."""
        return self.qk_head_dim ** -0.5 * latent.yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    def frequencies(self):
        """The rotary's law (`latent.rope`'s `inv_freq`)."""
        if self.rope_factor <= 1:
            return latent.plain_frequencies(self.rope_theta)
        return latent.yarn_frequencies(
            self.rope_theta, factor=self.rope_factor,
            original=self.rope_original_positions,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow)


# -- parameters ---------------------------------------------------------------

# Gains of the seeded weights (the configuration file's `assumed.weights`
# says why each): the branches' out-projections (40 branches of RMS near
# 0.2 leave the embedding near half of the exit), MLA's query (scores of
# std near 2.5 after YaRN's temperature: a softmax over 2,000 flat scores
# would silence the layer), the maps' bias towards the identity.
MLA_OUT_GAIN = 0.45
MLA_QUERY_GAIN = 1.2
DENSE_OUT_GAIN = 0.3
EXPERT_OUT_GAIN = 0.9
SHARED_OUT_GAIN = 0.25
HC_RES_DIAGONAL = 2.0
# The leading channels of every stream that no branch writes (every
# out-projection's columns for them are 0) and that alone the routers
# read: there each stream is the token's embedding times one factor a
# token, which moves no order of the router's scores.
ROUTER_CHANNELS = 64


def _hc_init(key, config: XingConfig) -> dict:
    """A sub-layer's maps: phi of unit gain (m of unit scale), the three
    alphas in (0.5, 1.5), b_pre = b_post = 0 (H_pre near 1/2, H_post near
    1), b_res leaning on the diagonal: the maps differ token by token and
    H_res stays near a soft identity."""
    n, width = config.hc_mult, config.hc_mult * config.hidden_size
    phi, alpha = jax.random.split(key)
    return {"phi": jax.random.normal(phi, (width, config.maps_width),
                                     jnp.float32) * width ** -0.5,
            "alpha": jax.random.uniform(alpha, (3,), minval=0.5, maxval=1.5),
            "bias": jnp.concatenate([
                jnp.zeros((2 * n,)),
                HC_RES_DIAGONAL * jnp.eye(n).reshape(-1)])}


def init_params(rng: jax.Array, config: XingConfig) -> dict:
    """Leaves in `config.dtype` (the small float32 ones apart: norm
    scales, the maps' phi, alpha and bias, the router and its bias).
    Seeded so that a random-weight generation is not degenerate: an
    embedding of N(0, 1) and a head of unit gain (logits of unit scale),
    each residual branch small against the embedding, MLA's scores of std
    about 2.5, a router of unit gain that reads `ROUTER_CHANNELS` channels
    no branch writes (a mix across streams keeps a channel's content, so
    on those channels every stream is the token's embedding times a
    positive factor, in any precision; models/ling_hybrid.py:init_params
    says what a router on the whole stream cost) and a selection bias of
    ZERO: the factor is the pre-mix's sum over the norm's root mean
    square, and H_pre moves with the rounding of the streams by a
    thousandth, which under a bias of std 0.02 turned 14 of 19,456
    (token, layer) choices between the bfloat16 program and the float32
    one (hidden 512, the sandbox's CPU, PR 54), each worth a tenth or
    more of a logit; with no bias the order of sigmoid(factor x z) is the
    order of z under any positive factor and the choice is the token's
    own. The tests give the bias values (tests/unit/test_xing.py)."""
    dtype = jnp.dtype(config.dtype)
    d, h = config.hidden_size, config.num_heads
    quiet = min(ROUTER_CHANNELS, d // 4)
    written = (jnp.arange(d) >= quiet).astype(jnp.float32)

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def out(key, shape, std):
        """An out-projection: nothing onto the routers' channels."""
        return (jax.random.normal(key, shape, jnp.float32) * std
                * written).astype(dtype)

    keys = iter(jax.random.split(rng, 14 * config.num_layers + 3))
    layers = []
    for ffn in config.ffn_types:
        rank, q_rank = config.kv_lora_rank, config.q_lora_rank
        layer = {
            "attn_hc": _hc_init(next(keys), config),
            "ffn_hc": _hc_init(next(keys), config),
            "norm": nn.rms_norm_init(d), "ffn_norm": nn.rms_norm_init(d),
            "mla": {
                "qa": {"kernel": normal(next(keys), (d, q_rank), d ** -0.5)},
                "q_norm": nn.rms_norm_init(q_rank),
                "qb": {"kernel": normal(
                    next(keys), (q_rank, h * config.qk_head_dim),
                    MLA_QUERY_GAIN * q_rank ** -0.5)},
                "kva": {"kernel": normal(next(keys),
                                         (d, config.latent_width), d ** -0.5)},
                "kv_norm": nn.rms_norm_init(rank),
                # a head's columns side by side: its nope keys, its values
                "kvb": {"kernel": normal(
                    next(keys), (rank, h * (config.qk_nope_head_dim
                                            + config.v_head_dim)),
                    rank ** -0.5)},
                "out": {"kernel": out(
                    next(keys), (h * config.v_head_dim, d),
                    MLA_OUT_GAIN * (h * config.v_head_dim) ** -0.5)}}}
        if ffn == "dense":
            f = config.intermediate_size
            layer["mlp"] = {
                "wi": {"kernel": normal(next(keys), (d, 2 * f), d ** -0.5)},
                "wo": {"kernel": out(next(keys), (f, d),
                                     DENSE_OUT_GAIN * (2.0 / f) ** 0.5)}}
        else:
            f, held = config.moe_intermediate_size, config.experts_held
            fs = config.shared_intermediate_size
            layer["moe"] = {
                "router": jax.random.normal(
                    next(keys), (d, config.num_experts), jnp.float32)
                * quiet ** -0.5 * (1.0 - written)[:, None],
                "bias": jnp.zeros((config.num_experts,), jnp.float32),
                "w_in": normal(next(keys), (held, d, 2 * f), d ** -0.5),
                "w_out": out(next(keys), (held, f, d),
                             EXPERT_OUT_GAIN * (2.0 / f) ** 0.5)}
            layer["shared"] = {
                "w_in": normal(next(keys), (d, 2 * fs), d ** -0.5),
                "w_out": out(next(keys), (fs, d),
                             SHARED_OUT_GAIN * (2.0 / fs) ** 0.5)}
        layers.append(layer)
    return {"embed": {"embedding": normal(next(keys),
                                          (config.vocab_size, d), 1.0)},
            "layers": layers, "final_norm": nn.rms_norm_init(d),
            "head": {"kernel": normal(next(keys), (d, config.vocab_size),
                                      d ** -0.5)}}


# -- pieces -------------------------------------------------------------------


def _norm(params: dict, x: jax.Array, config: XingConfig):
    return nn.rms_norm(params, x, eps=config.eps)


def _swiglu(w_in: jax.Array, w_out: jax.Array, x: jax.Array) -> jax.Array:
    """x (T, D) float32 (normed) through one gated layer -> float32."""
    f = w_out.shape[0]
    hidden = nn.mm(x, w_in, w_in.dtype).astype(jnp.float32)
    return nn.mm(jax.nn.silu(hidden[:, :f]) * hidden[:, f:], w_out)


def _feed_forward(layer: dict, x: jax.Array) -> jax.Array:
    """What of a layer's feed-forward every row passes: the dense layer,
    or beside the routed experts the shared one."""
    if "mlp" in layer:
        return _swiglu(layer["mlp"]["wi"]["kernel"],
                       layer["mlp"]["wo"]["kernel"], x)
    return _swiglu(layer["shared"]["w_in"], layer["shared"]["w_out"], x)


def _experts(config: XingConfig, layer: dict, x: jax.Array, **routing):
    return held_experts_ffn(
        HeldExperts(**layer["moe"]), x, top_k=config.top_k,
        experts_held=config.experts_held,
        expert_offset=config.expert_offset, routing="sigmoid",
        scale=config.routed_scaling_factor, **routing)


def _logits(params: dict, config: XingConfig, h: jax.Array):
    return nn.mm(_norm(params["final_norm"], h, config),
                 params["head"]["kernel"])


def _maps(config: XingConfig, hc: dict, x: jax.Array):
    """A sub-layer's three maps for the streams x (n, T, C)."""
    return mhc.mhc_maps(
        mhc.mhc_project(x, hc["phi"], config.hc_eps), hc["alpha"],
        hc["bias"], n=config.hc_mult, iters=config.hc_sinkhorn_iters,
        eps=config.hc_eps, clamp=(config.hc_clamp_min, config.hc_clamp_max))


def _mla_inputs(config: XingConfig, p: dict, x: jax.Array,
                positions: jax.Array):
    """x (T, D) float32 (normed) at `positions` (T,) -> q (T, heads, nope
    + rope) through the low rank and its norm, rotated on its rope lanes,
    and the row the cache holds (T, rank + rope; `latent.latent_row`),
    both in the parameters' dtype."""
    dtype = p["qa"]["kernel"].dtype
    inv_freq = config.frequencies()
    low = _norm(p["q_norm"], nn.mm(x, p["qa"]["kernel"]), config)
    q = latent.rotate_query(
        nn.mm(low, p["qb"]["kernel"]).reshape(-1, config.num_heads,
                                              config.qk_head_dim),
        positions, inv_freq, config.qk_nope_head_dim)
    row = latent.latent_row(nn.mm(x, p["kva"]["kernel"]), p["kv_norm"],
                            positions, inv_freq, rank=config.kv_lora_rank,
                            eps=config.eps)
    return q.astype(dtype), row.astype(dtype)


def _latent_sizes(config: XingConfig) -> dict:
    return dict(nope=config.qk_nope_head_dim, v_head_dim=config.v_head_dim,
                scale=config.attention_scale)


# -- prefill ------------------------------------------------------------------


def _prefill_chunk(params: dict, config: XingConfig, ids: jax.Array,
                   max_decode_len: int, row_block: int):
    """Some examples (b, S) through the whole stack, as
    `packed.prefill_by_chunks` takes them. The streams are PACKED
    (`packed.Packing`), (n, t, C) float32 with the real tokens first: the
    maps, the pre-mix, norms, projections, rotation, out-projections, the
    dense layer, the shared expert, the router and the post-mix run over
    the blocks the real tokens fill; attention alone sees the (example,
    position) grid and its output is read back by row index.

    A layer is two passes over the blocks. `project`: the attention
    sub-layer's maps and pre-mix, q and the latent rows. `mix`: its
    post-mix, then the feed-forward sub-layer whole (maps, pre-mix, the
    dense layer or the shared expert, post-mix). The routed experts run
    over all rows after `mix`; what they add, H_post y, is `owed` to the
    streams and paid by the next layer's `project` (or at the exit), so
    the streams are not passed over once more for it."""
    pk = packed.pack(ids, config.pad_id, row_block)
    b, s, t, block, cut, put = pk.b, pk.s, pk.t, pk.block, pk.cut, pk.put
    n, d, heads = config.hc_mult, config.hidden_size, config.num_heads
    dtype = params["embed"]["embedding"].dtype

    def cut_at(x, lo, axis):
        return jax.lax.dynamic_slice_in_dim(x, lo, block, axis)

    def put_at(x, part, lo, axis):
        return jax.lax.dynamic_update_slice_in_dim(x, part, lo, axis)

    def owing(post, y):
        """What the routed experts owe the streams of some rows: their
        output y (T, C) through H_post (n, T) -> (n, T, C)."""
        return post[:, :, None] * y[None]

    x = mhc.mhc_enter(
        params["embed"]["embedding"][pk.tokens].astype(jnp.float32), n)
    caches, held, loads, owed = [], jnp.zeros((b,), jnp.int32), [], None
    for layer in params["layers"]:
        p = layer["mla"]

        def project(lo, carry, layer=layer, p=p, owed=owed):
            x, q, rows, post, res = carry
            x_ = cut_at(x, lo, 1)
            if owed is not None:
                x_ = x_ + owing(cut_at(owed[0], lo, 1), cut(owed[1], lo))
                x = put_at(x, x_, lo, 1)
            pre_, post_, res_ = _maps(config, layer["attn_hc"], x_)
            q_, rows_ = _mla_inputs(
                config, p, _norm(layer["norm"], mhc.mhc_pre(x_, pre_),
                                 config), cut(pk.position, lo))
            return (x, put(q, q_.reshape(block, -1), lo),
                    put(rows, rows_, lo), put_at(post, post_, lo, 1),
                    put_at(res, res_, lo, 2))

        # rows that no block writes stay zeros: masked positions
        x, q, rows, post, res = pk.over_blocks(project, (
            x, jnp.zeros((t, heads * config.qk_head_dim), dtype),
            jnp.zeros((t, latent.cache_width(config.latent_width)), dtype),
            jnp.zeros((n, t), jnp.float32), jnp.zeros((n, n, t), jnp.float32)))
        rows = pk.grid(rows)
        mixer_rows = latent.decompressed_attention(
            p["kvb"]["kernel"], pk.grid(q).reshape(b, s, heads, -1), rows,
            pk.lengths, **_latent_sizes(config)).reshape(b * s, -1)
        caches.append({"latent": jnp.pad(
            rows[:, None], ((0, 0), (0, 0), (0, max_decode_len), (0, 0)))})
        dense = "mlp" in layer

        def mix(lo, carry, layer=layer, p=p, post=post, res=res,
                mixer_rows=mixer_rows, dense=dense):
            x, normed, ffn_post = carry
            x_ = mhc.mhc_post(
                cut_at(x, lo, 1),
                nn.mm(pk.back(mixer_rows, lo), p["out"]["kernel"]),
                cut_at(post, lo, 1), cut_at(res, lo, 2))
            pre_, post_, res_ = _maps(config, layer["ffn_hc"], x_)
            u = _norm(layer["ffn_norm"], mhc.mhc_pre(x_, pre_), config)
            x = put_at(x, mhc.mhc_post(x_, _feed_forward(layer, u), post_,
                                       res_), lo, 1)
            if dense:
                return x, normed, ffn_post
            return x, put(normed, u, lo), put_at(ffn_post, post_, lo, 1)

        x, normed, ffn_post = pk.over_blocks(mix, (x, *(
            (None, None) if dense else (jnp.zeros((t, d), jnp.float32),
                                        jnp.zeros((n, t), jnp.float32)))))
        owed = None
        if not dense:
            y, routed = _experts(config, layer, normed, rows=pk.total)
            owed = (ffn_post, y)
            held = pk.held_by_example(routed.held, onto=held)
            loads.append(routed.load)
    last = jnp.maximum(pk.ends - 1, 0)
    x_last = x[:, last]
    if owed is not None:
        x_last = x_last + owing(owed[0][:, last], owed[1][last])
    h = jnp.where(pk.lengths[:, None] > 0, mhc.mhc_exit(x_last), 0.0)
    none = jnp.zeros((b,), jnp.int32)
    load = (jnp.stack(loads) if loads
            else jnp.zeros((0, config.experts_held), jnp.int32))
    return (caches, _logits(params, config, h), held, load,
            pk.blocks * pk.block,
            {"latent_rows_read": none, "latent_rows_held": none,
             "latent_rows_copied": none,
             "stream_rows": pk.lengths * 2 * config.num_layers})


def prefill(params: dict, config: XingConfig, input_ids: jax.Array,
            *, max_decode_len: int,
            row_block: int = packed.PREFILL_ROW_BLOCK) -> dict:
    """The prompts (B, seq_len), right-padded with pad_id -> the state a
    generation carries (models/packed.py), `config.prefill_rows` examples
    at a time. Its caches: per layer the latent rows of seq_len +
    max_decode_len positions, those past an example's length whatever the
    padding left (a step masks them). A row of length 0 (one that pads
    the batch) gives logits of zeros and counts nothing."""
    return packed.prefill_by_chunks(
        lambda chunk: _prefill_chunk(params, config, chunk, max_decode_len,
                                     row_block),
        input_ids, rows=config.prefill_rows, pad_id=config.pad_id)


# -- one decode step ----------------------------------------------------------


def step(params: dict, config: XingConfig, state: dict):
    """(state) -> (state', token (B,)): each example's next token
    (`packed.choose`) through the stack, its n streams (n, B, C): every
    layer writes the token's latent row at the example's own position in
    its cache and attends in the latent space. A row that pads the batch
    is routed to no expert."""
    token, finished, position, owned = packed.choose(
        state, config.pad_id, config.eos_id)
    b = token.shape[0]
    x = mhc.mhc_enter(
        params["embed"]["embedding"][token].astype(jnp.float32),
        config.hc_mult)
    caches, held = [], jnp.zeros((b,), jnp.int32)
    hit = jnp.zeros((), jnp.int32)
    latent_read = latent_held = latent_copied = jnp.zeros((b,), jnp.int32)
    for layer, cache in zip(params["layers"], state["caches"]):
        p = layer["mla"]
        pre, post, res = _maps(config, layer["attn_hc"], x)
        q, row = _mla_inputs(
            config, p, _norm(layer["norm"], mhc.mhc_pre(x, pre), config),
            position)
        mixed, cached, copied = latent.absorbed_attention(
            p["kvb"]["kernel"], q, cache["latent"], row, position, owned,
            **_latent_sizes(config))
        caches.append({"latent": cached})
        x = mhc.mhc_post(x, nn.mm(mixed, p["out"]["kernel"]), post, res)
        latent_read = latent_read + jnp.where(owned, position + 1, 0)
        latent_held = latent_held + jnp.where(owned, cached.shape[2], 0)
        latent_copied = latent_copied + jnp.where(owned, copied, 0)
        pre, post, res = _maps(config, layer["ffn_hc"], x)
        u = _norm(layer["ffn_norm"], mhc.mhc_pre(x, pre), config)
        y = _feed_forward(layer, u)
        if "moe" in layer:
            routed_y, routed = _experts(config, layer, u, valid=owned)
            y = y + routed_y
            held, hit = held + routed.held, hit + routed.hit
        x = mhc.mhc_post(x, y, post, res)
    return packed.advance(
        state, caches, _logits(params, config, mhc.mhc_exit(x)), token,
        finished, held_decode=held, hit_decode=hit,
        latent_rows_read=latent_read, latent_rows_held=latent_held,
        latent_rows_copied=latent_copied,
        stream_rows=jnp.where(owned, 2 * config.num_layers, 0)), token


# -- serving ------------------------------------------------------------------


def count_tables(config: XingConfig) -> tuple:
    """The expert layers' table, the latent caches' (Ling's columns, an
    example's rows summed over the layers) and the residual streams'."""
    from min_tfs_client_tpu.servables.decode_signatures import CountTable

    return (packed.route_table(config.top_k * config.expert_layers),
            CountTable(
                output="latent_counts", span="generate/latent",
                section="latent", columns=LATENT_COLUMNS),
            CountTable(
                output="stream_counts", span="generate/streams",
                section="streams", columns=STREAM_COLUMNS,
                derived={"sinkhorn_rounds": (
                    "stream_rows", config.hc_sinkhorn_iters)}))


def build_signatures(params: dict, config: XingConfig, *,
                     seq_len: int, max_decode_len: int,
                     batch_buckets: tuple = (1, 4, 16, 32)) -> dict:
    """`serving_default` alone (generation_signature), with the expert
    layers' counts (`route_counts`), the latent caches' (`latent_counts`)
    and the streams' (`stream_counts`): no state outlives the loop."""
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
