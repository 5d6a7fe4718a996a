"""A decoder of the Ling-3.0 (`bailing_hybrid`) kind: pre-RMSNorm blocks
whose sequence mixers are of two kinds in one stack, delta-rule linear
attention with a per-channel decay (KDA: a causal convolution over 4
rows, then ops/kda.py with a float32 state of heads x d_k x d_v, a norm a
head and a gate a head) and, one layer in six, latent attention (MLA:
keys and values compressed to one latent of 512 + 64 rotary lanes a
position, which is all the cache holds); the feed-forward layers SwiGLU,
dense in the leading layers and after them a group-limited sigmoid
top-k expert layer (parallel/moe.py: this chip's share of the experts,
dropless) beside a shared expert that every token passes. The embedding
and the head are two matrices. The multi-token-prediction module of the
published model is left out: the front decodes greedily, one token a
step, and no weight of that module is held.

Served as whole generations on `serving_default` through the
whole-generation front (servables/decode_signatures.generation_signature)
over the decode contract, `prefill(params, ids) -> state` and `step(params,
state) -> (state', token)`, both written over models/packed.py. The state
carries three kinds of memory through one loop: for a KDA layer the last
3 rows before the convolution and the delta-rule state (the same at any
context), for the MLA layer the latent cache; with each example's own
length. Latent attention has two forms: the prefill decompresses K and V
from the chunk's latents and runs the flash kernel; a decode step attends
IN THE LATENT SPACE (the up-projection absorbed into the query and into
the output), so it reads 576 values a cached position and never
decompresses the cache.

Numerics: matrices and their operands in the parameters' dtype (bfloat16
as served) with float32 accumulation; the residual stream, the norms,
the decay, beta, the delta-rule state, its update and the chunked form's
products, the scores, the softmax, the router and the logits in float32;
the convolution's window and the latent cache in the parameters' dtype.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from min_tfs_client_tpu.models import latent
from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.models import packed
from min_tfs_client_tpu.ops import kda
from min_tfs_client_tpu.parallel.moe import HeldExperts, held_experts_ffn

# The columns of `state_counts` (models/granite_hybrid.py's, so that one
# reader reads both): an example's prompt tokens, the rows the chunked
# delta rule ran for it in one KDA layer, the bytes of state it holds
# through the loop, its decode steps; and of its BATCH, on every row: the
# (row, KDA layer, step) states the decode steps held and those they moved.
STATE_COLUMNS = ("prompt_tokens", "scan_rows", "state_bytes", "steps",
                 "state_rows_held", "state_rows_moved")
# Of `latent_counts`, one row an example: the cached positions its decode
# steps' attention read (a step reads the positions up to its own) and
# those the latent cache held for it meanwhile (its whole length a step),
# and the rows the steps brought in (whole blocks where the step's kernel
# ran, `models/latent.py:absorbed_attention`; all it held elsewhere).
LATENT_COLUMNS = ("prompt_tokens", "steps", "latent_rows_read",
                  "latent_rows_held", "latent_rows_copied")


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_layers: int = 42
    # One entry a layer (longer lists are cut to num_layers): "kda" or
    # "mla"; "dense" or "moe". None: the published pattern, MLA where
    # (i + 1) % 6 == 0, dense in the two leading layers.
    layer_types: tuple | None = None
    ffn_types: tuple | None = None
    num_heads: int = 32
    head_dim: int = 128            # of a KDA key and of a KDA value
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0  # the log decay lies in (this, 0)
    kda_chunk: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 6e6
    intermediate_size: int = 6144          # of a dense layer
    moe_intermediate_size: int = 768       # of one routed expert
    shared_intermediate_size: int = 768
    num_experts: int = 512         # the router's width
    experts_held: int = 512        # this chip's share of them ...
    expert_offset: int = 0         # ... starting at this expert
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # The clamp of SwiGLU in the published model's LATE layers, one entry
    # a layer run. Its form is not published: every entry must be 0.
    expert_swiglu_limits: tuple = ()
    shared_swiglu_limits: tuple = ()
    eps: float = 1e-6
    pad_id: int = 0
    eos_id: int = 1
    dtype: str = "bfloat16"
    # Examples the prefill takes through the stack at a time: bounds its
    # activations (the KDA projections' rows and the delta rule's float32
    # operands on the (example, position) grid above all).
    prefill_rows: int = 4

    def __post_init__(self):
        n = self.num_layers
        kinds = self.layer_types or tuple(
            "mla" if (i + 1) % 6 == 0 else "kda" for i in range(n))
        ffns = self.ffn_types or tuple(
            "dense" if i < 2 else "moe" for i in range(n))
        for name, given, known in (("layer_types", kinds, {"kda", "mla"}),
                                   ("ffn_types", ffns, {"dense", "moe"})):
            given = tuple(str(v) for v in given)[:n]
            object.__setattr__(self, name, given)
            if len(given) != n:
                raise ValueError(f"{name} has fewer entries than layers")
            if set(given) - known:
                raise ValueError(f"unknown {name} in {given}")
        for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
            limits = tuple(getattr(self, name))
            object.__setattr__(self, name, limits)
            if any(limits):
                raise ValueError(
                    f"{name} {limits}: a clamped SwiGLU is not implemented "
                    "(its form is not published); only layers whose limit "
                    "is 0 can be run")
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError("the held experts lie outside the router")
        if self.num_experts % self.n_group:
            raise ValueError("the experts do not fall into whole groups")

    @property
    def kda_width(self) -> int:
        return self.num_heads * self.head_dim

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
    def state_bytes(self) -> int:
        """Of one sequence, whatever its context: the float32 delta-rule
        states and the convolution windows (in `dtype`) of the KDA
        layers."""
        layers = sum(kind == "kda" for kind in self.layer_types)
        return layers * (4 * self.num_heads * self.head_dim ** 2
                         + jnp.dtype(self.dtype).itemsize
                         * (self.conv_kernel - 1) * 3 * self.kda_width)


# -- parameters ---------------------------------------------------------------

# Gains of the seeded weights (the configuration file's `assumed.weights`
# says why each): the mixers' and the feed-forward branches'
# out-projections, MLA's query (scores of std 2.5: a softmax over 2,000
# flat scores would silence the layer), the embedding.
KDA_OUT_GAIN = 0.6
MLA_OUT_GAIN = 0.8
MLA_QUERY_GAIN = 2.5
DENSE_OUT_GAIN = 0.5
EXPERT_OUT_GAIN = 1.5
SHARED_OUT_GAIN = 0.3
# The leading channels of the residual stream that no branch writes (every
# out-projection's columns for them are 0) and that alone the routers read:
# there the stream is the token's embedding, bit for bit in any precision.
ROUTER_CHANNELS = 64


def init_params(rng: jax.Array, config: LingHybridConfig) -> dict:
    """Leaves in `config.dtype` (the small float32 ones apart: norm
    scales, the convolution, dt_bias, A_log, the router and its bias).
    Seeded so that a random-weight generation is not degenerate: an
    embedding of N(0, 1) and a head of unit gain (logits of unit scale),
    each residual branch of RMS about 0.3 (after 14 branches the
    embedding is still near half of the stream), MLA's scores of std 2.5,
    a router of unit gain with a selection bias of std 0.02, dt_bias in
    (-9, 0) and exp(A_log) in (0.5, 2): a channel's decay exp(g) spans
    0.08 to 0.999 a token, most channels remembering tens of tokens.

    The routers read `ROUTER_CHANNELS` channels of the stream that no
    branch writes. A sigmoid router's chosen scores are renormalised, so
    every chosen expert enters with a weight near 2.5 / 8 however the
    scores lie, and a choice that turns on the rounding of the rows
    before it moves logits by as much as an expert left out does (on the
    chip, the router on the whole stream: 7 of 9 prompts' first logits
    off by 0.1 to 1.8, PERF.md section 6, PR 51). On channels that hold
    the embedding alone the program's router and a float32 reference's
    see the same numbers, up to the norm's one common factor, which moves
    no order: the choice is the token's own, the same in every
    precision, and what is compared is the arithmetic."""
    dtype = jnp.dtype(config.dtype)
    d, h = config.hidden_size, config.num_heads
    hk = config.kda_width
    quiet = min(ROUTER_CHANNELS, d // 4)
    written = (jnp.arange(d) >= quiet).astype(jnp.float32)

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def out(key, shape, std):
        """An out-projection: nothing onto the routers' channels."""
        return (jax.random.normal(key, shape, jnp.float32) * std
                * written).astype(dtype)

    keys = iter(jax.random.split(rng, 12 * config.num_layers + 3))
    layers = []
    for kind, ffn in zip(config.layer_types, config.ffn_types):
        layer = {"norm": nn.rms_norm_init(d), "ffn_norm": nn.rms_norm_init(d)}
        if kind == "kda":
            layer["kda"] = {
                # q, k, v (before the convolution) and the decay's input
                "qkvf": {"kernel": normal(next(keys), (d, 4 * hk), d ** -0.5)},
                # beta and the output gate, one of each a head
                "bg": {"kernel": normal(next(keys), (d, 2 * h), d ** -0.5)},
                "conv": jax.random.uniform(
                    next(keys), (config.conv_kernel, 3 * hk),
                    minval=-0.5, maxval=0.5),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), minval=0.5, maxval=2.0)),
                "dt_bias": jax.random.uniform(
                    next(keys), (hk,), minval=-9.0, maxval=0.0),
                "norm": nn.rms_norm_init(config.head_dim),
                "out": {"kernel": out(next(keys), (hk, d),
                                      KDA_OUT_GAIN * hk ** -0.5)}}
        else:
            rank = config.kv_lora_rank
            layer["mla"] = {
                "q": {"kernel": normal(next(keys), (d, h * config.qk_head_dim),
                                       MLA_QUERY_GAIN * d ** -0.5)},
                "kva": {"kernel": normal(next(keys),
                                         (d, config.latent_width), d ** -0.5)},
                "kv_norm": nn.rms_norm_init(rank),
                # a head's columns side by side: its nope keys, its values
                "kvb": {"kernel": normal(
                    next(keys), (rank, h * (config.qk_nope_head_dim
                                            + config.v_head_dim)),
                    rank ** -0.5)},
                "g": {"kernel": normal(next(keys), (d, h), d ** -0.5)},
                "out": {"kernel": out(
                    next(keys), (h * config.v_head_dim, d),
                    MLA_OUT_GAIN * (h * config.v_head_dim) ** -0.5)}}
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
                "bias": jax.random.normal(
                    next(keys), (config.num_experts,), jnp.float32) * 0.02,
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


def _norm(params: dict, x: jax.Array, config: LingHybridConfig):
    return nn.rms_norm(params, x, eps=config.eps)


def _swiglu(w_in: jax.Array, w_out: jax.Array, x: jax.Array) -> jax.Array:
    """x (T, D) float32 (normed) through one gated layer -> float32."""
    f = w_out.shape[0]
    hidden = nn.mm(x, w_in, w_in.dtype).astype(jnp.float32)
    return nn.mm(jax.nn.silu(hidden[:, :f]) * hidden[:, f:], w_out)


def _experts(config: LingHybridConfig, layer: dict, x: jax.Array, **routing):
    return held_experts_ffn(
        HeldExperts(**layer["moe"]), x, top_k=config.top_k,
        experts_held=config.experts_held,
        expert_offset=config.expert_offset, routing="sigmoid_grouped",
        n_group=config.n_group, topk_group=config.topk_group,
        scale=config.routed_scaling_factor, **routing)


def _logits(params: dict, config: LingHybridConfig, h: jax.Array):
    return nn.mm(_norm(params["final_norm"], h, config),
                 params["head"]["kernel"])


def _kda_inputs(config: LingHybridConfig, p: dict, x: jax.Array):
    """x (T, D) float32 (normed) -> the rows before the convolution (T,
    3 x heads x d) in the parameters' dtype, and in float32 the log decay
    a key channel g (T, heads x d) in (kda_lower_bound, 0), beta (T,
    heads) and the output gate (T, heads)."""
    hk, h = config.kda_width, config.num_heads
    proj = nn.mm(x, p["qkvf"]["kernel"])
    rate = jnp.repeat(jnp.exp(p["a_log"]), config.head_dim)
    g = config.kda_lower_bound * jax.nn.sigmoid(
        rate * (proj[:, 3 * hk:] + p["dt_bias"]))
    gates = jax.nn.sigmoid(nn.mm(x, p["bg"]["kernel"]))
    return (proj[:, :3 * hk].astype(p["qkvf"]["kernel"].dtype), g,
            gates[:, :h], gates[:, h:])


def _kda_heads(config: LingHybridConfig, mixed: jax.Array):
    """The rows after the convolution (..., 3 x heads x d) -> q, k, v
    (..., heads, d) float32: q and k of unit length a head, q times
    d ** -0.5."""
    h, d = config.num_heads, config.head_dim
    q, k, v = jnp.split(mixed.astype(jnp.float32).reshape(
        *mixed.shape[:-1], 3 * h, d), 3, axis=-2)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * d ** -0.5, unit(k), v


def _kda_out(config: LingHybridConfig, p: dict, o: jax.Array,
             gate: jax.Array) -> jax.Array:
    """o (T, heads, d) float32, gate (T, heads): an RMSNorm over each
    head's channels, the head's gate, the out-projection -> (T, D)."""
    normed = _norm(p["norm"], o, config) * gate[..., None]
    return nn.mm(normed.reshape(o.shape[0], -1), p["out"]["kernel"])


def _mla_inputs(config: LingHybridConfig, p: dict, x: jax.Array,
                positions: jax.Array):
    """x (T, D) float32 (normed) at `positions` (T,) -> q (T, heads, nope
    + rope) rotated on its rope lanes, the row the cache holds (T, rank +
    rope; `latent.latent_row`), both in the parameters' dtype; and the
    output gate (T, heads) float32."""
    dtype = p["q"]["kernel"].dtype
    inv_freq = latent.plain_frequencies(config.rope_theta)
    q = latent.rotate_query(
        nn.mm(x, p["q"]["kernel"]).reshape(-1, config.num_heads,
                                           config.qk_head_dim),
        positions, inv_freq, config.qk_nope_head_dim)
    row = latent.latent_row(nn.mm(x, p["kva"]["kernel"]), p["kv_norm"],
                            positions, inv_freq, rank=config.kv_lora_rank,
                            eps=config.eps)
    return (q.astype(dtype), row.astype(dtype),
            jax.nn.sigmoid(nn.mm(x, p["g"]["kernel"])))


def _mla_out(config: LingHybridConfig, p: dict, o: jax.Array,
             gate: jax.Array) -> jax.Array:
    """o (T, heads x d_v), gate (T, heads) -> (T, D)."""
    gated = o.astype(jnp.float32).reshape(
        o.shape[0], config.num_heads, -1) * gate[..., None]
    return nn.mm(gated.reshape(o.shape[0], -1), p["out"]["kernel"])


def _latent_sizes(config: LingHybridConfig) -> dict:
    """What the two forms of latent attention (models/latent.py) take of
    this model: no stretch of the rotary, so the plain scale."""
    return dict(nope=config.qk_nope_head_dim, v_head_dim=config.v_head_dim,
                scale=config.qk_head_dim ** -0.5)


# -- prefill ------------------------------------------------------------------


def _prefill_chunk(params: dict, config: LingHybridConfig, ids: jax.Array,
                   max_decode_len: int, row_block: int):
    """Some examples (b, S) through the whole stack, as
    `packed.prefill_by_chunks` takes them, with the rows the chunked
    delta rule ran as the model's own count. The residual stream is
    PACKED (`packed.Packing`): what treats rows one by one (norms,
    projections, the convolution, rotation, gates, out-projections, the
    dense layer, the shared expert, the router, residual sums) runs over
    the blocks the real tokens fill; the delta rule and attention see
    the (example, position) grid (of the rows behind an example's last
    the delta rule makes g = 0 and beta = 0, attention masks them) and
    their output is read back by row index."""
    pk = packed.pack(ids, config.pad_id, row_block)
    b, s, t, block, cut, put = pk.b, pk.s, pk.t, pk.block, pk.cut, pk.put
    lengths, ends = pk.lengths, pk.ends
    h = params["embed"]["embedding"][pk.tokens].astype(jnp.float32)
    dtype = params["embed"]["embedding"].dtype
    taps, heads, hk = config.conv_kernel, config.num_heads, config.kda_width

    caches, held, loads, scanned = [], jnp.zeros((b,), jnp.int32), [], None
    for kind, ffn, layer in zip(config.layer_types, config.ffn_types,
                                params["layers"]):
        if kind == "kda":
            p = layer["kda"]

            def project(lo, carry, h=h, layer=layer, p=p):
                pre, mixed, g, beta, gate, tail = carry
                pre_, g_, beta_, gate_ = _kda_inputs(
                    config, p, _norm(layer["norm"], cut(h, lo), config))
                # the causal convolution over the packed rows: a tap that
                # reaches before its example's first row reads nothing
                seen = jnp.concatenate([tail, pre_]).astype(jnp.float32)
                at = cut(pk.position, lo)[:, None]
                conv = sum(
                    jnp.where(at >= taps - 1 - k, seen[k:k + block], 0.0)
                    * p["conv"][k] for k in range(taps))
                return (put(pre, pre_, lo),
                        put(mixed, jax.nn.silu(conv).astype(dtype), lo),
                        put(g, g_, lo), put(beta, beta_, lo),
                        put(gate, gate_, lo), pre_[block - (taps - 1):])

            pre, mixed, g, beta, gate, _ = pk.over_blocks(project, (
                jnp.zeros((t, 3 * hk), dtype), jnp.zeros((t, 3 * hk), dtype),
                jnp.zeros((t, hk), jnp.float32),
                jnp.zeros((t, heads), jnp.float32),
                jnp.zeros((t, heads), jnp.float32),
                jnp.zeros((taps - 1, 3 * hk), dtype)))
            q, k, v = _kda_heads(config, pk.grid(mixed))
            o, state, scanned = kda.kda_prefill(
                q, k, v, pk.grid(g).reshape(b, s, heads, config.head_dim),
                pk.grid(beta), lengths, chunk=config.kda_chunk)
            # the window decoding goes on from: the last rows before the
            # convolution of each example's REAL tokens
            back_by = jnp.arange(taps - 1)[None, :] - (taps - 1)
            window = jnp.where(
                (lengths[:, None] + back_by >= 0)[..., None],
                pre[jnp.clip(ends[:, None] + back_by, 0, t - 1)], 0)
            caches.append({"conv": window, "kda": state})
            mixer_rows = o.reshape(b * s, hk)

            def mixer_out(lo, p=p, gate=gate, mixer_rows=mixer_rows):
                return _kda_out(
                    config, p, pk.back(mixer_rows, lo).reshape(
                        block, heads, config.head_dim), cut(gate, lo))
        else:
            p = layer["mla"]

            def project(lo, carry, h=h, layer=layer, p=p):
                q, rows, gate = carry
                q_, rows_, gate_ = _mla_inputs(
                    config, p, _norm(layer["norm"], cut(h, lo), config),
                    cut(pk.position, lo))
                return (put(q, q_.reshape(block, -1), lo),
                        put(rows, rows_, lo), put(gate, gate_, lo))

            # rows that no block writes stay zeros: masked positions
            q, rows, gate = pk.over_blocks(project, (
                jnp.zeros((t, heads * config.qk_head_dim), dtype),
                jnp.zeros((t, latent.cache_width(config.latent_width)),
                          dtype),
                jnp.zeros((t, heads), jnp.float32)))
            rows = pk.grid(rows)
            out = latent.decompressed_attention(
                p["kvb"]["kernel"], pk.grid(q).reshape(b, s, heads, -1), rows,
                lengths, **_latent_sizes(config))
            caches.append({"latent": jnp.pad(
                rows[:, None], ((0, 0), (0, 0), (0, max_decode_len), (0, 0)))})
            mixer_rows = out.reshape(b * s, -1)

            def mixer_out(lo, p=p, gate=gate, mixer_rows=mixer_rows):
                return _mla_out(config, p, pk.back(mixer_rows, lo),
                                cut(gate, lo))

        dense = ffn == "dense"

        def mix(lo, carry, layer=layer, mixer_out=mixer_out, dense=dense):
            h, normed = carry
            rows = cut(h, lo) + mixer_out(lo)
            x = _norm(layer["ffn_norm"], rows, config)
            if dense:
                mlp = layer["mlp"]
                return put(h, rows + _swiglu(mlp["wi"]["kernel"],
                                             mlp["wo"]["kernel"], x),
                           lo), normed
            rows = rows + _swiglu(layer["shared"]["w_in"],
                                  layer["shared"]["w_out"], x)
            return put(h, rows, lo), put(normed, x, lo)

        h, normed = pk.over_blocks(mix, (h, None if dense else jnp.zeros(
            (t, config.hidden_size), jnp.float32)))
        if not dense:
            h, routed = _experts(config, layer, normed, rows=pk.total, onto=h)
            held = pk.held_by_example(routed.held, onto=held)
            loads.append(routed.load)
    none = jnp.zeros((b,), jnp.int32)
    load = (jnp.stack(loads) if loads
            else jnp.zeros((0, config.experts_held), jnp.int32))
    return (caches, _logits(params, config, pk.last_rows(h)), held, load,
            pk.blocks * pk.block,
            {"scan_rows": none if scanned is None else scanned,
             "latent_rows_read": none, "latent_rows_held": none,
             "latent_rows_copied": none})


def prefill(params: dict, config: LingHybridConfig, input_ids: jax.Array,
            *, max_decode_len: int,
            row_block: int = packed.PREFILL_ROW_BLOCK) -> dict:
    """The prompts (B, seq_len), right-padded with pad_id -> the state a
    generation carries (models/packed.py), `config.prefill_rows` examples
    at a time. Its caches: per KDA layer the convolution's window (its
    last 3 REAL rows) and the delta-rule state AFTER EACH EXAMPLE'S LAST
    REAL TOKEN, per MLA layer the latent rows of seq_len +
    max_decode_len positions. A row of length 0 (one that pads the
    batch) touches nothing: zero state, zero window."""
    return packed.prefill_by_chunks(
        lambda chunk: _prefill_chunk(params, config, chunk, max_decode_len,
                                     row_block),
        input_ids, rows=config.prefill_rows, pad_id=config.pad_id,
        extra_counts=("state_rows_held", "state_rows_moved"))


# -- one decode step ----------------------------------------------------------


def step(params: dict, config: LingHybridConfig, state: dict):
    """(state) -> (state', token (B,)): each example's next token
    (`packed.choose`) through the stack: a KDA layer shifts its window by
    the token's row and moves its delta-rule state one step, where it
    lies; the MLA layer writes the token's latent row at the example's
    own position and attends in the latent space. A row that pads the
    batch is routed to no expert, and its delta-rule states are neither
    read nor written."""
    token, finished, position, owned = packed.choose(
        state, config.pad_id, config.eos_id)
    b = token.shape[0]
    h = params["embed"]["embedding"][token].astype(jnp.float32)
    caches, held = [], jnp.zeros((b,), jnp.int32)
    hit = jnp.zeros((), jnp.int32)
    states_held = states_moved = jnp.zeros((), jnp.int32)
    latent_read = latent_held = latent_copied = jnp.zeros((b,), jnp.int32)
    for kind, ffn, layer, cache in zip(config.layer_types, config.ffn_types,
                                       params["layers"], state["caches"]):
        x = _norm(layer["norm"], h, config)
        if kind == "kda":
            p = layer["kda"]
            pre, g, beta, gate = _kda_inputs(config, p, x)
            seen = jnp.concatenate([cache["conv"], pre[:, None]], axis=1)
            mixed = jax.nn.silu(jnp.sum(seen.astype(jnp.float32) * p["conv"],
                                        axis=1)).astype(pre.dtype)
            q, k, v = _kda_heads(config, mixed)
            moved, o = kda.kda_step(
                cache["kda"], q, k, v,
                g.reshape(b, config.num_heads, config.head_dim), beta,
                owned=owned)
            states_held = states_held + b
            states_moved = states_moved + jnp.sum(owned, dtype=jnp.int32)
            caches.append({"conv": seen[:, 1:], "kda": moved})
            h = h + _kda_out(config, p, o, gate)
        else:
            p = layer["mla"]
            q, row, gate = _mla_inputs(config, p, x, position)
            mixed, cached, copied = latent.absorbed_attention(
                p["kvb"]["kernel"], q, cache["latent"], row, position, owned,
                **_latent_sizes(config))
            caches.append({"latent": cached})
            h = h + _mla_out(config, p, mixed, gate)
            latent_read = latent_read + jnp.where(owned, position + 1, 0)
            latent_held = latent_held + jnp.where(owned, cached.shape[2], 0)
            latent_copied = latent_copied + jnp.where(owned, copied, 0)
        x = _norm(layer["ffn_norm"], h, config)
        if ffn == "dense":
            h = h + _swiglu(layer["mlp"]["wi"]["kernel"],
                            layer["mlp"]["wo"]["kernel"], x)
            continue
        y, routed = _experts(config, layer, x, valid=owned)
        h = h + y + _swiglu(layer["shared"]["w_in"],
                            layer["shared"]["w_out"], x)
        held, hit = held + routed.held, hit + routed.hit
    return packed.advance(
        state, caches, _logits(params, config, h), token, finished,
        held_decode=held, hit_decode=hit, state_rows_held=states_held,
        state_rows_moved=states_moved, latent_rows_read=latent_read,
        latent_rows_held=latent_held, latent_rows_copied=latent_copied), token


# -- serving ------------------------------------------------------------------


def count_tables(config: LingHybridConfig) -> tuple:
    """The expert layers' table, the delta-rule state's (Granite's
    columns: the batch's two figures go as they are onto every rider) and
    the latent cache's (an example's own rows)."""
    from min_tfs_client_tpu.servables.decode_signatures import CountTable

    return (packed.route_table(config.top_k * config.expert_layers),
            CountTable(
                output="state_counts", span="generate/state",
                section="state", columns=STATE_COLUMNS,
                derived={"state_bytes": (None, config.state_bytes)},
                batch=("state_rows_held", "state_rows_moved")),
            CountTable(
                output="latent_counts", span="generate/latent",
                section="latent", columns=LATENT_COLUMNS))


def build_signatures(params: dict, config: LingHybridConfig, *,
                     seq_len: int, max_decode_len: int,
                     batch_buckets: tuple = (1, 4, 16, 32)) -> dict:
    """`serving_default` alone (generation_signature), with the expert
    layers' counts (`route_counts`), the delta-rule state's
    (`state_counts`) and the latent cache's (`latent_counts`): no state
    outlives the loop."""
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
