"""T5 encoder-decoder family (BASELINE.md config 5: seq2seq decode).

The reference is stateless request/response (SURVEY.md §7 step 9); this
family goes beyond it: autoregressive greedy decode with the KV cache held
as device state *inside one jitted call* — encode, decoder prefill, and a
lax.scan over decode steps compile to a single XLA program, so a serving
Predict("decode") does the full generation on-chip with zero host round
trips per token.

Architecture: T5 v1.0 (relative position bias shared from layer 0,
pre-RMSNorm, ReLU MLP, no biases in dense layers, tied softmax scaled by
1/sqrt(d_model)).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.models import layers as nn


@dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    num_heads: int = 8
    d_ff: int = 2048
    num_encoder_layers: int = 6
    num_decoder_layers: int = 6
    rel_pos_buckets: int = 32
    rel_pos_max_distance: int = 128
    pad_id: int = 0
    eos_id: int = 1
    decoder_start_id: int = 0

    @staticmethod
    def small(**kw) -> "T5Config":
        return T5Config(**kw)

    @staticmethod
    def tiny(**kw) -> "T5Config":
        kw.setdefault("vocab_size", 64)
        kw.setdefault("d_model", 32)
        kw.setdefault("d_kv", 8)
        kw.setdefault("num_heads", 2)
        kw.setdefault("d_ff", 64)
        kw.setdefault("num_encoder_layers", 2)
        kw.setdefault("num_decoder_layers", 2)
        kw.setdefault("rel_pos_buckets", 8)
        kw.setdefault("rel_pos_max_distance", 16)
        return T5Config(**kw)


# -- relative position bias (t5 bucketing) -----------------------------------


def _relative_bucket(relative_position: jax.Array, *, bidirectional: bool,
                     num_buckets: int, max_distance: int) -> jax.Array:
    rel = relative_position
    bucket = 0
    if bidirectional:
        num_buckets //= 2
        bucket += jnp.where(rel > 0, num_buckets, 0)
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = num_buckets // 2
    is_small = rel < max_exact
    log_ratio = (jnp.log(rel.astype(jnp.float32) / max_exact + 1e-9)
                 / np.log(max_distance / max_exact))
    large = max_exact + (log_ratio * (num_buckets - max_exact)).astype(
        jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return bucket + jnp.where(is_small, rel, large)


def relative_bias(params: dict, config: T5Config, qlen: int, klen: int, *,
                  bidirectional: bool, q_offset: jax.Array | int = 0
                  ) -> jax.Array:
    """(1, H, qlen, klen) additive bias. q_offset positions the query rows
    absolutely (decode step i attends from position i)."""
    ctx = jnp.arange(qlen)[:, None] + q_offset
    mem = jnp.arange(klen)[None, :]
    buckets = _relative_bucket(
        mem - ctx, bidirectional=bidirectional,
        num_buckets=config.rel_pos_buckets,
        max_distance=config.rel_pos_max_distance)
    # embedding table (num_buckets, H) -> (1, H, q, k)
    table = params["embedding"].astype(jnp.float32)
    return table[buckets].transpose(2, 0, 1)[None]


# -- parameters --------------------------------------------------------------


def _block_init(rng, config: T5Config, *, cross: bool) -> dict:
    n = 6 if cross else 4
    keys = iter(jax.random.split(rng, n))
    block = {
        "self_attention": nn.mha_init(next(keys), config.d_model,
                                      config.num_heads, d_kv=config.d_kv,
                                      use_bias=False),
        "self_norm": nn.rms_norm_init(config.d_model),
        "mlp": nn.mlp_init(next(keys), config.d_model, config.d_ff,
                           use_bias=False),
        "mlp_norm": nn.rms_norm_init(config.d_model),
    }
    if cross:
        block["cross_attention"] = nn.mha_init(
            next(keys), config.d_model, config.num_heads, d_kv=config.d_kv,
            use_bias=False)
        block["cross_norm"] = nn.rms_norm_init(config.d_model)
    return block


def init_params(rng: jax.Array, config: T5Config) -> dict:
    total = 3 + config.num_encoder_layers + config.num_decoder_layers
    keys = iter(jax.random.split(rng, total))
    return {
        "shared_embedding": nn.embed_init(next(keys), config.vocab_size,
                                          config.d_model, stddev=1.0),
        "encoder": {
            "rel_bias": {"embedding": jax.random.normal(
                next(keys), (config.rel_pos_buckets, config.num_heads),
                jnp.float32) * 0.1},
            "layers": [_block_init(k, config, cross=False) for k in
                       [next(keys) for _ in range(config.num_encoder_layers)]],
            "final_norm": nn.rms_norm_init(config.d_model),
        },
        "decoder": {
            "rel_bias": {"embedding": jax.random.normal(
                next(keys), (config.rel_pos_buckets, config.num_heads),
                jnp.float32) * 0.1},
            "layers": [_block_init(k, config, cross=True) for k in
                       [next(keys) for _ in range(config.num_decoder_layers)]],
            "final_norm": nn.rms_norm_init(config.d_model),
        },
    }


# -- encoder -----------------------------------------------------------------


def encode(params: dict, config: T5Config, input_ids: jax.Array,
           lengths: jax.Array) -> jax.Array:
    x = nn.embed(params["shared_embedding"], input_ids)
    enc = params["encoder"]
    s = input_ids.shape[1]
    bias = relative_bias(enc["rel_bias"], config, s, s, bidirectional=True)
    # T5 attention is unscaled (scale folded into init): scale=1.0.
    for layer in enc["layers"]:
        h = nn.rms_norm(layer["self_norm"], x)
        attn, _ = nn.mha(layer["self_attention"], h,
                         num_heads=config.num_heads, lengths=lengths,
                         bias=bias, scale=1.0)
        x = x + attn
        h = nn.rms_norm(layer["mlp_norm"], x)
        x = x + nn.mlp(layer["mlp"], h, activation=jax.nn.relu)
    return nn.rms_norm(params["encoder"]["final_norm"], x)


# -- pipeline-parallel serving (encoder stack; SURVEY.md §2.11 PP row) -------


def build_pipeline_state(params: dict, config: T5Config, *, mesh) -> dict:
    """Regroup T5 params for a pipelined ENCODER: the encoder layers
    split into `stage` contiguous groups stacked with a leading stage dim
    (sharded over the mesh's stage axis — each device holds exactly its
    stage's weights); everything else — shared embedding, relative-bias
    table, final norm, the whole decoder — replicates under "rest" (the
    decoder runs outside the pipeline on every device). Mirrors
    bert.build_pipeline_state."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from min_tfs_client_tpu.parallel.pipeline import (
        STAGE_AXIS,
        stack_stage_params,
    )

    n_stages = int(mesh.shape[STAGE_AXIS])
    if config.num_encoder_layers % n_stages:
        raise ValueError(
            f"num_encoder_layers {config.num_encoder_layers} not "
            f"divisible by {n_stages} pipeline stages")
    group = config.num_encoder_layers // n_stages
    enc_layers = params["encoder"]["layers"]
    stacked = stack_stage_params(
        [{"layers": enc_layers[i * group:(i + 1) * group]}
         for i in range(n_stages)])
    stacked = jax.tree_util.tree_map(
        lambda p: jax.device_put(jnp.asarray(p),
                                 NamedSharding(mesh, P(STAGE_AXIS))),
        stacked)
    replicate = NamedSharding(mesh, P())
    rest = {
        "shared_embedding": params["shared_embedding"],
        "decoder": params["decoder"],
        "encoder": {k: v for k, v in params["encoder"].items()
                    if k != "layers"},
    }
    rest = jax.tree_util.tree_map(
        lambda p: jax.device_put(jnp.asarray(p), replicate), rest)
    return {"stages": stacked, "rest": rest}


def pipelined_encode(pp_params: dict, config: T5Config,
                     input_ids: jax.Array, lengths: jax.Array, *,
                     mesh, n_micro: int | None = None) -> jax.Array:
    """encode() over stage-sharded params: embedding + relative bias on
    every device, the encoder layer stack as a GPipe microbatch pipeline
    (one ICI hop per stage), final norm on the drained outputs. Matches
    encode() numerics exactly — same layers, different residency."""
    import math

    from min_tfs_client_tpu.parallel.pipeline import (
        STAGE_AXIS,
        pipeline_apply,
    )

    rest = pp_params["rest"]
    b, s = input_ids.shape
    x = nn.embed(rest["shared_embedding"], input_ids)
    bias = relative_bias(rest["encoder"]["rel_bias"], config, s, s,
                         bidirectional=True)
    # pipeline_apply microbatches dim 0 of every carried leaf: broadcast
    # the (1, heads, s, s) bias so it can travel with the activations.
    bias = jnp.broadcast_to(bias, (b,) + bias.shape[1:])

    def stage_fn(stage_tree, carry):
        x, lengths, bias = carry
        for layer in stage_tree["layers"]:
            h = nn.rms_norm(layer["self_norm"], x)
            attn, _ = nn.mha(layer["self_attention"], h,
                             num_heads=config.num_heads, lengths=lengths,
                             bias=bias, scale=1.0)
            x = x + attn
            h = nn.rms_norm(layer["mlp_norm"], x)
            x = x + nn.mlp(layer["mlp"], h, activation=jax.nn.relu)
        return (x, lengths, bias)

    requested = n_micro or int(mesh.shape[STAGE_AXIS])
    x, _, _ = pipeline_apply(
        stage_fn, pp_params["stages"], (x, lengths, bias), mesh=mesh,
        # gcd keeps the microbatch schedule legal for small batch buckets
        # (batch is static under jit).
        n_micro=math.gcd(b, requested))
    return nn.rms_norm(rest["encoder"]["final_norm"], x)


# -- decoder -----------------------------------------------------------------


def _project_cross(params: dict, encoded: jax.Array) -> dict:
    """Every decoder layer's cross-attention K and V of `encoded`, for a
    loop that decodes against one `encoded` from start to end: projected
    here ONCE, before the loop, and handed to every `_decoder_positions`
    of it as `cross` (nn.cross_rows: rows, read by length)."""
    return nn.cross_rows([layer["cross_attention"]
                          for layer in params["decoder"]["layers"]], encoded)


def _decoder_positions(params: dict, config: T5Config, tokens: jax.Array,
                       step: jax.Array, caches: list[dict] | dict,
                       encoded: jax.Array | None, enc_lengths: jax.Array,
                       cross: dict | None = None,
                       ) -> tuple[jax.Array, list[dict] | dict]:
    """Decode a block of L positions: tokens (B, L) at absolute positions
    step .. step+L (causal within the block, attending the cache behind
    it). L=1 is the classic decode step; L=k+1 is a speculative verify
    block. Cross-attention reads `cross` (`_project_cross(params,
    encoded)`, the whole-generation loops) where it is given, and
    otherwise projects `encoded` itself (one step of a session).
    `caches` is a layer's dense {"self": {"k", "v"}} each (the sessions'
    layout; beams reorder it), or ONE rows cache for all layers
    (`nn.init_rows_cache`: a whole generation that only appends).
    Returns (logits (B, L, vocab), updated caches)."""
    dec = params["decoder"]
    x = nn.embed(params["shared_embedding"], tokens)
    in_rows = isinstance(caches, dict)
    max_len = (caches["key"] if in_rows else caches[0]["self"]["k"]).shape[2]
    bias = relative_bias(dec["rel_bias"], config, tokens.shape[1], max_len,
                         bidirectional=False, q_offset=step)
    new_caches = []
    for i, layer in enumerate(dec["layers"]):
        h = nn.rms_norm(layer["self_norm"], x)
        if in_rows:
            attn, caches = nn.mha_rows(
                layer["self_attention"], h, caches, i,
                num_heads=config.num_heads, bias=bias, cache_index=step,
                scale=1.0)
        else:
            attn, self_cache = nn.mha(
                layer["self_attention"], h, num_heads=config.num_heads,
                causal=True, bias=bias, cache=caches[i]["self"],
                cache_index=step, scale=1.0)
            new_caches.append({"self": self_cache})
        x = x + attn
        h = nn.rms_norm(layer["cross_norm"], x)
        if cross is None:
            attended, _ = nn.mha(
                layer["cross_attention"], h, num_heads=config.num_heads,
                kv=encoded, lengths=enc_lengths, scale=1.0)
        else:
            attended, _ = nn.mha_rows(
                layer["cross_attention"], h, cross, i,
                num_heads=config.num_heads, lengths=enc_lengths, scale=1.0)
        x = x + attended
        h = nn.rms_norm(layer["mlp_norm"], x)
        x = x + nn.mlp(layer["mlp"], h, activation=jax.nn.relu)
    x = nn.rms_norm(dec["final_norm"], x)
    # Tied output embedding, T5-style 1/sqrt(d) rescale.
    logits = jnp.einsum(
        "bld,vd->blv", x.astype(jnp.float32) / np.sqrt(config.d_model),
        params["shared_embedding"]["embedding"])
    return logits, caches if in_rows else new_caches


def _decoder_step(params: dict, config: T5Config, token: jax.Array,
                  step: jax.Array, caches: list[dict] | dict,
                  encoded: jax.Array | None, enc_lengths: jax.Array,
                  cross: dict | None = None,
                  ) -> tuple[jax.Array, list[dict] | dict]:
    """One decode position: token (B, 1) at absolute position `step`.
    Returns (logits (B, vocab), updated caches)."""
    logits, new_caches = _decoder_positions(
        params, config, token, step, caches, encoded, enc_lengths, cross)
    return logits[:, 0], new_caches


# -- paging-aware decoder (block-table KV: the step contract's math) ----------


def _cache_key(layer: int, name: str) -> tuple:
    """PagedKV arena key for decoder layer `layer`'s self-attention K or V
    — the pytree path of that leaf in the session state, which is how the
    pooled tick (decode_sessions.PagedSlotPool) keys the arenas it hands
    the step contract."""
    return ("caches", layer, "self", name)


def paged_decoder_positions(params: dict, config: T5Config,
                            tokens: jax.Array, q_start: jax.Array,
                            kv, encoded: jax.Array,
                            enc_lengths: jax.Array, *,
                            chunk_lens: jax.Array | None = None,
                            need_logits: bool = True
                            ) -> tuple[jax.Array | None, object]:
    """_decoder_positions over a block-table-paged KV store: tokens (B, L)
    at per-example absolute positions q_start (B,) .. q_start+L-1, with
    the decoder self-attention caches living in `kv` (an
    ops/attention.PagedKV keyed by _cache_key) instead of dense
    max-length blocks. Per layer the new K/V rows are APPENDED into the
    arenas (this position's rows — exactly what the dense path's
    dynamic_update_slice wrote) and attention runs through the block
    tables via ops/attention.paged_attention — the ragged Pallas kernel
    on TPU, the gather oracle elsewhere; either way reads scale with the
    pages the sequences own, not max length.

    chunk_lens (B,) marks how many of the L rows are real (a chunked
    prefill's short final chunk): rows past it write to the trash page
    and attend nothing beyond the valid keys. need_logits=False skips the
    final norm + vocab projection (prefill chunks only fill the cache).
    Returns (logits (B, L, vocab) or None, updated kv)."""
    dec = params["decoder"]
    b, length = tokens.shape
    x = nn.embed(params["shared_embedding"], tokens)
    klen = kv.tables.shape[1] * kv.block_size
    # Per-example absolute query offsets: vmap the shared bias builder.
    bias = jax.vmap(
        lambda off: relative_bias(dec["rel_bias"], config, length, klen,
                                  bidirectional=False, q_offset=off)[0]
    )(q_start)                                      # (B, H, L, klen)
    lengths_in = q_start + (chunk_lens if chunk_lens is not None
                            else jnp.int32(length))
    for i, layer in enumerate(dec["layers"]):
        h = nn.rms_norm(layer["self_norm"], x)
        p = layer["self_attention"]
        q = nn.heads(nn.dense(p["query"], h), config.num_heads)
        # K and V go in as the projection leaves them, (B, L, H * D): one
        # arena row a token, its heads side by side.
        kv = kv.append(
            {_cache_key(i, "k"): nn.dense(p["key"], h),
             _cache_key(i, "v"): nn.dense(p["value"], h)},
            row_valid=chunk_lens)
        out = kv.attend(q, _cache_key(i, "k"), _cache_key(i, "v"),
                        bias=bias, scale=1.0, lengths=lengths_in,
                        q_start=q_start)
        x = x + nn.dense(p["out"], nn.unheads(out))
        h = nn.rms_norm(layer["cross_norm"], x)
        cross, _ = nn.mha(
            layer["cross_attention"], h, num_heads=config.num_heads,
            kv=encoded, lengths=enc_lengths, scale=1.0)
        x = x + cross
        h = nn.rms_norm(layer["mlp_norm"], x)
        x = x + nn.mlp(layer["mlp"], h, activation=jax.nn.relu)
    if not need_logits:
        return None, kv
    x = nn.rms_norm(dec["final_norm"], x)
    logits = jnp.einsum(
        "bld,vd->blv", x.astype(jnp.float32) / np.sqrt(config.d_model),
        params["shared_embedding"]["embedding"])
    return logits, kv


class _T5PagedStep:
    """T5's paging-aware step contract (decode_sessions.PagedSlotPool
    `paged_step`): the pooled tick hands slot-batched dense state plus a
    PagedKV handle; decode() advances one token per active slot through
    paged_decoder_positions, prefill_chunk() streams a forced decoder
    prefix through the same Sq>1 path. Token-for-token equal to
    decode_step_state on the dense pool (the paged-decode suite asserts
    it) — the only difference is what the tick reads."""

    def __init__(self, config: T5Config, *, sampling: bool = False,
                 top_k: int = 0):
        self._config = config
        self._sampling = sampling
        self._top_k = top_k

    def decode(self, params: dict, tree: dict, kv):
        from min_tfs_client_tpu.models.quantize import maybe_dequantize

        config = self._config
        p = maybe_dequantize(params) if params is not None else params
        logits, kv = paged_decoder_positions(
            p, config, tree["token"][:, 0], kv.lengths, kv,
            tree["encoded"][:, 0], tree["enc_lengths"][:, 0])
        logits = logits[:, 0]                      # (slots, vocab)
        finished = tree["finished"][:, 0]
        if self._sampling:
            keys, subs = _split_keys(tree["key"][:, 0])
            next_token = _sample_token(
                logits, subs, tree["temperature"][:, 0], self._top_k,
                config.pad_id,
                tree["top_p"][:, 0] if "top_p" in tree else None)
        else:
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_token = jnp.where(finished, config.pad_id, next_token)
        new_finished = jnp.logical_or(finished, next_token == config.eos_id)
        new_tree = {
            "encoded": tree["encoded"],
            "enc_lengths": tree["enc_lengths"],
            "caches": tree["caches"],              # None leaves: in arenas
            "token": next_token[:, None, None],
            "finished": new_finished[:, None],
            "step": tree["step"] + 1,
        }
        if self._sampling:
            new_tree["temperature"] = tree["temperature"]
            new_tree["key"] = keys[:, None]
            if "top_p" in tree:
                new_tree["top_p"] = tree["top_p"]
        outputs = {"token": next_token[:, None],
                   "finished": new_finished[:, None]}
        return new_tree, kv, outputs

    def prefill_chunk(self, params: dict, tree: dict, kv,
                      tokens: jax.Array, chunk_lens: jax.Array,
                      next_tokens: jax.Array):
        from min_tfs_client_tpu.models.quantize import maybe_dequantize

        p = maybe_dequantize(params) if params is not None else params
        _, kv = paged_decoder_positions(
            p, self._config, tokens, kv.lengths, kv,
            tree["encoded"][:, 0], tree["enc_lengths"][:, 0],
            chunk_lens=chunk_lens, need_logits=False)
        new_tree = dict(tree)
        new_tree["token"] = next_tokens[:, :, None]
        new_tree["step"] = tree["step"] + chunk_lens
        return new_tree, kv


def greedy_decode(params: dict, config: T5Config, input_ids: jax.Array,
                  lengths: jax.Array, *, max_decode_len: int,
                  encoded: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Full generation in one traced program. Returns (output_ids
    (B, max_decode_len) padded with pad_id after EOS, output_lengths (B,)).
    `encoded` lets a caller inject encoder outputs computed elsewhere
    (the pipelined encoder); `params` then only needs the decoder +
    shared embedding."""
    b = input_ids.shape[0]
    if encoded is None:
        encoded = encode(params, config, input_ids, lengths)
    caches = nn.init_rows_cache(config.num_decoder_layers, b, max_decode_len,
                                config.num_heads * config.d_kv)
    token0 = jnp.full((b, 1), config.decoder_start_id, jnp.int32)
    cross = _project_cross(params, encoded)

    def step_fn(carry, step):
        token, caches, finished = carry
        logits, caches = _decoder_step(params, config, token, step, caches,
                                       None, lengths, cross)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_token = jnp.where(finished, config.pad_id, next_token)
        finished = jnp.logical_or(finished, next_token == config.eos_id)
        return (next_token[:, None], caches, finished), next_token

    (_, _, finished), tokens = jax.lax.scan(
        step_fn, (token0, caches, jnp.zeros((b,), bool)),
        jnp.arange(max_decode_len))
    output_ids = tokens.T  # (B, max_decode_len)
    out_lengths = jnp.sum(
        (output_ids != config.pad_id).astype(jnp.int32), axis=-1)
    return output_ids, out_lengths


def _per_example_keys(seed: jax.Array) -> jax.Array:
    """seed (B,) int32 -> (B, 2) uint32 old-style PRNG keys (plain uint32
    data so they stack/zero-init cleanly in session slot pools)."""
    return jax.vmap(
        lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(seed)


def _split_keys(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, 2) keys -> (new_keys (B, 2), subkeys (B, 2))."""
    both = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return both[:, 0], both[:, 1]


def _sample_token(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: int,
                  pad_id: int,
                  top_p: jax.Array | None = None) -> jax.Array:
    """Per-example token sampling. logits (B, V); keys (B, 2) per-example
    PRNG keys; temperature (B,) — 0 or negative means greedy for that
    example (the untouched argmax, keeping temperature-0 EXACTLY equal to
    greedy_decode). top_k is STATIC (0 = full distribution); top_p (B,)
    is per-example nucleus sampling (>= 1 disables). pad_id is masked out
    of the sampling distribution: pad marks end-of-stream on the wire, so
    a random draw must never emit it mid-generation."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    scaled = scaled.at[:, pad_id].set(-jnp.inf)
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p is not None:
        # Nucleus: keep the smallest prefix of descending-prob tokens
        # whose mass reaches top_p (the first crossing token included).
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        before = jnp.cumsum(probs, axis=-1) - probs
        keep = before < jnp.clip(top_p, 1e-6, 1.0)[:, None]
        cutoff = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_decode(params: dict, config: T5Config, input_ids: jax.Array,
                  lengths: jax.Array, *, max_decode_len: int,
                  temperature: jax.Array, seed: jax.Array,
                  top_k: int = 0,
                  top_p: jax.Array | None = None,
                  encoded: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Sampled generation: greedy_decode's scan with a categorical draw
    per step. temperature (B,) f32 per example (<= 0 -> greedy for that
    example, making this a strict superset of greedy_decode); seed (B,)
    int32 per example — identical seeds give identical streams.
    Returns (output_ids (B, max_decode_len), output_lengths (B,))."""
    b = input_ids.shape[0]
    if encoded is None:
        encoded = encode(params, config, input_ids, lengths)
    caches = nn.init_rows_cache(config.num_decoder_layers, b, max_decode_len,
                                config.num_heads * config.d_kv)
    token0 = jnp.full((b, 1), config.decoder_start_id, jnp.int32)
    keys0 = _per_example_keys(seed)
    cross = _project_cross(params, encoded)

    def step_fn(carry, step):
        token, caches, finished, keys = carry
        logits, caches = _decoder_step(params, config, token, step, caches,
                                       None, lengths, cross)
        keys, subs = _split_keys(keys)
        next_token = _sample_token(logits, subs, temperature, top_k,
                                   config.pad_id, top_p)
        next_token = jnp.where(finished, config.pad_id, next_token)
        finished = jnp.logical_or(finished, next_token == config.eos_id)
        return (next_token[:, None], caches, finished, keys), next_token

    (_, _, finished, _), tokens = jax.lax.scan(
        step_fn, (token0, caches, jnp.zeros((b,), bool), keys0),
        jnp.arange(max_decode_len))
    output_ids = tokens.T
    out_lengths = jnp.sum(
        (output_ids != config.pad_id).astype(jnp.int32), axis=-1)
    return output_ids, out_lengths


def beam_decode(params: dict, config: T5Config, input_ids: jax.Array,
                lengths: jax.Array, *, max_decode_len: int,
                beam_size: int = 4, length_penalty: float = 1.0,
                encoded: jax.Array | None = None,
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Beam search over the decoder: returns the highest-scoring finished
    sequence per example (GNMT length penalty ((5+len)/6)^alpha), falling
    back to the best alive beam when nothing finished.

    One jitted lax.scan over steps; beams ride a flattened (B*K) batch
    dim so every decoder step is one MXU-friendly batched call, and KV
    caches reorder with the beams via take_along_axis gathers. beam_size
    and length_penalty are static. Returns (output_ids (B, max_decode_len)
    pad-padded after EOS, output_lengths (B,), scores (B,) — the winning
    sequence's length-normalized log prob)."""
    b = input_ids.shape[0]
    k = beam_size
    neg = -1e9  # python float: stays concrete under jit tracing

    if encoded is None:
        encoded = encode(params, config, input_ids, lengths)
    # Beams share the prompt: project its K and V once, then tile them
    # to (B*K, ...).
    cross_k = jax.tree_util.tree_map(
        lambda rows: jnp.repeat(rows, k, axis=1),
        _project_cross(params, encoded))
    len_k = jnp.repeat(lengths, k, axis=0)
    caches = [{"self": nn.init_cache(b * k, config.num_heads,
                                     max_decode_len, config.d_kv)}
              for _ in range(config.num_decoder_layers)]

    def penalty(length):
        return ((5.0 + length.astype(jnp.float32)) / 6.0) ** length_penalty

    def gather_beams(tree, parent):  # parent (B, K) indices into K
        def g(x):
            xk = x.reshape((b, k) + x.shape[1:])
            idx = parent.reshape((b, k) + (1,) * (x.ndim - 1))
            return jnp.take_along_axis(xk, idx, axis=1).reshape(x.shape)
        return jax.tree_util.tree_map(g, tree)

    # alive: log probs (B, K) — beam 0 starts at 0, the rest at -inf so
    # step 0 expands a single root; tokens (B, K, L); cur (B*K, 1).
    alive_scores0 = jnp.tile(
        jnp.asarray([0.0] + [neg] * (k - 1), jnp.float32), (b, 1))
    state0 = dict(
        cur=jnp.full((b * k, 1), config.decoder_start_id, jnp.int32),
        alive_scores=alive_scores0,
        alive_tokens=jnp.full((b, k, max_decode_len), config.pad_id,
                              jnp.int32),
        fin_scores=jnp.full((b, k), neg, jnp.float32),
        fin_tokens=jnp.full((b, k, max_decode_len), config.pad_id,
                            jnp.int32),
        fin_lengths=jnp.zeros((b, k), jnp.int32),
        caches=caches,
    )

    def step_fn(state, step):
        logits, caches = _decoder_step(
            params, config, state["cur"], step, state["caches"],
            None, len_k, cross_k)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        v = logp.shape[-1]
        logp = logp.reshape(b, k, v)
        # A beam must never extend with pad (pad is padding, not a move).
        logp = logp.at[:, :, config.pad_id].set(neg)
        cand = state["alive_scores"][:, :, None] + logp      # (B, K, V)
        flat = cand.reshape(b, k * v)
        # 2K candidates: even if K of them are EOS, K alive survive.
        top_scores, top_idx = jax.lax.top_k(flat, 2 * k)
        parent = top_idx // v                                 # (B, 2K)
        token = (top_idx % v).astype(jnp.int32)

        seqs = jnp.take_along_axis(
            state["alive_tokens"], parent[:, :, None], axis=1)
        seqs = seqs.at[:, :, step].set(token)                 # wrote pos

        is_eos = token == config.eos_id
        # -- finished pool: EOS candidates, length-normalized, merged
        # with the existing pool; keep top K.
        fin_cand = jnp.where(is_eos,
                             top_scores / penalty(step + 1), neg)
        all_fin_scores = jnp.concatenate(
            [state["fin_scores"], fin_cand], axis=1)          # (B, 3K)
        all_fin_tokens = jnp.concatenate(
            [state["fin_tokens"], seqs], axis=1)
        all_fin_lengths = jnp.concatenate(
            [state["fin_lengths"],
             jnp.full((b, 2 * k), step + 1, jnp.int32)], axis=1)
        fs, fi = jax.lax.top_k(all_fin_scores, k)
        fin_tokens = jnp.take_along_axis(
            all_fin_tokens, fi[:, :, None], axis=1)
        fin_lengths = jnp.take_along_axis(all_fin_lengths, fi, axis=1)

        # -- alive: the top K non-EOS candidates.
        alive_cand = jnp.where(is_eos, neg, top_scores)
        as_, ai = jax.lax.top_k(alive_cand, k)                # (B, K)
        alive_parent = jnp.take_along_axis(parent, ai, axis=1)
        alive_token = jnp.take_along_axis(token, ai, axis=1)
        alive_tokens = jnp.take_along_axis(seqs, ai[:, :, None], axis=1)
        caches = gather_beams(caches, alive_parent)

        return dict(
            cur=alive_token.reshape(b * k, 1),
            alive_scores=as_,
            alive_tokens=alive_tokens,
            fin_scores=fs,
            fin_tokens=fin_tokens,
            fin_lengths=fin_lengths,
            caches=caches,
        ), None

    state, _ = jax.lax.scan(step_fn, state0, jnp.arange(max_decode_len))

    # Prefer finished beams; fall back to the best alive (normalized at
    # full length) when nothing finished for an example.
    alive_norm = state["alive_scores"][:, 0] / penalty(
        jnp.int32(max_decode_len))
    best_fin = state["fin_scores"][:, 0]
    use_fin = best_fin > neg / 2
    out = jnp.where(use_fin[:, None], state["fin_tokens"][:, 0],
                    state["alive_tokens"][:, 0])
    out_len = jnp.where(use_fin, state["fin_lengths"][:, 0],
                        jnp.int32(max_decode_len))
    scores = jnp.where(use_fin, best_fin, alive_norm)
    # Zero out positions past the winning length (EOS kept, pad after).
    pos = jnp.arange(max_decode_len)[None, :]
    out = jnp.where(pos < out_len[:, None], out, config.pad_id)
    return out, out_len, scores


def speculative_decode(
    params: dict,
    config: T5Config,
    draft_params: dict,
    draft_config: T5Config,
    input_ids: jax.Array,
    lengths: jax.Array,
    *,
    max_decode_len: int,
    k: int = 4,
    kv_block_size: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy speculative decoding: draft proposes k tokens, the target
    verifies all of them in ONE decoder pass (`_decoder_positions` block).

    kv_block_size > 0 composes speculation with paging: the TARGET's
    self-attention caches live in block-table page arenas and every
    verify block (Sq=k+1, the multi-query path) runs through
    ops/attention.paged_attention — the ragged Pallas kernel on TPU —
    instead of dense max-length caches. The draft's caches stay dense
    (it is a throwaway helper model whose quality never touches
    outputs). Token streams are identical either way; the paged-decode
    suite asserts it.

    Token-exact versus `greedy_decode(params, config, ...)` by
    construction: only tokens the target's own greedy argmax would emit
    are ever accepted, so the draft quality affects speed, never output.
    Per round the target runs once over k+1 positions and advances
    n_accepted+1 tokens (1..k+1); with a good draft that's ~k+1 tokens
    per target pass instead of 1 — the MXU sees k+1-wide matmuls instead
    of width-1 vectors, which is where the speedup comes from on TPU.

    Batched: examples advance in lockstep by the batch-min acceptance
    (conservative, still exact); finished examples emit pad (oracle
    semantics). Returns (output_ids (B, max_decode_len), output_lengths
    (B,), target_passes scalar int32 — rounds of target execution, for
    acceptance-rate accounting).
    """
    b = input_ids.shape[0]
    encoded_t = encode(params, config, input_ids, lengths)
    cross_d = _project_cross(
        draft_params, encode(draft_params, draft_config, input_ids, lengths))
    cache_len = max_decode_len + k  # room for the last round's overshoot
    if kv_block_size:
        # Target caches as page arenas + per-example block tables (each
        # example owns a contiguous page range; the layout under test is
        # the block-table indirection the serving pool uses, so verify
        # blocks exercise the kernel's Sq>1 path end to end).
        bs = int(kv_block_size)
        pages_per = -(-cache_len // bs)
        n_pages = b * pages_per
        from min_tfs_client_tpu.ops.attention import PagedKV

        caches_t = {
            _cache_key(i, name): PagedKV.arena(
                n_pages, bs, (config.num_heads, config.d_kv),
                nn.COMPUTE_DTYPE)
            for i in range(config.num_decoder_layers) for name in ("k", "v")}
        spec_tables = jnp.asarray(
            np.arange(n_pages, dtype=np.int32).reshape(b, pages_per))
    else:
        caches_t = [{"self": nn.init_cache(b, config.num_heads, cache_len,
                                           config.d_kv)}
                    for _ in range(config.num_decoder_layers)]
        cross_t = _project_cross(params, encoded_t)
    caches_d = [{"self": nn.init_cache(b, draft_config.num_heads, cache_len,
                                       draft_config.d_kv)}
                for _ in range(draft_config.num_decoder_layers)]
    out0 = jnp.full((b, max_decode_len + k + 1), config.pad_id, jnp.int32)
    cur0 = jnp.full((b, 1), config.decoder_start_id, jnp.int32)

    def cond(carry):
        step, _, finished, *_ = carry
        return jnp.logical_and(step < max_decode_len,
                               jnp.logical_not(jnp.all(finished)))

    def body(carry):
        step, cur, finished, caches_t, caches_d, out, passes = carry

        # Draft: k greedy single-token steps from `cur`.
        def dstep(c, i):
            tok, caches_d = c
            logits, caches_d = _decoder_step(
                draft_params, draft_config, tok, step + i, caches_d,
                None, lengths, cross_d)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return (nxt, caches_d), nxt[:, 0]

        (_, caches_d), d_tokens = jax.lax.scan(
            dstep, (cur, caches_d), jnp.arange(k))
        d_tokens = d_tokens.T  # (B, k)

        # Target: ONE pass over the k+1-position block [cur, d_1..d_k].
        block = jnp.concatenate([cur, d_tokens], axis=1)  # (B, k+1)
        if kv_block_size:
            q_start = jnp.full((b,), step, jnp.int32)
            kv = PagedKV(caches_t, spec_tables, q_start,
                         block_size=bs, trash=n_pages)
            logits, kv = paged_decoder_positions(
                params, config, block, q_start, kv, encoded_t, lengths)
            caches_t = kv.arenas
        else:
            logits, caches_t = _decoder_positions(
                params, config, block, step, caches_t, None, lengths,
                cross_t)
        t_pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)

        # Acceptance: longest prefix where the draft matched the target's
        # own greedy choice; batch-min keeps examples in lockstep.
        # Finished rows count as all-accepted — their emissions are
        # pad-masked regardless, and letting their (meaningless) draft
        # mismatches pin the batch min would degrade every live row to
        # one token per round.
        matches = (d_tokens == t_pred[:, :k]).astype(jnp.int32)
        matches = jnp.where(finished[:, None], 1, matches)
        n_acc = jnp.min(jnp.sum(jnp.cumprod(matches, axis=1), axis=1))
        n_emit = n_acc + 1  # accepted drafts + the target's bonus token

        # Oracle emission semantics: finished examples emit pad; EOS
        # flips finished from the next position on.
        def emit(fin, raw):
            tok = jnp.where(fin, config.pad_id, raw)
            return jnp.logical_or(fin, tok == config.eos_id), tok

        finished_in = finished
        _, emitted = jax.lax.scan(emit, finished_in, t_pred.T)
        emitted = emitted.T  # (B, k+1)
        # The scan's final flag saw positions beyond n_emit (not actually
        # emitted — they are overwritten next round or masked after the
        # loop); recompute `finished` over the kept prefix only.
        kept = jnp.arange(k + 1)[None, :] < n_emit
        finished = jnp.logical_or(
            finished_in,
            jnp.any(jnp.logical_and(emitted == config.eos_id, kept),
                    axis=1))

        out = jax.lax.dynamic_update_slice(out, emitted, (0, step))
        cur = jnp.take_along_axis(
            emitted, jnp.full((b, 1), n_acc, jnp.int32), axis=1)
        return (step + n_emit, cur, finished, caches_t, caches_d, out,
                passes + 1)

    step, _, finished, _, _, out, passes = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), cur0, jnp.zeros((b,), bool), caches_t, caches_d,
         out0, jnp.int32(0)))
    # Positions past the final frontier were never emitted: oracle pads
    # them (the loop only exits early when every example is finished).
    pos = jnp.arange(max_decode_len + k + 1)[None, :]
    out = jnp.where(pos < step, out, config.pad_id)[:, :max_decode_len]
    out_lengths = jnp.sum((out != config.pad_id).astype(jnp.int32), axis=-1)
    return out, out_lengths, passes


# -- servable construction ---------------------------------------------------


def build_signatures(params: dict, config: T5Config, *, seq_len: int,
                     max_decode_len: int,
                     continuous_batching: bool = False,
                     max_sessions: int = 64,
                     session_ttl_s: float = 600.0,
                     draft_params: dict | None = None,
                     draft_config: "T5Config | None" = None,
                     speculative_k: int = 4,
                     sampling_top_k: int = 0,
                     sampling_top_p: bool = False,
                     session_sampling: bool = False,
                     beam_size: int = 0,
                     beam_length_penalty: float = 1.0,
                     pipeline_mesh=None,
                     pipeline_n_micro: int | None = None,
                     kv_block_size: int | None = None,
                     kv_num_blocks: int | None = None,
                     kv_evict_policy: str | None = None,
                     kv_prefill_chunk: int | None = None) -> dict:
    from min_tfs_client_tpu.ops.attention import rows_block, rows_copied
    from min_tfs_client_tpu.servables import decode_signatures
    from min_tfs_client_tpu.servables.decode_sessions import Paging
    from min_tfs_client_tpu.servables.servable import Signature, TensorSpec

    # A kv_* knob left None defers to the server flags, through the
    # loader's paging_scope; resolved here, once, for the sessions' pool
    # and the speculative verify blocks alike.
    paging = Paging.resolve(kv_block_size, kv_num_blocks, kv_evict_policy,
                            kv_prefill_chunk)

    # With `pipeline_mesh` (a Mesh carrying a "stage" axis) the ENCODER
    # stack serves pipeline-parallel for the whole-generation surfaces
    # (decode/serving_default, encode, decode_sampled, decode_beam):
    # stage-resident encoder weights, GPipe microbatch schedule, decoder
    # replicated (it runs the autoregressive scan on every device).
    # Speculative decoding and sessions keep the standard replicated
    # tree (their prefill/step state machinery owns the param layout).
    if pipeline_mesh is not None:
        sig_params = build_pipeline_state(params, config,
                                          mesh=pipeline_mesh)

        def run_encode(tree, ids, lengths):
            return pipelined_encode(tree, config, ids, lengths,
                                    mesh=pipeline_mesh,
                                    n_micro=pipeline_n_micro)

        def dec_tree(tree):
            return tree["rest"]
    else:
        sig_params = params

        def run_encode(tree, ids, lengths):
            return encode(tree, config, ids, lengths)

        def dec_tree(tree):
            return tree

    def decode_fn(tree, inputs):
        ids = jnp.asarray(inputs["input_ids"], jnp.int32)
        lengths = jnp.sum((ids != config.pad_id).astype(jnp.int32),
                          axis=-1)
        output_ids, out_lengths = greedy_decode(
            dec_tree(tree), config, ids, lengths,
            max_decode_len=max_decode_len,
            encoded=run_encode(tree, ids, lengths))
        return {"output_ids": output_ids, "output_lengths": out_lengths}

    def encode_sig_fn(tree, inputs):
        ids = jnp.asarray(inputs["input_ids"], jnp.int32)
        lengths = jnp.sum((ids != config.pad_id).astype(jnp.int32),
                          axis=-1)
        return {"encodings": run_encode(tree, ids,
                                        lengths).astype(jnp.float32)}

    block = rows_block(seq_len)
    # What ONE layer's self-attention copies of an example's cache over
    # the steps of a generation: step t sees t + 1 keys.
    self_rows_read = int(np.sum(rows_copied(
        np.arange(max_decode_len) + 1, max_decode_len)))

    def note_reads(signature, inputs):
        """The `on_request` of the whole-generation signatures: what the
        request's own example(s) make the decode steps' attention read,
        as two spans of no duration on its trace. `generate/cross`, every
        step's cross-attention (`input_tokens`, its non-pad tokens;
        `blocks_read`, the ceil(tokens / block) blocks of K and of V a
        layer reads for it; `blocks_held`, the seq_len / block it holds).
        `generate/self`, the steps' self-attention over the cache
        (`rows_read`, the rows of K and of V ONE layer copies for it,
        summed over the generation's steps: each step its keys so far to
        the tile; `rows_held`, steps x the cache's max_decode_len rows).
        All five also go into the process's counters
        (`/monitoring/runtime`, `route`, under the signature's label)."""
        tokens = np.sum(np.asarray(inputs["input_ids"]) != config.pad_id,
                        axis=-1).reshape(-1)
        cross = {"input_tokens": int(tokens.sum()),
                 "blocks_read": int(np.sum(-(-tokens // block))),
                 "blocks_held": int(tokens.size * (seq_len // block))}
        cache = {"rows_read": int(tokens.size * self_rows_read),
                 "rows_held": int(tokens.size * max_decode_len ** 2)}
        decode_signatures.note_generation(
            signature, "route", {"generate/cross": cross,
                                 "generate/self": cache}, {**cross, **cache})

    decode_sig = Signature(
        fn=decode_fn,
        params=sig_params,
        inputs={"input_ids": TensorSpec(np.int32, (None, seq_len))},
        outputs={"output_ids": TensorSpec(np.int32, (None, max_decode_len)),
                 "output_lengths": TensorSpec(np.int32, (None,))},
        # Decode compiles are expensive: serve a small bucket ladder.
        batch_buckets=(1, 4, 16, 32),
        # a padding row is an input of length 0: its cross-attention
        # reads nothing (a repeat of row 0 would read row 0's blocks)
        batch_pad_values={"input_ids": config.pad_id},
        on_request=note_reads,
    )

    encode_sig = Signature(
        fn=encode_sig_fn,
        params=sig_params,
        inputs={"input_ids": TensorSpec(np.int32, (None, seq_len))},
        outputs={"encodings": TensorSpec(
            np.float32, (None, seq_len, config.d_model))},
        batch_buckets=(1, 4, 16, 32),
    )

    def sampled_fn(tree, inputs):
        ids = jnp.asarray(inputs["input_ids"], jnp.int32)
        lens = jnp.sum((ids != config.pad_id).astype(jnp.int32), axis=-1)
        out_ids, out_lengths = sample_decode(
            dec_tree(tree), config, ids, lens,
            max_decode_len=max_decode_len,
            temperature=jnp.asarray(inputs["temperature"], jnp.float32),
            seed=jnp.asarray(inputs["seed"], jnp.int32),
            top_k=sampling_top_k,
            top_p=(jnp.asarray(inputs["top_p"], jnp.float32)
                   if sampling_top_p else None),
            encoded=run_encode(tree, ids, lens))
        return {"output_ids": out_ids, "output_lengths": out_lengths}

    sampled_inputs = {"input_ids": TensorSpec(np.int32, (None, seq_len)),
                      "temperature": TensorSpec(np.float32, (None,)),
                      "seed": TensorSpec(np.int32, (None,))}
    if sampling_top_p:
        # Nucleus is opt-in: its per-step full-vocab sort only compiles
        # into the executable when the export asks for it.
        sampled_inputs["top_p"] = TensorSpec(np.float32, (None,))
    sampled_sig = Signature(
        fn=sampled_fn,
        params=sig_params,
        inputs=sampled_inputs,
        outputs={"output_ids": TensorSpec(np.int32, (None, max_decode_len)),
                 "output_lengths": TensorSpec(np.int32, (None,))},
        batch_buckets=(1, 4, 16, 32),
        # a padding row is an input of length 0: its cross-attention
        # reads nothing (a repeat of row 0 would read row 0's blocks)
        batch_pad_values={"input_ids": config.pad_id},
        on_request=note_reads,
    )

    signatures = {"serving_default": decode_sig, "decode": decode_sig,
                  "decode_sampled": sampled_sig, "encode": encode_sig}

    if beam_size:
        def beam_fn(tree, inputs):
            ids = jnp.asarray(inputs["input_ids"], jnp.int32)
            lens = jnp.sum((ids != config.pad_id).astype(jnp.int32),
                           axis=-1)
            out_ids, out_lengths, scores = beam_decode(
                dec_tree(tree), config, ids, lens,
                max_decode_len=max_decode_len,
                beam_size=beam_size, length_penalty=beam_length_penalty,
                encoded=run_encode(tree, ids, lens))
            return {"output_ids": out_ids, "output_lengths": out_lengths,
                    "scores": scores}

        signatures["decode_beam"] = Signature(
            fn=beam_fn,
            params=sig_params,
            inputs={"input_ids": TensorSpec(np.int32, (None, seq_len))},
            outputs={"output_ids": TensorSpec(
                         np.int32, (None, max_decode_len)),
                     "output_lengths": TensorSpec(np.int32, (None,)),
                     "scores": TensorSpec(np.float32, (None,))},
            batch_buckets=(1, 4, 16, 32),
        )

    if draft_params is not None:
        if draft_config is None:
            raise ValueError("draft_params requires draft_config")
        # Speculation composes with paging: when the export/server enables
        # the paged KV store, the target's verify blocks run through the
        # block-table kernel path too (same knob, same default-off).
        def spec_fn(bundle, inputs):
            ids = jnp.asarray(inputs["input_ids"], jnp.int32)
            lens = jnp.sum((ids != config.pad_id).astype(jnp.int32),
                           axis=-1)
            out_ids, out_lengths, passes = speculative_decode(
                bundle["target"], config, bundle["draft"],
                draft_config, ids, lens,
                max_decode_len=max_decode_len, k=speculative_k,
                kv_block_size=paging.block_size)
            return {"output_ids": out_ids,
                    "output_lengths": out_lengths,
                    "target_passes": jnp.broadcast_to(
                        passes, out_lengths.shape)}

        signatures["decode_speculative"] = Signature(
            fn=spec_fn,
            # BOTH weight trees ride as jit arguments: a closed-over
            # draft would be re-baked as constants into every batch
            # bucket's executable.
            params={"target": params, "draft": draft_params},
            inputs={"input_ids": TensorSpec(np.int32, (None, seq_len))},
            outputs={
                "output_ids": TensorSpec(np.int32, (None, max_decode_len)),
                "output_lengths": TensorSpec(np.int32, (None,)),
                "target_passes": TensorSpec(np.int32, (None,)),
            },
            batch_buckets=(1, 4, 16, 32),
        )

    signatures.update(decode_signatures.build_session_signatures(
        params,
        decode_model(config, max_decode_len=max_decode_len,
                     sampling=session_sampling,
                     sampling_top_k=sampling_top_k,
                     sampling_top_p=sampling_top_p),
        seq_len=seq_len, max_decode_len=max_decode_len,
        max_sessions=max_sessions, session_ttl_s=session_ttl_s,
        continuous_batching=continuous_batching, paging=paging))
    return signatures


# -- per-session incremental decode (repeated Predict() over the wire) -------


def prefill_state(params: dict, config: T5Config, input_ids: jax.Array,
                  *, max_decode_len: int,
                  temperature: jax.Array | None = None,
                  seed: jax.Array | None = None,
                  top_p: jax.Array | None = None,
                  prefix_ids: jax.Array | None = None) -> dict:
    """Encode the prompt and build empty caches: the device state one
    decode session carries between Predict("decode_step") calls. With
    `temperature`/`seed` (B,) the state also carries per-example PRNG
    keys and sampling temperature (sampled sessions); absent, steps are
    greedy.

    `prefix_ids` (B, max_decode_len; pad-suffixed, at least one real
    token, one shared length per batch) is a FORCED decoder prefix: the
    MONOLITHIC prefill runs the decoder over the whole (static-width)
    block in one pass — _decoder_positions' causal prompt mode — leaving
    the caches warm through position P-1, step=P, and the last prefix
    token queued as the next decode input. Cache rows past P hold
    garbage; they are masked (and later overwritten) exactly like the
    unwritten zeros of a fresh cache. The paged step-contract pool skips
    this path and streams the same prefix CHUNKED through the ragged
    kernel instead — token streams are asserted identical."""
    b = input_ids.shape[0]
    lengths = jnp.sum((input_ids != config.pad_id).astype(jnp.int32), axis=-1)
    encoded = encode(params, config, input_ids, lengths)
    caches = [{"self": nn.init_cache(b, config.num_heads, max_decode_len,
                                     config.d_kv)}
              for _ in range(config.num_decoder_layers)]
    state = {
        "encoded": encoded,
        "enc_lengths": lengths,
        "caches": caches,
        "token": jnp.full((b, 1), config.decoder_start_id, jnp.int32),
        "finished": jnp.zeros((b,), jnp.bool_),
        "step": jnp.int32(0),
    }
    if prefix_ids is not None:
        prefix = jnp.asarray(prefix_ids, jnp.int32)
        plen = jnp.sum((prefix[0] != config.pad_id).astype(jnp.int32))
        # Decoder inputs for positions 0..W-1: start token, then the
        # prefix shifted right; rows at or past plen compute garbage
        # K/V that stays masked behind `step` until overwritten.
        block = jnp.concatenate([state["token"], prefix[:, :-1]], axis=1)
        _, caches = _decoder_positions(
            params, config, block, jnp.int32(0), caches, encoded, lengths)
        state["caches"] = caches
        state["step"] = plen
        state["token"] = jnp.take_along_axis(
            prefix, jnp.full((b, 1), plen - 1, jnp.int32), axis=1)
    if temperature is not None:
        state["temperature"] = jnp.asarray(temperature, jnp.float32)
        state["key"] = _per_example_keys(jnp.asarray(seed, jnp.int32))
        if top_p is not None:
            # Present only when nucleus sampling is enabled at build
            # time: its per-step full-vocab sort then compiles in.
            state["top_p"] = jnp.asarray(top_p, jnp.float32)
    return state


def decode_step_state(params: dict, config: T5Config, state: dict,
                      *, top_k: int = 0) -> tuple[dict, jax.Array]:
    """Advance one token. Pure: (state) -> (state', token); jitted with
    the state donated so the KV caches update in place in HBM. Sampled
    when the state carries temperature/key (see prefill_state), greedy
    otherwise — the choice is part of the traced structure."""
    logits, caches = _decoder_step(
        params, config, state["token"], state["step"], state["caches"],
        state["encoded"], state["enc_lengths"])
    if "temperature" in state:
        keys, subs = _split_keys(state["key"])
        next_token = _sample_token(logits, subs, state["temperature"],
                                   top_k, config.pad_id,
                                   state.get("top_p"))
    else:
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    next_token = jnp.where(state["finished"], config.pad_id, next_token)
    finished = jnp.logical_or(state["finished"],
                              next_token == config.eos_id)
    new_state = {
        "encoded": state["encoded"],
        "enc_lengths": state["enc_lengths"],
        "caches": caches,
        "token": next_token[:, None],
        "finished": finished,
        "step": state["step"] + 1,
    }
    if "temperature" in state:
        new_state["temperature"] = state["temperature"]
        new_state["key"] = keys
        if "top_p" in state:
            new_state["top_p"] = state["top_p"]
    return new_state, next_token


def decode_model(config: T5Config, *, max_decode_len: int,
                 sampling: bool = False, sampling_top_k: int = 0,
                 sampling_top_p: bool = False):
    """T5 as servables/decode_signatures.DecodeModel: everything the
    session surface needs to know about this model, and all that is T5
    in it — the prefill, the dense one-token step, the paged step
    contract, which leaves page, the two token ids."""
    from min_tfs_client_tpu.models.quantize import maybe_dequantize
    from min_tfs_client_tpu.servables.decode_signatures import DecodeModel

    names = ()
    if sampling:
        names = ("temperature", "seed") + (("top_p",) if sampling_top_p
                                            else ())

    def prefill_fn(p, ids, *rest):
        """rest: the sampling extras (when built with sampling), then
        optionally a forced decoder prefix — the trailing-arity call is
        decode_init_prefix's monolithic dense path; each arity jits its
        own trace."""
        extras = dict(zip(names, rest))
        prefix = rest[len(names)] if len(rest) > len(names) else None
        return prefill_state(maybe_dequantize(p), config, ids,
                             max_decode_len=max_decode_len,
                             prefix_ids=prefix, **extras)

    def step_fn(p, state):
        return decode_step_state(maybe_dequantize(p), config, state,
                                 top_k=sampling_top_k)

    def paged_axis_fn(path):
        # Page the decoder self-attention caches: leaves under "caches"
        # named k/v, seq axis 2 of their (1, H, max_decode_len, d_kv)
        # layout. Everything else (encoded prompt, token, PRNG keys, ...)
        # stays dense — it is fully used from the first step.
        return 2 if ("caches" in path and path[-1] in ("k", "v")) else None

    return DecodeModel(
        name="t5", prefill=prefill_fn, step=step_fn,
        paged_step=_T5PagedStep(config, sampling=sampling,
                                top_k=sampling_top_k),
        paged_axis_fn=paged_axis_fn,
        decoder_start_id=config.decoder_start_id, pad_id=config.pad_id,
        sampling_inputs=names)


def build_session_signatures(params: dict, config: T5Config, *, seq_len: int,
                             max_decode_len: int,
                             max_sessions: int = 64,
                             session_ttl_s: float = 600.0,
                             continuous_batching: bool = False,
                             sampling: bool = False,
                             sampling_top_k: int = 0,
                             sampling_top_p: bool = False,
                             kv_block_size: int | None = None,
                             kv_num_blocks: int | None = None,
                             kv_evict_policy: str | None = None,
                             kv_prefill_chunk: int | None = None) -> dict:
    """T5's decode sessions (decode_init / decode_init_prefix /
    decode_step / decode_close): servables/decode_signatures'
    `build_session_signatures` over `decode_model`. A kv_* knob left None
    defers to the server flags (--kv_block_size etc., through the
    loader's paging_scope); kv_block_size 0 forces the dense slot pool."""
    from min_tfs_client_tpu.servables import decode_signatures
    from min_tfs_client_tpu.servables.decode_sessions import Paging

    return decode_signatures.build_session_signatures(
        params,
        decode_model(config, max_decode_len=max_decode_len,
                     sampling=sampling, sampling_top_k=sampling_top_k,
                     sampling_top_p=sampling_top_p),
        seq_len=seq_len, max_decode_len=max_decode_len,
        max_sessions=max_sessions, session_ttl_s=session_ttl_s,
        continuous_batching=continuous_batching,
        paging=Paging.resolve(kv_block_size, kv_num_blocks,
                              kv_evict_policy, kv_prefill_chunk))
