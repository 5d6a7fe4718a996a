"""BERT encoder family (BASELINE.md config 3: BERT-base, batch 1/32).

TPU-first re-design of the capability the reference serves as an opaque
SavedModel graph (servables/tensorflow/ runs it through Session::Run):
here the encoder is a pure-JAX function built from models/layers.py blocks
— bf16 on the MXU, flash attention, static shapes per batch bucket — and
exposed through the same Predict/Classify/Regress signature contract
(predict_util.cc:188-206; classifier.h:16-90 scores/classes outputs).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.tensor.example_codec import FeatureSpec


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    num_labels: int = 2
    layer_norm_eps: float = 1e-12
    # Switch-MoE FFN: >0 replaces every layer's dense MLP with a routed
    # expert layer (parallel/moe.py); served expert-parallel when the
    # export's sharding mesh carries an "expert" axis (SURVEY.md §2.11 EP).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        """Test-scale config: same code paths, toy dimensions."""
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 32)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("intermediate_size", 64)
        kw.setdefault("max_position", 64)
        return BertConfig(**kw)


def init_params(rng: jax.Array, config: BertConfig) -> dict:
    keys = iter(jax.random.split(rng, 5 + 2 * config.num_layers))
    params = {
        "embeddings": {
            "word": nn.embed_init(next(keys), config.vocab_size,
                                  config.hidden_size),
            "position": nn.embed_init(next(keys), config.max_position,
                                      config.hidden_size),
            "token_type": nn.embed_init(next(keys), config.type_vocab_size,
                                        config.hidden_size),
            "norm": nn.layer_norm_init(config.hidden_size),
        },
        "layers": [],
        "pooler": nn.dense_init(next(keys), config.hidden_size,
                                config.hidden_size),
        "head": nn.dense_init(next(keys), config.hidden_size,
                              config.num_labels),
    }
    for _ in range(config.num_layers):
        layer = {
            "attention": nn.mha_init(next(keys), config.hidden_size,
                                     config.num_heads),
            "attention_norm": nn.layer_norm_init(config.hidden_size),
            "mlp_norm": nn.layer_norm_init(config.hidden_size),
        }
        if config.moe_experts:
            from min_tfs_client_tpu.parallel.moe import init_moe_params

            # Plain dict (not the MoeParams NamedTuple): the npz
            # round-trip in models/export.py preserves dicts exactly.
            layer["moe"] = init_moe_params(
                next(keys), config.hidden_size, config.intermediate_size,
                config.moe_experts)._asdict()
        else:
            layer["mlp"] = nn.mlp_init(next(keys), config.hidden_size,
                                       config.intermediate_size)
        params["layers"].append(layer)
    return params


def encode(params: dict, config: BertConfig, input_ids: jax.Array,
           attention_mask: jax.Array,
           token_type_ids: jax.Array | None = None,
           seq_mesh=None) -> jax.Array:
    """(B, S) ids -> (B, S, H) contextual embeddings. Post-LN transformer.

    With `seq_mesh` (a Mesh carrying a "seq" axis), every self-attention
    runs sequence-parallel over the ICI ring (ring_attention) — the
    long-context serving path for sequences whose scores would not fit
    one chip."""
    b, s = input_ids.shape
    emb = params["embeddings"]
    x = nn.embed(emb["word"], input_ids)
    x = x + nn.embed(emb["position"], jnp.arange(s)[None, :])
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(input_ids)
    x = x + nn.embed(emb["token_type"], token_type_ids)
    x = nn.layer_norm(emb["norm"], x, eps=config.layer_norm_eps)

    lengths = nn.lengths_from_mask(attention_mask)
    for layer in params["layers"]:
        attn, _ = nn.mha(layer["attention"], x, num_heads=config.num_heads,
                         lengths=lengths, seq_mesh=seq_mesh)
        x = nn.layer_norm(layer["attention_norm"], x + attn,
                          eps=config.layer_norm_eps)
        x = nn.layer_norm(layer["mlp_norm"], x + _ffn(layer, config, x),
                          eps=config.layer_norm_eps)
    return x


def _ffn(layer: dict, config: BertConfig, x: jax.Array) -> jax.Array:
    """Dense MLP, or the Switch-MoE layer when the config routes experts
    (capacity is static per compiled shape, so each bucket compiles one
    executable — dropped over-capacity tokens ride the residual)."""
    if "moe" not in layer:
        return nn.mlp(layer["mlp"], x)
    from min_tfs_client_tpu.parallel.moe import (
        MoeParams,
        capacity_for,
        moe_ffn,
    )

    b, s, _ = x.shape
    capacity = capacity_for(b * s, config.moe_experts,
                            config.moe_capacity_factor)
    y, _aux = moe_ffn(MoeParams(**layer["moe"]), x, capacity=capacity)
    return y


def pooled(params: dict, config: BertConfig, input_ids, attention_mask,
           token_type_ids=None) -> jax.Array:
    """[CLS] vector through the tanh pooler -> (B, H) f32."""
    x = encode(params, config, input_ids, attention_mask, token_type_ids)
    return jnp.tanh(nn.dense(params["pooler"], x[:, 0])).astype(jnp.float32)


def logits_fn(params: dict, config: BertConfig, input_ids, attention_mask,
              token_type_ids=None) -> jax.Array:
    h = pooled(params, config, input_ids, attention_mask, token_type_ids)
    return nn.dense(params["head"], h.astype(nn.COMPUTE_DTYPE)).astype(
        jnp.float32)


def reference_logits(params: dict, config: BertConfig, input_ids,
                     attention_mask) -> jax.Array:
    """The float32 reference `logits_fn` is compared against: the same
    post-LN encoder written in plain jax.numpy — float32 throughout,
    masked softmax attention, no Pallas, no bf16 — under "highest" matmul
    precision so a TPU does not quietly run it in bf16 either. Dense-MLP
    configs only."""
    f32 = jnp.float32
    tree = jax.tree_util.tree_map(lambda p: jnp.asarray(p, f32), params)

    def dense(p, x):
        return x @ p["kernel"] + p["bias"]

    def norm(p, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return ((x - mean) * jax.lax.rsqrt(var + config.layer_norm_eps)
                * p["scale"] + p["bias"])

    with jax.default_matmul_precision("highest"):
        b, s = input_ids.shape
        h, d = config.num_heads, config.hidden_size // config.num_heads
        emb = tree["embeddings"]
        x = (emb["word"]["embedding"][input_ids]
             + emb["position"]["embedding"][jnp.arange(s)][None]
             + emb["token_type"]["embedding"][jnp.zeros_like(input_ids)])
        x = norm(emb["norm"], x)
        key_mask = jnp.asarray(attention_mask, bool)[:, None, None, :]
        for layer in tree["layers"]:
            att = layer["attention"]
            q, k, v = (dense(att[n], x).reshape(b, s, h, d)
                       for n in ("query", "key", "value"))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            probs = jax.nn.softmax(
                jnp.where(key_mask, scores, -jnp.inf), axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * d)
            x = norm(layer["attention_norm"], x + dense(att["out"], ctx))
            mlp = layer["mlp"]
            ffn = dense(mlp["wo"], jax.nn.gelu(dense(mlp["wi"], x)))
            x = norm(layer["mlp_norm"], x + ffn)
        pooled_out = jnp.tanh(dense(tree["pooler"], x[:, 0]))
        return dense(tree["head"], pooled_out)


# -- pipeline-parallel serving (SURVEY.md §2.11 PP row) ----------------------


def build_pipeline_state(params: dict, config: BertConfig, *, mesh):
    """Regroup a standard BERT param pytree for pipelined serving: the
    encoder layers split into `stage` contiguous groups stacked with a
    leading stage dim (sharded over the mesh's stage axis — each device
    holds exactly its stage's weights); embeddings/pooler/head replicate
    (they run outside the pipeline on every stage)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from min_tfs_client_tpu.parallel.pipeline import (
        STAGE_AXIS,
        stack_stage_params,
    )

    n_stages = int(mesh.shape[STAGE_AXIS])
    if config.num_layers % n_stages:
        raise ValueError(
            f"num_layers {config.num_layers} not divisible by "
            f"{n_stages} pipeline stages")
    group = config.num_layers // n_stages
    stacked = stack_stage_params(
        [{"layers": params["layers"][i * group:(i + 1) * group]}
         for i in range(n_stages)])
    stacked = jax.tree_util.tree_map(
        lambda p: jax.device_put(jnp.asarray(p),
                                 NamedSharding(mesh, P(STAGE_AXIS))),
        stacked)
    replicate = NamedSharding(mesh, P())

    def rep(tree):
        return jax.tree_util.tree_map(
            lambda p: jax.device_put(jnp.asarray(p), replicate), tree)

    return {"embeddings": rep(params["embeddings"]), "stages": stacked,
            "pooler": rep(params["pooler"]), "head": rep(params["head"])}


def pipelined_logits_fn(pp_params: dict, config: BertConfig, input_ids,
                        attention_mask, *, mesh, n_micro: int | None = None):
    """logits_fn over stage-sharded params: embeddings on every device,
    the layer stack as a GPipe microbatch pipeline (one ICI hop per
    stage), pooler/head on the drained outputs. Matches logits_fn
    numerics exactly — same layers, different residency."""
    import math

    from min_tfs_client_tpu.parallel.pipeline import (
        STAGE_AXIS,
        pipeline_apply,
    )

    b, s = input_ids.shape
    emb = pp_params["embeddings"]
    x = nn.embed(emb["word"], input_ids)
    x = x + nn.embed(emb["position"], jnp.arange(s)[None, :])
    x = x + nn.embed(emb["token_type"], jnp.zeros_like(input_ids))
    x = nn.layer_norm(emb["norm"], x, eps=config.layer_norm_eps)
    lengths = nn.lengths_from_mask(attention_mask)

    def stage_fn(stage_tree, carry):
        x, lengths = carry
        for layer in stage_tree["layers"]:
            attn, _ = nn.mha(layer["attention"], x,
                             num_heads=config.num_heads, lengths=lengths)
            x = nn.layer_norm(layer["attention_norm"], x + attn,
                              eps=config.layer_norm_eps)
            x = nn.layer_norm(layer["mlp_norm"],
                              x + _ffn(layer, config, x),
                              eps=config.layer_norm_eps)
        return (x, lengths)

    requested = n_micro or int(mesh.shape[STAGE_AXIS])
    x, _ = pipeline_apply(
        stage_fn, pp_params["stages"], (x, lengths), mesh=mesh,
        # Small batch buckets can't fill the requested microbatch count;
        # gcd keeps the schedule legal per compiled shape (batch is
        # static under jit).
        n_micro=math.gcd(b, requested))
    h = jnp.tanh(nn.dense(pp_params["pooler"], x[:, 0])).astype(jnp.float32)
    return nn.dense(pp_params["head"], h.astype(nn.COMPUTE_DTYPE)).astype(
        jnp.float32)


# -- servable construction ---------------------------------------------------


def build_long_context_signature(params: dict, config: BertConfig, *,
                                 seq_len: int, mesh=None,
                                 batch_buckets=(1, 2, 4)):
    """Served long-context encoder: (B, seq_len) -> (B, seq_len, H)
    embeddings with self-attention sharded on the mesh's "seq" axis
    (ring attention over ICI; SURVEY §5 long-context row — capability the
    reference lacks entirely). seq_len must be a multiple of the mesh's
    seq axis size and within the model's max_position; falls back to
    single-device attention when no multi-device mesh is available (same
    numerics)."""
    from min_tfs_client_tpu.parallel.mesh import SEQ_AXIS, make_mesh
    from min_tfs_client_tpu.servables.servable import Signature, TensorSpec

    if seq_len > config.max_position:
        # Past the position table, gathers clamp and embeddings silently
        # corrupt — same guard as SequenceBucketing.hard_max.
        raise ValueError(
            f"long_context seq_len {seq_len} exceeds the model's "
            f"max_position {config.max_position}")
    auto_mesh = mesh is None
    if auto_mesh:
        try:
            mesh = make_mesh({SEQ_AXIS: -1})
        except Exception:
            mesh = None
        if mesh is not None and dict(mesh.shape).get(SEQ_AXIS, 1) <= 1:
            mesh = None
    if mesh is not None:
        n_seq = dict(mesh.shape).get(SEQ_AXIS)
        if n_seq is None:
            raise ValueError(
                f"long-context mesh has no {SEQ_AXIS!r} axis "
                f"(axes: {sorted(dict(mesh.shape))})")
        if seq_len % n_seq:
            if auto_mesh:
                # Host device count is an environment property, not a
                # model property: an export must stay loadable anywhere.
                # Fall back to single-device attention (same numerics).
                mesh = None
            else:
                raise ValueError(
                    f"long-context seq_len {seq_len} must be a multiple "
                    f"of the mesh's {SEQ_AXIS} axis size {n_seq}")

    def encode_long(params, inputs):
        ids = jnp.asarray(inputs["input_ids"], jnp.int32)
        mask = jnp.asarray(inputs["attention_mask"], jnp.int32)
        x = encode(params, config, ids, mask, seq_mesh=mesh)
        return {"embeddings": x.astype(jnp.float32)}

    return Signature(
        fn=encode_long,
        params=params,
        inputs={"input_ids": TensorSpec(np.int32, (None, seq_len)),
                "attention_mask": TensorSpec(np.int32, (None, seq_len))},
        outputs={"embeddings": TensorSpec(
            np.float32, (None, seq_len, config.hidden_size))},
        batch_buckets=tuple(batch_buckets),
    )


def build_signatures(params: dict, config: BertConfig, *, seq_len: int,
                     class_labels: list[bytes] | None = None,
                     seq_buckets: tuple | list | None = None,
                     long_context_seq: int | None = None,
                     pipeline_mesh=None,
                     pipeline_n_micro: int | None = None) -> dict:
    """The model family's serving surface:

      serving_default / predict: ids+mask -> logits, probabilities
      classify: Example path -> scores (+classes when labels given)
      regress:  Example path -> outputs (label-0 logit as the value)

    With `seq_buckets`, the predict signature takes any sequence length
    up to max(seq_buckets): requests round up to the nearest bucket, pad
    ids with 0 and the mask with 0, and the attention-length masking makes
    the padded positions invisible — classification outputs are exact (one
    executable per batch x seq bucket; warmup primes the matrix).

    With `pipeline_mesh` (a Mesh carrying a "stage" axis), every
    signature serves pipeline-parallel: the layer stack is regrouped into
    stage-resident weights and executed as a GPipe microbatch schedule
    (pipelined_logits_fn) — same numerics, stage-sharded residency.
    """
    from min_tfs_client_tpu.servables.servable import (
        CLASSIFY_METHOD_NAME,
        CLASSIFY_OUTPUT_CLASSES,
        CLASSIFY_OUTPUT_SCORES,
        REGRESS_METHOD_NAME,
        REGRESS_OUTPUTS,
        SequenceBucketing,
        Signature,
        TensorSpec,
    )

    if pipeline_mesh is not None:
        if config.moe_experts:
            raise ValueError(
                "pipeline and moe_experts cannot combine: per-microbatch "
                "expert capacity diverges from sequential routing")
        params = build_pipeline_state(params, config, mesh=pipeline_mesh)

        def compute_logits(params, ids, mask):
            return pipelined_logits_fn(params, config, ids, mask,
                                       mesh=pipeline_mesh,
                                       n_micro=pipeline_n_micro)
    else:
        def compute_logits(params, ids, mask):
            return logits_fn(params, config, ids, mask)

    def predict(params, inputs):
        logits = compute_logits(params,
                                jnp.asarray(inputs["input_ids"]),
                                jnp.asarray(inputs["attention_mask"]))
        return {"logits": logits,
                "probabilities": jax.nn.softmax(logits, axis=-1)}

    if seq_buckets:
        predict_seq_dim = None
        bucketing = SequenceBucketing(
            buckets=tuple(seq_buckets),  # normalized by __post_init__
            pad_values={"input_ids": 0, "attention_mask": 0},
            # Position embeddings bound every bucket: a longer bucket
            # would clamp position gathers and silently corrupt outputs.
            hard_max=config.max_position,
            content_aliases=("input_ids",))
        # Example-path signatures keep a fixed decode width.
        seq_len = seq_len or max(bucketing.buckets)
    else:
        predict_seq_dim = seq_len
        bucketing = None

    predict_sig = Signature(
        fn=predict,
        params=params,
        inputs={"input_ids": TensorSpec(np.int32, (None, predict_seq_dim)),
                "attention_mask": TensorSpec(np.int32,
                                             (None, predict_seq_dim))},
        outputs={"logits": TensorSpec(np.float32, (None, config.num_labels)),
                 "probabilities": TensorSpec(np.float32,
                                             (None, config.num_labels))},
        sequence_bucketing=bucketing,
    )

    feature_specs = {
        "input_ids": FeatureSpec(np.int64, (seq_len,)),
        "attention_mask": FeatureSpec(np.int64, (seq_len,),
                                      default=np.ones(seq_len, np.int64)),
    }

    def classify(params, inputs):
        logits = compute_logits(
            params,
            jnp.asarray(inputs["input_ids"], jnp.int32),
            jnp.asarray(inputs["attention_mask"], jnp.int32))
        return {CLASSIFY_OUTPUT_SCORES: jax.nn.softmax(logits, axis=-1)}

    classify_sig = Signature(
        fn=classify,
        params=params,
        inputs={"input_ids": TensorSpec(np.int64, (None, seq_len)),
                "attention_mask": TensorSpec(np.int64, (None, seq_len))},
        outputs={CLASSIFY_OUTPUT_SCORES: TensorSpec(
            np.float32, (None, config.num_labels))},
        method_name=CLASSIFY_METHOD_NAME,
        feature_specs=feature_specs,
        class_labels=class_labels,
    )

    def regress(params, inputs):
        logits = compute_logits(
            params,
            jnp.asarray(inputs["input_ids"], jnp.int32),
            jnp.asarray(inputs["attention_mask"], jnp.int32))
        return {REGRESS_OUTPUTS: logits[:, 0]}

    regress_sig = Signature(
        fn=regress,
        params=params,
        inputs={"input_ids": TensorSpec(np.int64, (None, seq_len)),
                "attention_mask": TensorSpec(np.int64, (None, seq_len))},
        outputs={REGRESS_OUTPUTS: TensorSpec(np.float32, (None,))},
        method_name=REGRESS_METHOD_NAME,
        feature_specs=feature_specs,
    )

    signatures = {"serving_default": predict_sig, "predict": predict_sig,
                  "classify": classify_sig, "regress": regress_sig}
    if long_context_seq:
        if pipeline_mesh is not None:
            raise ValueError(
                "long_context_seq and pipeline_mesh cannot combine: the "
                "ring-attention path needs the standard param layout")
        signatures["encode_long"] = build_long_context_signature(
            params, config, seq_len=long_context_seq)
    return signatures
