"""Model families: forward shapes/sanity, KV-cache decode equivalence,
export -> load -> serve round trips through the real lifecycle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from min_tfs_client_tpu.models import bert, export, resnet, t5, use
from min_tfs_client_tpu.models import layers as nn


def test_bert_tiny_forward_shapes():
    config = bert.BertConfig.tiny(num_labels=3)
    params = bert.init_params(jax.random.PRNGKey(0), config)
    ids = np.array([[5, 6, 7, 0], [8, 9, 0, 0]], np.int32)
    mask = np.array([[1, 1, 1, 0], [1, 1, 0, 0]], np.int32)
    logits = bert.logits_fn(params, config, ids, mask)
    assert logits.shape == (2, 3)
    assert np.isfinite(np.asarray(logits)).all()


def test_bert_padding_invariance():
    """Masked positions must not change the result — the flash-kernel
    lengths path and the serving pad-to-bucket rule depend on it."""
    config = bert.BertConfig.tiny()
    params = bert.init_params(jax.random.PRNGKey(1), config)
    ids = np.array([[5, 6, 7, 0, 0, 0, 0, 0]], np.int32)
    mask = np.array([[1, 1, 1, 0, 0, 0, 0, 0]], np.int32)
    a = bert.logits_fn(params, config, ids, mask)
    ids2 = ids.copy()
    ids2[0, 3:] = 99  # garbage in masked slots
    b = bert.logits_fn(params, config, ids2, mask)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)


def test_bert_serving_forward_agrees_with_the_float32_reference():
    """logits_fn (bf16, the served path) against reference_logits (plain
    jax.numpy float32): logits-level agreement at bf16 resolution, with
    ragged masks. chip_smoke.py makes the same comparison at BERT-base
    width on the chip."""
    config = bert.BertConfig.tiny(num_labels=3)
    params = bert.init_params(jax.random.PRNGKey(2), config)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config.vocab_size, (4, 16)).astype(np.int32)
    lengths = np.array([16, 9, 3, 1])
    mask = (np.arange(16)[None] < lengths[:, None]).astype(np.int32)
    want = np.asarray(bert.reference_logits(params, config, ids, mask))
    assert want.dtype == np.float32
    got = np.asarray(bert.logits_fn(params, config, ids, mask))
    np.testing.assert_allclose(got, want, atol=0.03)


def test_t5_greedy_decode_shapes_and_determinism():
    config = t5.T5Config.tiny()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    ids = np.array([[4, 5, 6, 0], [7, 8, 0, 0]], np.int32)
    lengths = np.array([3, 2], np.int32)
    out1, len1 = t5.greedy_decode(params, config, ids, lengths,
                                  max_decode_len=8)
    out2, _ = t5.greedy_decode(params, config, ids, lengths,
                               max_decode_len=8)
    assert out1.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert (np.asarray(len1) <= 8).all()


def test_t5_cached_decode_matches_uncached_teacher_forcing():
    """The KV-cache step must produce the same logits as full re-encoding
    of the prefix (teacher forcing) — the cache is an optimisation, not a
    different model."""
    config = t5.T5Config.tiny()
    params = t5.init_params(jax.random.PRNGKey(2), config)
    b, s_in, steps = 1, 4, 4
    ids = np.array([[4, 5, 6, 2]], np.int32)
    lengths = np.array([4], np.int32)
    encoded = t5.encode(params, config, ids, lengths)

    # Cached pass: step tokens one at a time.
    caches = [{"self": nn.init_cache(b, config.num_heads, steps, config.d_kv)}
              for _ in range(config.num_decoder_layers)]
    tokens = [0, 9, 10, 11]
    cached_logits = []
    for i, tok in enumerate(tokens):
        logits, caches = t5._decoder_step(
            params, config, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray(i), caches, encoded, jnp.asarray(lengths))
        cached_logits.append(np.asarray(logits))

    # Uncached oracle: re-run the full prefix each step via a fresh cache
    # prefill of length i+1... simplest correct oracle: recompute with a
    # bigger cache and compare the last-step logits.
    for i in range(1, len(tokens)):
        caches2 = [{"self": nn.init_cache(b, config.num_heads, i + 1,
                                          config.d_kv)}
                   for _ in range(config.num_decoder_layers)]
        last = None
        for j, tok in enumerate(tokens[:i + 1]):
            last, caches2 = t5._decoder_step(
                params, config, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray(j), caches2, encoded, jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(last), cached_logits[i],
                                   atol=1e-4, rtol=1e-4)


def test_resnet_tiny_forward():
    config = resnet.ResNetConfig.tiny()
    params = resnet.init_params(jax.random.PRNGKey(0), config)
    images = np.random.default_rng(0).standard_normal(
        (2, config.image_size, config.image_size, 3)).astype(np.float32)
    logits = resnet.forward(params, config, images)
    assert logits.shape == (2, config.num_classes)
    assert np.isfinite(np.asarray(logits)).all()


def test_resnet_fold_batchnorm():
    conv = {"kernel": jnp.ones((1, 1, 1, 2), jnp.float32),
            "scale": jnp.ones((2,)), "bias": jnp.zeros((2,))}
    folded = resnet.fold_batchnorm(
        conv, gamma=np.array([2.0, 1.0]), beta=np.array([1.0, 0.0]),
        mean=np.array([0.5, 0.0]), var=np.array([0.25, 1.0]), eps=0.0)
    # y = gamma*(x-mean)/sqrt(var) + beta for x=1: [2*(1-.5)/.5+1, 1*1/1+0]
    x = jnp.ones((1, 1, 1, 1), jnp.float32)
    y = resnet._conv(folded, x, relu=False)
    np.testing.assert_allclose(np.asarray(y).reshape(-1), [3.0, 1.0],
                               atol=1e-2)


def test_use_tokenizer_stable_and_bounded():
    config = use.USEConfig.tiny()
    toks = use.tokenize(b"Hello, World! hello", config)
    assert toks == use.tokenize("hello world HELLO", config)
    assert all(1 <= t < config.vocab_size for t in toks)


def test_use_encode_string_batch():
    config = use.USEConfig.tiny()
    params = use.init_params(jax.random.PRNGKey(0), config)
    sigs = use.build_signatures(params, config)
    out = sigs["serving_default"].run({
        "text": np.array([b"the quick brown fox", b"hi"], object)})
    emb = out["embeddings"]
    assert emb.shape == (2, config.embed_dim)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=-1), [1.0, 1.0],
                               atol=1e-3)
    # Ragged batching: same text alone or in a batch gives the same vector.
    solo = sigs["serving_default"].run({"text": np.array([b"hi"], object)})
    np.testing.assert_allclose(solo["embeddings"][0], emb[1], atol=2e-2)


def test_param_pytree_roundtrip(tmp_path):
    config = bert.BertConfig.tiny()
    params = bert.init_params(jax.random.PRNGKey(3), config)
    export.save_params(tmp_path / "p.npz", params)
    loaded = export.load_params(tmp_path / "p.npz")
    flat_a = export.flatten_params(params)
    flat_b = export.flatten_params(loaded)
    assert set(flat_a) == set(flat_b)
    for key in flat_a:
        np.testing.assert_array_equal(flat_a[key], flat_b[key])
    assert isinstance(loaded["layers"], list)  # list structure restored


@pytest.mark.parametrize("family", ["bert", "t5", "resnet", "use"])
def test_export_load_serve_roundtrip(tmp_path, family):
    """Every family exports to a version dir the jax platform can load, and
    the loaded servable serves a request."""
    from min_tfs_client_tpu.servables.platforms import make_loader

    rng = jax.random.PRNGKey(0)
    if family == "bert":
        config = bert.BertConfig.tiny(num_labels=2)
        params = bert.init_params(rng, config)
        export.export_servable(
            tmp_path / family, 1, "bert",
            {"vocab_size": config.vocab_size, "hidden_size": config.hidden_size,
             "num_layers": config.num_layers, "num_heads": config.num_heads,
             "intermediate_size": config.intermediate_size,
             "max_position": config.max_position, "num_labels": 2},
            params, {"seq_len": 8, "class_labels": [b"neg", b"pos"]})
        request = {"input_ids": np.zeros((1, 8), np.int32),
                   "attention_mask": np.ones((1, 8), np.int32)}
        out_key = "probabilities"
    elif family == "t5":
        config = t5.T5Config.tiny()
        params = t5.init_params(rng, config)
        export.export_servable(
            tmp_path / family, 1, "t5",
            {"vocab_size": config.vocab_size, "d_model": config.d_model,
             "d_kv": config.d_kv, "num_heads": config.num_heads,
             "d_ff": config.d_ff,
             "num_encoder_layers": config.num_encoder_layers,
             "num_decoder_layers": config.num_decoder_layers,
             "rel_pos_buckets": config.rel_pos_buckets,
             "rel_pos_max_distance": config.rel_pos_max_distance},
            params, {"seq_len": 8, "max_decode_len": 4})
        request = {"input_ids": np.ones((1, 8), np.int32)}
        out_key = "output_ids"
    elif family == "resnet":
        config = resnet.ResNetConfig.tiny()
        params = resnet.init_params(rng, config)
        export.export_servable(
            tmp_path / family, 1, "resnet",
            {"stage_sizes": list(config.stage_sizes), "width": config.width,
             "num_classes": config.num_classes,
             "image_size": config.image_size},
            params, {})
        request = {"images": np.zeros(
            (1, config.image_size, config.image_size, 3), np.float32)}
        out_key = "probabilities"
    else:
        config = use.USEConfig.tiny()
        params = use.init_params(rng, config)
        export.export_servable(
            tmp_path / family, 1, "use",
            {"vocab_size": config.vocab_size,
             "hidden_size": config.hidden_size,
             "num_layers": config.num_layers, "num_heads": config.num_heads,
             "intermediate_size": config.intermediate_size,
             "embed_dim": config.embed_dim, "max_tokens": config.max_tokens,
             "seq_buckets": list(config.seq_buckets)},
            params, {})
        request = {"text": np.array([b"hello world"], object)}
        out_key = "embeddings"

    loader = make_loader("jax", family, 1, str(tmp_path / family / "1"),
                         {"enable_model_warmup": False})
    loader.load()
    servable = loader.servable()
    result = servable.signature("").run(request)
    assert out_key in result
    loader.unload()
