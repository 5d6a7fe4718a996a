"""models/latent.py, latent attention's one home: the absorbed decode
form against the decompressed one at each caller's sizes (Ling's plain
scale, Xing's low-rank query and YaRN's scale), YaRN's frequencies
against DeepSeek-V3's formula written out again, the rotary, and Ling's
program lowering to the text it lowered to before the forms moved."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import latent, ling_hybrid, xing

# (heads, nope, rope, v, rank, scale): Ling's MLA layer at a small size
# (no stretch: qk_head_dim ** -0.5), and Xing's, whose scale carries
# YaRN's temperature squared
CALLERS = {
    "ling": (4, 16, 8, 16, 32, 24 ** -0.5),
    "xing": (4, 16, 8, 16, 32,
             24 ** -0.5 * latent.yarn_mscale(64.0, 1.0) ** 2),
    "other_sizes": (2, 8, 16, 24, 16, 0.3),
}


@pytest.mark.parametrize("caller", list(CALLERS))
def test_the_absorbed_decode_is_the_decompressed_form(caller):
    """Latent attention's two forms on the same rows: a query at each
    example's last position over the latent rows, in the latent space
    (the step's) and over decompressed K and V (the prefill's)."""
    h, nope, rope, dv, rank, scale = CALLERS[caller]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s = 3, 40
    kvb = jax.random.normal(keys[2], (rank, h * (nope + dv))) * rank ** -0.5
    q = jax.random.normal(keys[0], (b, s, h, nope + rope))
    rows = jax.random.normal(keys[1], (b, s, rank + rope))
    lengths = jnp.asarray([40, 17, 1], jnp.int32)
    sizes = dict(nope=nope, v_head_dim=dv, scale=scale)
    with jax.default_matmul_precision("highest"):
        whole = latent.decompressed_attention(kvb, q, rows, lengths, **sizes)
        last = lengths - 1
        seen = jnp.arange(s)[None, :] <= last[:, None]
        one = latent.absorbed_attention(
            kvb, q[jnp.arange(b), last], rows[:, None], seen, **sizes)
        twice = latent.absorbed_attention(
            kvb, q[jnp.arange(b), last], rows[:, None], seen,
            **dict(sizes, scale=2 * scale))
    assert whole.shape == (b, s, h * dv)
    np.testing.assert_allclose(one, whole[jnp.arange(b), last], atol=1e-5)
    assert float(jnp.std(one)) > 0.05
    # the scale is the caller's and it matters (but where one key is seen)
    assert float(jnp.max(jnp.abs(twice - one)[:2])) > 1e-3
    np.testing.assert_allclose(twice[2], one[2], atol=1e-6)


def test_xing_s_query_goes_through_its_low_rank_and_its_norm():
    """models/xing.py's inputs of the two forms: q = W_qb RMSNorm(W_qa x)
    rotated on its rope lanes, the cached row RMSNorm(c) | rope(k_r);
    prefill form and step form agree on them."""
    pc = xing.XingConfig(
        vocab_size=32, hidden_size=32, num_layers=1, ffn_types=("dense",),
        num_heads=2, q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=16,
        rope_original_positions=16, dtype="float32")
    p = xing.init_params(jax.random.PRNGKey(0), pc)["layers"][0]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    positions = jnp.arange(24)
    with jax.default_matmul_precision("highest"):
        q, rows = xing._mla_inputs(pc, p, x, positions)
        low = x @ p["qa"]["kernel"]
        low = low * jax.lax.rsqrt(jnp.mean(low * low, -1, keepdims=True)
                                  + pc.eps)
        want = (low @ p["qb"]["kernel"]).reshape(24, 2, 16)
        np.testing.assert_allclose(q[..., :8], want[..., :8], atol=1e-5)
        # a rotation keeps a pair's length, and position 0 is not rotated
        np.testing.assert_allclose(
            jnp.sum(q[..., 8:] ** 2, -1), jnp.sum(want[..., 8:] ** 2, -1),
            rtol=1e-4)
        np.testing.assert_allclose(q[0], want[0], atol=1e-5)
        assert float(jnp.max(jnp.abs(q[5, :, 8:] - want[5, :, 8:]))) > 0.01
        sizes = dict(nope=8, v_head_dim=8, scale=pc.attention_scale)
        whole = latent.decompressed_attention(
            p["kvb"]["kernel"], q[None], rows[None], jnp.asarray([24]),
            **sizes)
        one = latent.absorbed_attention(
            p["kvb"]["kernel"], q[None, 23], rows[None, None],
            jnp.ones((1, 24), bool), **sizes)
    np.testing.assert_allclose(one[0], whole[0, 23], atol=1e-5)
    assert rows.shape == (24, 24)
    assert pc.attention_scale == pytest.approx(
        16 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)


def deepseek_v3_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies as DeepSeek-V3's modelling code has
    them (find_correction_dim / range, linear_ramp_mask), written out."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def test_yarn_s_frequencies_are_deepseek_v3_s():
    got = latent.yarn_frequencies(
        1e4, factor=64.0, original=4096, beta_fast=32.0, beta_slow=1.0)(32)
    want = deepseek_v3_inv_freq(64, 1e4, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(latent.plain_frequencies(1e4)(32))
    # the quick pairs keep their frequency, the slow ones have it divided
    # by the factor, between the correction dimensions (10 and 23) a ramp
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    assert np.all(got[11:23] < plain[11:23])
    assert np.all(got[11:23] > plain[11:23] / 64)
    assert latent.yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert latent.yarn_mscale(1.0, 1.0) == 1.0


def test_the_rotary_turns_interleaved_pairs_by_position_times_frequency():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 8)),
                    jnp.float32)
    positions = jnp.asarray([0, 1, 2, 7, 100])
    law = latent.plain_frequencies(100.0)
    got = np.asarray(latent.rope(x, positions, law))
    inv = np.asarray(law(4))
    for t, position in enumerate(np.asarray(positions)):
        for pair in range(4):
            angle = position * inv[pair]
            a, b = np.asarray(x[t, :, 2 * pair]), np.asarray(x[t, :, 2 * pair + 1])
            np.testing.assert_allclose(
                got[t, :, 2 * pair], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-5)
            np.testing.assert_allclose(
                got[t, :, 2 * pair + 1], b * np.cos(angle) + a * np.sin(angle),
                atol=1e-5)


# -- Ling lowers as it did ------------------------------------------------------

# sha256 of the StableHLO text (`.lower(...).as_text()`) of Ling's prefill
# and step at tests/unit/test_ling_hybrid.py's small size, taken on the
# tree BEFORE `decompressed_attention`, `absorbed_attention`, the latent
# row and the rotary moved out of models/ling_hybrid.py (commit 7c4bf55,
# this JAX). A change of Ling's own arithmetic moves them, and then they
# are taken again; the move itself left them as they were.
LING_BEFORE_THE_MOVE = {
    "prefill": (359504, "b8fe75b051539528edd5e13571144e4af0dea194b001d9a5"
                        "ac02cf10e4ae7b12"),
    "step": (120790, "fec8f7f99c422c627d53a72cc34b7b62c43c3a23ac8db18be0"
                     "dbeba12fcc2025"),
}


@pytest.fixture(scope="module")
def ling_lowered():
    from tests.unit import test_ling_hybrid as small

    from perfbench import children

    pc = ling_hybrid.LingHybridConfig(
        **children.program_config_kwargs(small.published()))
    params = jax.eval_shape(
        lambda: ling_hybrid.init_params(jax.random.PRNGKey(7), pc))
    ids = jax.ShapeDtypeStruct((4, small.SEQ), jnp.int32)
    prefill = jax.jit(lambda p, i: ling_hybrid.prefill(
        p, pc, i, max_decode_len=small.STEPS, row_block=32))
    state = jax.eval_shape(prefill, params, ids)
    return {"prefill": prefill.lower(params, ids).as_text(),
            "step": jax.jit(lambda p, s: ling_hybrid.step(p, pc, s)).lower(
                params, state).as_text()}


@pytest.mark.parametrize("program", list(LING_BEFORE_THE_MOVE))
def test_ling_lowers_to_the_text_it_lowered_to_before_the_move(
        ling_lowered, program):
    text = ling_lowered[program]
    length, digest = LING_BEFORE_THE_MOVE[program]
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == (length, digest)


def test_ling_keeps_no_copy_of_the_latent_forms():
    for name in ("decompressed_attention", "absorbed_attention", "_rope"):
        assert not hasattr(ling_hybrid, name)
        assert not hasattr(xing, name)
