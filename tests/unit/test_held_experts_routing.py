"""parallel/moe.py's third routing rule, group-limited sigmoid top-k
(`sigmoid_grouped`), against its definition written out in numpy; and
`held_experts_ffn` under it at 128 held experts of 512, in both forms,
against a sum gathered token by token. (The other two rules' tests live
with their models: tests/unit/test_mimo.py, test_granite_hybrid.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.parallel import moe

D, F = 32, 16


def definition(x, router, bias, top_k, n_group, topk_group):
    """The rule, a token at a time, float64: -> (chosen experts as a set
    a token, {expert: weight} a token)."""
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                   @ np.asarray(router, np.float64))))
    biased = scores + np.asarray(bias, np.float64)
    size = scores.shape[1] // n_group
    out = []
    for s, b in zip(scores, biased):
        groups = b.reshape(n_group, size)
        worth = np.sort(groups, -1)[:, -2:].sum(-1)
        stays = np.argsort(-worth)[:topk_group]
        allowed = np.full(b.shape, -np.inf)
        for group in stays:
            allowed[group * size:(group + 1) * size] = groups[group]
        chosen = np.argsort(-allowed)[:top_k]
        out.append({int(e): s[e] / s[chosen].sum() for e in chosen})
    return out


def case(tokens=24, experts=64, seed=0, gain=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(ks[0], (tokens, D)),
            "router": jax.random.normal(ks[1], (D, experts)) * gain
            * D ** -0.5,
            "bias": jax.random.normal(ks[2], (experts,)) * 0.05,
            "w_in": jax.random.normal(ks[3], (experts, D, 2 * F)) * D ** -0.5,
            "w_out": jax.random.normal(ks[4], (experts, F, D)) * F ** -0.5}


def test_sigmoid_grouped_top_k_is_its_definition():
    c = case()
    experts, weights = moe.sigmoid_grouped_top_k(
        c["x"], c["router"], c["bias"], 8, 8, 4)
    want = definition(c["x"], c["router"], c["bias"], 8, 8, 4)
    for row, (chosen, w) in enumerate(zip(np.asarray(experts),
                                          np.asarray(weights))):
        assert set(chosen.tolist()) == set(want[row])
        np.testing.assert_allclose(
            w, [want[row][int(e)] for e in chosen], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # every token's choices lie in at most 4 of the 8 groups
    assert max(len(set((row // 8).tolist()))
               for row in np.asarray(experts)) <= 4


def test_a_token_whose_best_experts_lie_in_five_groups_loses_the_fifths():
    """8 groups of 8; the token's 8 largest scores are planted two in
    each of groups 0-2 and one each in groups 3 and 4: groups 0-3 stay
    (a group's worth is its two largest), group 4's expert is left out
    though it outscores the runners-up that take its place."""
    router = np.full((1, 64), -4.0, np.float32)
    planted = {0: 3.0, 1: 2.9, 8: 2.8, 9: 2.7, 16: 2.6, 17: 2.5,
               24: 2.45, 25: -1.0,      # group 3: one large, one middling
               32: 2.4}                 # group 4: one large, worth less
    for expert, logit in planted.items():
        router[0, expert] = logit
    x = jnp.ones((1, 1))
    zero = jnp.zeros((64,))
    plain, _ = moe.sigmoid_top_k(x, jnp.asarray(router), zero, 8)
    assert set(np.asarray(plain)[0].tolist()) == {0, 1, 8, 9, 16, 17, 24, 32}
    grouped, weights = moe.sigmoid_grouped_top_k(
        x, jnp.asarray(router), zero, 8, 8, 4)
    assert set(np.asarray(grouped)[0].tolist()) \
        == {0, 1, 8, 9, 16, 17, 24, 25}
    np.testing.assert_allclose(np.asarray(weights).sum(), 1.0, rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weight():
    c = case(tokens=16, seed=1)
    none = jnp.zeros_like(c["bias"])
    before, _ = moe.sigmoid_grouped_top_k(c["x"], c["router"], none, 8, 8, 4)
    push = none.at[5].set(10.0)               # expert 5 wins every choice
    experts, weights = moe.sigmoid_grouped_top_k(
        c["x"], c["router"], push, 8, 8, 4)
    experts, weights = np.asarray(experts), np.asarray(weights)
    assert (experts == 5).any(-1).all()
    assert not (np.asarray(before) == 5).any(-1).all()
    # its weight is its SCORE's share, not its score + bias's
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        c["x"], c["router"], precision=jax.lax.Precision.HIGHEST)))
    for row in range(16):
        chosen = experts[row]
        np.testing.assert_allclose(
            weights[row], scores[row, chosen] / scores[row, chosen].sum(),
            rtol=1e-5)


def gathered(c, top_k, n_group, topk_group, held: set, scale):
    """Token by token, float64: the sum over its chosen HELD experts."""
    x = np.asarray(c["x"], np.float64)
    out = np.zeros_like(x)
    for t, chosen in enumerate(definition(
            c["x"], c["router"], c["bias"], top_k, n_group, topk_group)):
        for e, w in chosen.items():
            if e in held:
                hidden = x[t] @ np.asarray(c["w_in"][e], np.float64)
                act = hidden[:F] / (1 + np.exp(-hidden[:F])) * hidden[F:]
                out[t] += scale * w * (act @ np.asarray(c["w_out"][e],
                                                        np.float64))
    return out


@pytest.mark.parametrize("form", ["by_hit_expert", "by_sorted_pair"])
@pytest.mark.parametrize("offset", [0, 384])
def test_128_held_of_512_in_both_forms_agree_with_a_gathered_sum(form,
                                                                 offset):
    """The regime no other model has: far more held experts than a step
    has rows, most of them hit by no row. 32 rows, top-8 of 512 in 8
    groups, experts `offset`..`offset` + 127 held, the scaling factor
    2.5; a padding row (`valid`) is routed nowhere."""
    c = case(tokens=32, experts=512, seed=2)
    params = moe.HeldExperts(c["router"], c["bias"],
                             c["w_in"][offset:offset + 128],
                             c["w_out"][offset:offset + 128])
    valid = jnp.arange(32) != 7
    extra = {"valid": valid} if form == "by_hit_expert" else {
        "rows": jnp.asarray(32), "row_block": 16, "valid": valid}
    with jax.default_matmul_precision("highest"):
        y, routed = moe.held_experts_ffn(
            params, c["x"], top_k=8, experts_held=128, expert_offset=offset,
            routing="sigmoid_grouped", n_group=8, topk_group=4, scale=2.5,
            **extra)
    want = gathered(c, 8, 8, 4, set(range(offset, offset + 128)), 2.5)
    want[7] = 0.0
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert not np.any(np.asarray(y)[7]) and int(routed.held[7]) == 0
    load = np.asarray(routed.load)
    assert load.sum() == int(routed.held.sum()) and load.shape == (128,)
    hit = int((load > 0).sum())
    assert 0 < hit < 128                       # most held experts idle
    assert int(routed.hit) == (hit if form == "by_hit_expert" else 0)


def test_both_forms_are_one_arithmetic_at_128_held():
    c = case(tokens=32, experts=512, seed=3)
    params = moe.HeldExperts(c["router"], c["bias"], c["w_in"][:128],
                             c["w_out"][:128])
    common = dict(top_k=8, experts_held=128, expert_offset=0,
                  routing="sigmoid_grouped", n_group=8, topk_group=4,
                  scale=2.5)
    walked, a = moe.held_experts_ffn(params, c["x"], **common)
    sorted_, b = moe.held_experts_ffn(params, c["x"], rows=jnp.asarray(32),
                                      **common)
    np.testing.assert_allclose(walked, sorted_, atol=1e-5)
    assert np.array_equal(a.load, b.load) and np.array_equal(a.held, b.held)


def test_the_other_rules_lower_as_they_did_without_the_group_arguments():
    """MiMo's and Granite's calls name neither `n_group` nor
    `topk_group`: they lower to the programs they lowered to before."""
    c = case(tokens=8, experts=16, seed=4)
    params = moe.HeldExperts(c["router"], c["bias"], c["w_in"], c["w_out"])
    common = dict(top_k=4, experts_held=16, expert_offset=0)
    for routing in ("sigmoid", "softmax_top_k"):
        plain = jax.jit(lambda p, x, routing=routing: moe.held_experts_ffn(
            p, x, routing=routing, **common))
        named = jax.jit(lambda p, x, routing=routing: moe.held_experts_ffn(
            p, x, routing=routing, n_group=None, topk_group=None, **common))
        assert plain.lower(params, c["x"]).as_text() \
            == named.lower(params, c["x"]).as_text()
    assert "sigmoid_grouped" in moe.ROUTINGS


# -- the walk's kernel and its gate (PR 52) ------------------------------------

# (held, D, F) of the three decoders' expert layers as the cells run them
WIDTHS = {"ling": (128, 2560, 768), "granite": (18, 4096, 768),
          "mimo": (16, 4096, 2048)}
INSIDE = {"ling": True, "granite": True, "mimo": False}


def shapes_of(held, d, f, t=32, dtype=jnp.bfloat16, experts=None):
    """A layer as shapes alone: nothing here has a value to read."""
    s = jax.ShapeDtypeStruct
    return (moe.HeldExperts(s((d, experts or held), jnp.float32),
                            s((experts or held,), jnp.float32),
                            s((held, d, 2 * f), dtype),
                            s((held, f, d), dtype)),
            s((t, d), jnp.float32))


def test_the_gate_of_the_walks_kernel_reads_shapes_alone():
    """`_walk_kernel_applies` answers from shapes and dtypes (here there
    is nothing else to read): lane multiples, one dtype, and two experts'
    matrices beside the rows inside its VMEM budget."""
    def answer(held, d, f, t=32, dtype=jnp.bfloat16, rows_dtype=None):
        params, x = shapes_of(held, d, f, t, dtype)
        return moe._walk_kernel_applies(
            params, jax.ShapeDtypeStruct(x.shape, rows_dtype or dtype))

    for name, widths in WIDTHS.items():
        for t in (1, 4, 16, 32):                    # a decode step's rows
            assert answer(*widths, t=t) is INSIDE[name], (name, t)
    # the rows stand in VMEM beside the two experts in flight
    assert answer(*WIDTHS["ling"], t=moe.DECODE_ROWS)
    assert not answer(*WIDTHS["granite"], t=moe.DECODE_ROWS)
    assert not answer(128, 2560 + 64, 768)          # rows of split lane tiles
    assert not answer(128, 2560, 768 + 64)          # a gated half of them
    assert not answer(128, 2560, 768, rows_dtype=jnp.float32)
    assert answer(128, 2560, 768, dtype=jnp.float32)
    assert not answer(18, 4096, 768, dtype=jnp.float32)   # twice the bytes
    # how many are held says nothing about what is in flight
    assert answer(8, 2560, 768) and answer(512, 2560, 768)
    # arrays of the same shapes, whatever they hold
    c = case(tokens=8, experts=16, seed=4)
    for scale in (0.0, 1.0, 1e30):
        params = moe.HeldExperts(c["router"], c["bias"], c["w_in"] * scale,
                                 c["w_out"] * scale)
        assert not moe._walk_kernel_applies(params, c["x"] * scale)


@pytest.mark.parametrize("name", list(WIDTHS))
def test_a_decoder_the_gate_leaves_out_lowers_as_it_did(name, monkeypatch):
    """On a TPU the call forms of the decoders whose shapes fall outside
    the gate lower, for the TPU, to the very text of the loop (what the
    parent's `_by_hit_expert` is, and what runs off the TPU); the one
    inside lowers to `_expert_walk_kernel` and no loop of products."""
    held, d, f = WIDTHS[name]
    forms = {"mimo": dict(top_k=8, experts=256),
             "granite": dict(top_k=10, experts=72, routing="softmax_top_k",
                             scale=0.22),
             "ling": dict(top_k=8, experts=512, routing="sigmoid_grouped",
                          n_group=8, topk_group=4, scale=2.5)}[name]
    params, x = shapes_of(held, d, f, experts=forms.pop("experts"))
    valid = jax.ShapeDtypeStruct((32,), jnp.bool_)

    def lowered():
        return jax.jit(lambda p, x, valid, onto: moe.held_experts_ffn(
            p, x, experts_held=held, expert_offset=0, valid=valid,
            onto=onto, **forms)).trace(params, x, valid, x).lower(
                lowering_platforms=("tpu",)).as_text()

    off_the_tpu = lowered()
    assert "tpu_custom_call" not in off_the_tpu
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    on_the_tpu = lowered()
    if INSIDE[name]:
        assert "_expert_walk_kernel" in on_the_tpu
        assert on_the_tpu.count("stablehlo.dot_general") \
            < off_the_tpu.count("stablehlo.dot_general")
    else:
        assert on_the_tpu == off_the_tpu
