"""parallel/moe.py's walk over the hit experts as ONE Pallas kernel
(`_expert_walk_kernel`, interpreted here) against the loop it stands for
(`_by_hit_expert`'s `fori_loop`, what runs off the TPU and where the
gate says no): the same inputs through both, under each routing rule,
with none, one, some and all of the held experts hit, with rows that
pad the batch, with and without `onto`, at 1, 4, 16 and 32 rows."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.parallel import moe

D, F = 128, 128            # whole lane tiles: what the gate asks
EXPERTS, HELD, OFFSET, TOP_K = 16, 4, 4, 4   # the held ones are group 1 of 4
RULES = {"sigmoid": {},
         "softmax_top_k": {},
         "sigmoid_grouped": {"n_group": 4, "topk_group": 2}}
# What the first input channel (1.0 on every row) adds to each expert's
# logit: which held experts the rows can reach at all.
HELD_ONES = range(OFFSET, OFFSET + HELD)
STEER = {
    "none": [-16.0 if e in HELD_ONES else 0.0 for e in range(EXPERTS)],
    "one": [8.0 if e == OFFSET + 1 else -16.0 if e in HELD_ONES else -8.0
            for e in range(EXPERTS)],
    "some": [0.0] * EXPERTS,
    "all": [8.0 if e in HELD_ONES else 0.0 for e in range(EXPERTS)]}


def layer(t: int, hits: str, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (t, D)).at[:, 0].set(1.0)
    router = (jax.random.normal(ks[1], (D, EXPERTS)) * 0.5 * D ** -0.5
              ).at[0].set(jnp.asarray(STEER[hits]))
    params = moe.HeldExperts(
        router, jax.random.normal(ks[2], (EXPERTS,)) * 1e-5,
        (jax.random.normal(ks[3], (HELD, D, 2 * F)) * D ** -0.5
         ).astype(dtype),
        (jax.random.normal(ks[4], (HELD, F, D)) * F ** -0.5).astype(dtype))
    return params, x, jax.random.normal(ks[5], (t, D))


@pytest.fixture
def walked_by_the_kernel(monkeypatch):
    """`held_experts_ffn` as it runs on a TPU inside the gate, the kernel
    interpreted; -> the calls the kernel got."""
    calls = []
    kernel = moe.expert_walk_kernel

    def interpreted(*args):
        calls.append(args)
        return kernel(*args, interpret=True)

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "expert_walk_kernel", interpreted)
    return calls


@pytest.mark.parametrize("onto", [False, True], ids=["zeros", "onto"])
@pytest.mark.parametrize("t", [1, 4, 16, 32])
@pytest.mark.parametrize("hits", list(STEER))
@pytest.mark.parametrize("routing", list(RULES))
def test_the_kernel_is_the_loop(routing, hits, t, onto, request):
    params, x, residual = layer(t, hits)
    # the last quarter of the rows pads the batch (none at one row)
    valid = jnp.arange(t) < t - t // 4
    call = functools.partial(
        moe.held_experts_ffn, params, x, top_k=TOP_K, experts_held=HELD,
        expert_offset=OFFSET, routing=routing, valid=valid, scale=2.5,
        onto=residual if onto else None, **RULES[routing])
    want, counted = call()
    calls = request.getfixturevalue("walked_by_the_kernel")
    got, recounted = call()
    assert len(calls) == 1
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    for a, b in zip(counted, recounted):
        assert np.array_equal(a, b)
    hit = int(counted.hit)
    assert hit == int(np.sum(np.asarray(counted.load) > 0))
    assert {"none": hit == 0, "one": hit == 1, "all": hit == HELD,
            "some": hit <= min(HELD, t * TOP_K)
            and (t < 16 or 0 < hit)}[hits]
    # a row no held expert was chosen by comes back as it went in
    untouched = np.asarray(counted.held) == 0
    assert untouched[np.asarray(~valid)].all()
    assert np.array_equal(
        np.asarray(got)[untouched],
        np.asarray(residual)[untouched] if onto
        else np.zeros((int(untouched.sum()), D), np.float32))


@pytest.mark.parametrize("t", [1, 32])
def test_the_kernel_takes_its_operands_in_the_weights_dtype(
        t, walked_by_the_kernel):
    """bfloat16 matrices: the rows go in as bfloat16, the products
    accumulate and the SwiGLU and combine run in float32, as the loop's."""
    params, x, residual = layer(t, "all", jnp.bfloat16)
    got, counted = moe.held_experts_ffn(
        params, x, top_k=TOP_K, experts_held=HELD, expert_offset=OFFSET,
        onto=residual)
    rows = walked_by_the_kernel[0][0]
    assert rows.dtype == jnp.bfloat16 and got.dtype == jnp.float32
    experts, weights = moe.sigmoid_top_k(x, params.router, params.bias, TOP_K)
    want = np.asarray(residual, np.float64)
    for e in range(HELD):
        h = np.asarray(rows, np.float64) @ np.asarray(params.w_in[e],
                                                      np.float64)
        h = h[:, :F] / (1 + np.exp(-h[:, :F])) * h[:, F:]
        out = (np.asarray(jnp.asarray(h, jnp.float32).astype(jnp.bfloat16),
                          np.float64)
               @ np.asarray(params.w_out[e], np.float64))
        w = np.sum(np.where(np.asarray(experts) - OFFSET == e,
                            np.asarray(weights), 0.0), axis=1)
        want = want + w[:, None] * out
    assert int(counted.hit) == HELD
    np.testing.assert_allclose(got, want, atol=2e-2)


def test_the_list_is_walked_in_order_and_no_further():
    """The kernel alone: experts past `trips` in the list are not
    touched (their matrices hold NaNs here), and none listed twice is
    skipped."""
    params, x, residual = layer(16, "all")
    poisoned = params._replace(
        w_in=params.w_in.at[3].set(jnp.nan),
        w_out=params.w_out.at[3].set(jnp.nan))
    combine = jax.random.uniform(jax.random.PRNGKey(9), (HELD, 16))
    listed = jnp.asarray([2, 0, 2, 3], jnp.int32)
    got = moe.expert_walk_kernel(
        x, listed, jnp.asarray(3), combine, poisoned.w_in, poisoned.w_out,
        residual, interpret=True)
    want = residual
    for e in (2, 0, 2):
        h = x @ params.w_in[e]
        want = want + combine[e][:, None] * (
            (jax.nn.silu(h[:, :F]) * h[:, F:]) @ params.w_out[e])
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
