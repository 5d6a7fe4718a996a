"""ops/mhc.py on the CPU: Sinkhorn's rounds leave a doubly stochastic
matrix, the clamp engages, the maps and the two mixes against
`mhc_reference` token by token, entry and exit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.ops import mhc

N, C, T = 4, 48, 37
SIZES = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))


def case(seed=0, tokens=T, n=N, c=C, alpha=(1.0, 0.7, 1.3), spread=1.0,
         diagonal=2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = n * (n + 2)
    return {"x": jax.random.normal(k[0], (n, tokens, c)) * spread,
            "phi": jax.random.normal(k[1], (n * c, width)) * (n * c) ** -0.5,
            "alpha": jnp.asarray(alpha, jnp.float32),
            "bias": jnp.concatenate([
                jax.random.normal(k[2], (2 * n,)) * 0.3,
                (diagonal * jnp.eye(n)).reshape(-1)])}


def maps_of(c, **sizes):
    m = mhc.mhc_project(c["x"], c["phi"], SIZES["eps"])
    return mhc.mhc_maps(m, c["alpha"], c["bias"], n=c["x"].shape[0],
                        **{**SIZES, **sizes})


@pytest.mark.parametrize("n", [2, 4])
def test_h_res_is_doubly_stochastic_after_20_rounds(n):
    c = case(seed=1, n=n, alpha=(1.0, 0.7, 0.5), diagonal=0.0)
    _, _, res = maps_of(c)
    assert res.shape == (n, n, T)
    np.testing.assert_allclose(np.sum(res, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.sum(res, axis=0), 1.0, atol=1e-5)
    assert float(jnp.min(res)) > 0
    # the maps differ token by token
    assert float(jnp.std(res[0, 0])) > 0.01
    # a harder input (a diagonal of e^2 and a wider spread) is not there in
    # 20 rounds:
    # the columns, normalised last, are exact, the rows within a percent
    _, _, hard = maps_of(case(seed=1, n=n))
    np.testing.assert_allclose(np.sum(hard, axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.sum(hard, axis=1), 1.0, atol=2e-2)


def test_one_round_is_not_enough_and_the_rounds_are_rows_then_columns():
    c = case(seed=2)
    _, _, once = maps_of(c, iters=1)
    # the last normalisation was the columns': exact; the rows' is not yet
    np.testing.assert_allclose(np.sum(once, axis=0), 1.0, atol=1e-5)
    assert float(jnp.max(jnp.abs(jnp.sum(once, axis=1) - 1.0))) > 1e-3


def test_the_clamp_engages():
    """A stream that drives the matrix's input past the clamp: exp of an
    unclamped 200 is inf in float32 and the rounds give nan; clamped, the
    matrix is finite, doubly stochastic and hard (a permutation's)."""
    c = case(seed=3, alpha=(1.0, 1.0, 200.0))
    _, _, res = maps_of(c)
    assert bool(jnp.all(jnp.isfinite(res)))
    np.testing.assert_allclose(np.sum(res, axis=0), 1.0, atol=1e-5)
    _, _, loose = maps_of(c, clamp=(-1e9, 1e9))
    assert not bool(jnp.all(jnp.isfinite(loose)))
    _, _, tight = maps_of(c, clamp=(-1.0, 1.0))
    assert float(jnp.max(tight)) < 0.8 < 0.99 < float(jnp.max(res))


def test_the_maps_ranges():
    pre, post, _ = maps_of(case(seed=4, spread=3.0))
    assert pre.shape == post.shape == (N, T)
    assert 0 < float(jnp.min(pre)) and float(jnp.max(pre)) < 1
    assert 0 < float(jnp.min(post)) and float(jnp.max(post)) < 2
    assert float(jnp.max(post)) > 1      # the factor 2 is there


@pytest.mark.parametrize("seed, spread", [(5, 1.0), (6, 0.05), (7, 20.0)])
def test_a_sub_layer_is_the_reference_token_by_token(seed, spread):
    c = case(seed=seed, spread=spread)
    w = jax.random.normal(jax.random.PRNGKey(seed + 100), (C, C)) * C ** -0.5

    def branch(u):
        return np.tanh(np.asarray(u) @ np.asarray(w, np.float64))

    with jax.default_matmul_precision("highest"):
        pre, post, res = maps_of(c)
        u = mhc.mhc_pre(c["x"], pre)
        y = jnp.tanh(u @ w)
        got = mhc.mhc_post(c["x"], y, post, res)
    want = mhc.mhc_reference(
        np.asarray(c["x"]).transpose(1, 0, 2), c["phi"], c["alpha"],
        c["bias"], branch, **SIZES)
    np.testing.assert_allclose(np.asarray(got).transpose(1, 0, 2), want,
                               atol=2e-5 * max(spread, 1.0))
    assert float(jnp.std(got)) > 0.01


def test_the_scale_of_the_stream_moves_no_map():
    """m is taken from the stream over its own root mean square."""
    c = case(seed=8)
    a = maps_of(c)
    b = maps_of(dict(c, x=c["x"] * 50.0))
    for one, other in zip(a, b):
        np.testing.assert_allclose(one, other, atol=1e-4)


def test_entry_copies_and_exit_sums():
    h = jax.random.normal(jax.random.PRNGKey(9), (T, C))
    x = mhc.mhc_enter(h, N)
    assert x.shape == (N, T, C)
    assert all(np.array_equal(x[i], h) for i in range(N))
    np.testing.assert_allclose(mhc.mhc_exit(x), N * h, rtol=1e-6)
    # identity maps leave a plain residual path: H_pre = 1/n reads the
    # embedding, H_post = 1 and H_res = I add y onto every stream
    y = jax.random.normal(jax.random.PRNGKey(10), (T, C))
    eye = jnp.broadcast_to(jnp.eye(N)[:, :, None], (N, N, T))
    np.testing.assert_allclose(
        mhc.mhc_pre(x, jnp.full((N, T), 1.0 / N)), h, atol=1e-6)
    after = mhc.mhc_post(x, y, jnp.ones((N, T)), eye)
    np.testing.assert_allclose(mhc.mhc_exit(after), N * (h + y), atol=1e-5)


def test_the_maps_trace_to_elementwise_work_alone():
    """Sinkhorn's sums are sums of planes: the traced maps hold no
    reduction and no product but the one with phi."""
    c = case(seed=11)
    text = jax.jit(lambda x: maps_of(dict(c, x=x))).lower(c["x"]).as_text()
    assert text.count("stablehlo.dot_general") == 1
    # the root mean square's four means are the only reductions
    assert text.count("stablehlo.reduce") == N
