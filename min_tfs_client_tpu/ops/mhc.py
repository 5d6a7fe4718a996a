"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual path as n streams a
token. Every sub-layer reads ONE learned, per-token mix of the streams,
and writes its output back onto all of them while the streams themselves
are mixed by a doubly stochastic n x n matrix (Sinkhorn-Knopp on the
exponential of a clamped, per-token matrix):

    m      = rsqrt(mean(vec(X)^2) + eps) * (vec(X) phi)        (n^2 + 2n,)
    H_pre  = sigmoid(alpha_pre m[:n] + b[:n])
    H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])
    H_res  = sinkhorn(exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]))))
    u      = sum_i H_pre[i] X[i]            y = F(norm(u))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

Plain jax.numpy, float32 throughout, for any leading shape of rows. The
layout is chosen for the chip: the STREAMS lie on the leading axis, X (n,
T, C), so a stream is a plane of whole tiles (a (T, n, C) array pads n =
4 to 8 sublanes); the MAPS keep the tokens on the last axis, H_pre and
H_post (n, T) and H_res (n, n, T), and Sinkhorn's sums are written as
sums of planes: every round is elementwise over dense (T,) vectors, which
XLA fuses, and no reduction over a padded 4 x 4 tile is launched.

`mhc_reference` is the same equations token by token in numpy, the
tests' yardstick.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

UNROLL = 5   # Sinkhorn's rounds a trip of its loop: a trip costs the chip a
#              launch, a round traced again costs the compilers their time
H_POST_RANGE = 2.0   # H_post = 2 sigmoid(.): the paper's, 1 at a zero input


def mhc_project(x: jax.Array, phi: jax.Array, eps: float) -> jax.Array:
    """The streams x (n, T, C) float32 through phi (n * C, K) -> m (K, T):
    the flattened stream's product, at full float32 precision, times the
    reciprocal root mean square of ALL n * C values (no learned scale: it
    folds into phi)."""
    n, _, c = x.shape
    product = jnp.einsum("ntc,nck->kt", x, phi.reshape(n, c, -1),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    mean_square = sum(jnp.mean(x[i] * x[i], axis=-1) for i in range(n)) / n
    return product * jax.lax.rsqrt(mean_square + eps)


def sinkhorn(matrix: jax.Array, iters: int, eps: float) -> jax.Array:
    """(n, n, T) positive -> doubly stochastic: `iters` rounds, a round
    the rows (over axis 1) and then the columns (over axis 0), each sum
    with `eps` added. A round works on the n * n planes one by one, every
    value a (T,) vector of one shape: no slice, no broadcast and no
    reduction stands between two operations, so a round is one
    elementwise chain for XLA to fuse; the rounds are a `fori_loop` (the
    same chain traced 20 times over took the compilers ten times as
    long)."""
    n = matrix.shape[0]

    def one_round(_, m):
        rows = [sum(m[i]) + eps for i in range(n)]
        m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        columns = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        return [[m[i][j] / columns[j] for j in range(n)] for i in range(n)]

    m = jax.lax.fori_loop(
        0, iters, one_round,
        [[matrix[i, j] for j in range(n)] for i in range(n)], unroll=UNROLL)
    return jnp.stack([jnp.stack(row) for row in m])


def mhc_maps(m: jax.Array, alpha: jax.Array, bias: jax.Array, *, n: int,
             iters: int, eps: float, clamp: tuple):
    """m (n^2 + 2n, T) -> H_pre (n, T), H_post (n, T), H_res (n, n, T).
    alpha (3,): pre, post, res; bias (n^2 + 2n,), its last n^2 the
    matrix row by row."""
    bias = bias[:, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
    post = H_POST_RANGE * jax.nn.sigmoid(alpha[1] * m[n:2 * n]
                                         + bias[n:2 * n])
    logits = jnp.clip(alpha[2] * m[2 * n:] + bias[2 * n:], *clamp)
    res = sinkhorn(jnp.exp(logits).reshape(n, n, -1), iters, eps)
    return pre, post, res


def mhc_pre(x: jax.Array, pre: jax.Array) -> jax.Array:
    """What a sub-layer reads: x (n, T, C), H_pre (n, T) -> u (T, C)."""
    return sum(pre[i][:, None] * x[i] for i in range(x.shape[0]))


def mhc_post(x: jax.Array, y: jax.Array, post: jax.Array,
             res: jax.Array) -> jax.Array:
    """What a sub-layer leaves: x (n, T, C) mixed by H_res (n, n, T), the
    sub-layer's output y (T, C) written onto every stream by H_post (n,
    T) -> (n, T, C)."""
    n = x.shape[0]
    return jnp.stack([
        sum(res[i, j][:, None] * x[j] for j in range(n))
        + post[i][:, None] * y for i in range(n)])


def mhc_enter(h: jax.Array, n: int) -> jax.Array:
    """Entry: every stream is the token's embedding. (T, C) -> (n, T, C)."""
    return jnp.broadcast_to(h[None], (n, *h.shape))


def mhc_exit(x: jax.Array) -> jax.Array:
    """Exit: the streams' sum. (n, T, C) -> (T, C)."""
    return sum(x[i] for i in range(x.shape[0]))


def mhc_reference(x, phi, alpha, bias, branch, *, iters: int, eps: float,
                  clamp: tuple):
    """One sub-layer token by token, numpy float64: x (T, n, C) -> (T, n,
    C). `branch(u (C,)) -> (C,)` is the sub-layer (its norm included)."""
    x = np.asarray(x, np.float64)
    phi, alpha, bias = (np.asarray(a, np.float64) for a in (phi, alpha, bias))
    t, n, _ = x.shape
    out = np.zeros_like(x)
    for token in range(t):
        flat = x[token].reshape(-1)
        m = flat @ phi / np.sqrt(np.mean(flat * flat) + eps)
        pre = 1 / (1 + np.exp(-(alpha[0] * m[:n] + bias[:n])))
        post = H_POST_RANGE / (1 + np.exp(-(alpha[1] * m[n:2 * n]
                                            + bias[n:2 * n])))
        matrix = np.exp(np.clip(alpha[2] * m[2 * n:] + bias[2 * n:],
                                *clamp)).reshape(n, n)
        for _ in range(iters):
            matrix = matrix / (matrix.sum(axis=1, keepdims=True) + eps)
            matrix = matrix / (matrix.sum(axis=0, keepdims=True) + eps)
        y = np.asarray(branch(pre @ x[token]), np.float64)
        out[token] = matrix @ x[token] + post[:, None] * y[None]
    return out
