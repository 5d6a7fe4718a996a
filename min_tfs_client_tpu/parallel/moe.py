"""Expert parallelism: Switch-style MoE FFN sharded over an "expert" axis.

Completes the §2.11 parallelism inventory (SURVEY.md row "Expert
parallel") the TPU way — the GShard/Switch formulation: routing is
expressed as dense one-hot dispatch/combine einsums over an expert-major
tensor whose expert dim is sharded on the mesh's "expert" axis, and GSPMD
materializes the token all-to-alls on ICI from the shardings alone. No
hand-written NCCL alltoall, no host-side routing tables; capacity is a
static shape so every step compiles once.

Routing math (Switch Transformer, top-1):
- router logits (G, E) over G = B*S token groups; softmax -> gates;
- each token goes to its argmax expert, position = its running count
  within that expert, tokens beyond capacity C are dropped (output 0);
- dispatch tensor D (G, E, C) one-hot; combine tensor = D * gate;
- expert_in (E, C, D) = einsum(D, x); FFN per expert; combine back.

The auxiliary load-balancing loss (mean fraction * mean router prob per
expert, scaled by E) is returned for training use.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from min_tfs_client_tpu.parallel.mesh import EXPERT_AXIS


class MoeParams(NamedTuple):
    router: jax.Array  # (D, E)
    w_in: jax.Array    # (E, D, F)
    b_in: jax.Array    # (E, F)
    w_out: jax.Array   # (E, F, D)
    b_out: jax.Array   # (E, D)


def init_moe_params(rng: jax.Array, d_model: int, d_ff: int,
                    num_experts: int, dtype=jnp.float32) -> MoeParams:
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    return MoeParams(
        router=(jax.random.normal(k1, (d_model, num_experts)) *
                scale_in).astype(dtype),
        w_in=(jax.random.normal(k2, (num_experts, d_model, d_ff)) *
              scale_in).astype(dtype),
        b_in=jnp.zeros((num_experts, d_ff), dtype),
        w_out=(jax.random.normal(k3, (num_experts, d_ff, d_model)) *
               scale_out).astype(dtype),
        b_out=jnp.zeros((num_experts, d_model), dtype),
    )


def expert_shardings(mesh: Mesh,
                     axis_name: str = EXPERT_AXIS) -> MoeParams:
    """NamedShardings placing the expert dim of each weight on `axis_name`
    (router weights are replicated — every device routes its tokens)."""
    return MoeParams(
        router=NamedSharding(mesh, P()),
        w_in=NamedSharding(mesh, P(axis_name, None, None)),
        b_in=NamedSharding(mesh, P(axis_name, None)),
        w_out=NamedSharding(mesh, P(axis_name, None, None)),
        b_out=NamedSharding(mesh, P(axis_name, None)),
    )


def shard_moe_params(params: MoeParams, mesh: Mesh,
                     axis_name: str = EXPERT_AXIS) -> MoeParams:
    shardings = expert_shardings(mesh, axis_name)
    return MoeParams(*(jax.device_put(p, s)
                       for p, s in zip(params, shardings)))


def capacity_for(num_tokens: int, num_experts: int,
                 capacity_factor: float = 1.25) -> int:
    """Static per-expert token capacity (Switch capacity rule)."""
    return max(1, int(np.ceil(num_tokens / num_experts * capacity_factor)))


def moe_ffn(params: MoeParams, x: jax.Array, *,
            capacity: int) -> tuple[jax.Array, jax.Array]:
    """Switch MoE FFN. x (B, S, D) -> (y (B, S, D), aux_loss scalar).

    Tokens routed past an expert's static `capacity` produce zeros (the
    residual connection around the layer carries them through — Switch
    semantics). Under jit with `shard_moe_params` weights, the dispatch
    and combine einsums become ICI all-to-alls on the expert axis.
    """
    b, s, d = x.shape
    e = params.router.shape[1]
    g = b * s
    tokens = x.reshape(g, d)

    router_logits = tokens.astype(jnp.float32) @ params.router.astype(
        jnp.float32)                                          # (G, E)
    gates = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)                   # (G,)
    gate = jnp.take_along_axis(gates, expert_idx[:, None], 1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)   # (G, E)
    # Position of each token within its chosen expert's queue.
    position = jnp.cumsum(onehot, axis=0) * onehot - 1        # (G, E)
    pos_in_expert = jnp.sum(position * onehot, axis=-1)       # (G,)
    keep = pos_in_expert < capacity

    # dispatch (G, E, C): 1 where token g occupies slot c of expert e.
    slot = jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.int32)
    dispatch = (onehot[:, :, None] * slot[:, None, :] *
                keep[:, None, None]).astype(x.dtype)
    combine = dispatch * gate.astype(x.dtype)[:, None, None]

    # Expert-major compute; the e dim carries the expert-axis sharding.
    expert_in = jnp.einsum("gec,gd->ecd", dispatch, tokens)   # (E, C, D)
    h = jnp.einsum("ecd,edf->ecf", expert_in, params.w_in)
    h = jax.nn.relu(h + params.b_in[:, None, :])
    expert_out = jnp.einsum("ecf,efd->ecd", h, params.w_out)
    expert_out = expert_out + params.b_out[:, None, :]
    y = jnp.einsum("gec,ecd->gd", combine, expert_out)        # (G, D)

    # Switch aux loss: encourages uniform routing. fraction (E,): share of
    # tokens per expert; prob (E,): mean router probability.
    fraction = jnp.mean(onehot.astype(jnp.float32), axis=0)
    prob = jnp.mean(gates, axis=0)
    aux_loss = e * jnp.sum(fraction * prob)
    return y.reshape(b, s, d), aux_loss


def moe_ffn_reference(params: MoeParams, x: jax.Array) -> jax.Array:
    """Dense oracle: every token through its argmax expert, no capacity
    limit — what moe_ffn converges to with capacity >= tokens-per-expert
    max. For tests."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    gates = jax.nn.softmax(
        tokens.astype(jnp.float32) @ params.router.astype(jnp.float32), -1)
    idx = jnp.argmax(gates, axis=-1)
    gate = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0].astype(x.dtype)

    def one(tok, i, gt):
        h = jax.nn.relu(tok @ params.w_in[i] + params.b_in[i])
        return (h @ params.w_out[i] + params.b_out[i]) * gt

    out = jax.vmap(one)(tokens, idx, gate)
    return out.reshape(b, s, d)


# -- a chip's share of a dropless top-k expert layer -------------------------
#
# The served form of expert parallelism (models/mimo.py, granite_hybrid.py,
# ling_hybrid.py): the layer is told WHICH experts this chip holds, routes
# every token over ALL the experts (the router keeps its published width)
# by the model's own rule (`ROUTINGS`: sigmoid top-k, softmax top-k, or
# group-limited sigmoid top-k, whose group choice too runs over all the
# experts on every share), and computes the part of the result that its
# own experts give. What the absent experts would add is
# left out; the exchange that a deployment runs between its chips is not
# here, and nothing stands in for it. Nothing is dropped: the static bound
# is every (token, choice) pair, walked in row blocks by a loop whose trip
# count is the number of pairs that fell on held experts.


class HeldExperts(NamedTuple):
    router: jax.Array  # (D, E) over ALL experts
    bias: jax.Array | None  # (E,) the sigmoid selection's correction bias
    w_in: jax.Array    # (held, D, 2 F): gate and up, side by side
    w_out: jax.Array   # (held, F, D)


class Routed(NamedTuple):
    """What the layer counted: `held` (T,) pairs of each token that fell
    on held experts, `load` (held,) rows each held expert computed, `hit`
    () the products the walk over hit experts ran (0 where the pairs
    were sorted)."""
    held: jax.Array
    load: jax.Array
    hit: jax.Array


ROW_BLOCK = 2048   # rows a grouped product takes at a time
# Rows up to which the layer walks the experts that were hit, all rows
# through each: a product of T rows with one expert's matrices does T
# FLOPs a byte of weight, so below the chip's ridge (240 on the v5e) the
# rows ride the weights' stream for nothing, and the sort, gathers and
# scatter-add that keep a row off the experts it did not choose cost more
# than they save. On the v5e the walk takes 0.65 / 1.03 / 1.23 / 1.54 ms
# at 32 / 64 / 128 / 256 rows where the sorted pairs take 0.91 / 2.25 /
# 2.65 / 3.06 (16 held, all hit: PERF.md section 5); above 256 nothing
# was read. The same holds where most held experts are hit by NO row: at
# 128 held of 512 under the group-limited rule (tests/tpu/ling_pieces.py,
# PRs 51 and 52) 12 / 20 / 32 valid rows of 32 hit 27 / 37 / 51 experts
# and the walk as a loop of XLA products takes 0.62 / 0.82 / 1.11 ms, 22
# us a hit expert of 11.8 MB (two thirds of the chip's bandwidth), where
# the sorted pairs' `ragged_dot` over 128 groups takes 0.79 / 1.04 /
# 1.40: the walk's list of hit experts (comparisons over held x held)
# costs nothing to speak of. As one kernel (`_expert_walk_kernel`, below)
# the same walk takes 0.43 / 0.59 / 0.81 ms, 16 us a hit expert, where
# the loop takes 0.58 / 0.78 / 1.07 (16 applications a call: timed call
# by call from the host as the figures before, 0.57 / 0.64 / 0.86, the
# first of them the host's dispatch and not the device).
DECODE_ROWS = 256


def sigmoid_top_k(x: jax.Array, router: jax.Array, bias: jax.Array,
                  top_k: int) -> tuple[jax.Array, jax.Array]:
    """Scores sigmoid(x W) over all experts in float32 at full matmul
    precision (a choice among near-equal scores must not turn on how a
    product was rounded); the top_k of scores + bias are chosen, and the
    weights are the chosen scores over their sum. -> (experts (T, k)
    int32, weights (T, k) float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return (experts.astype(jnp.int32),
            chosen / jnp.sum(chosen, axis=-1, keepdims=True))


def softmax_top_k(x: jax.Array, router: jax.Array,
                  top_k: int) -> tuple[jax.Array, jax.Array]:
    """Logits x W over all experts in float32 at full matmul precision
    (as `sigmoid_top_k`); the top_k logits are chosen, and the weights
    are the softmax of the chosen logits alone. -> (experts (T, k) int32,
    weights (T, k) float32)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    chosen, experts = jax.lax.top_k(logits, top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)


def sigmoid_grouped_top_k(x: jax.Array, router: jax.Array, bias: jax.Array,
                          top_k: int, n_group: int, topk_group: int
                          ) -> tuple[jax.Array, jax.Array]:
    """Group-limited sigmoid routing (DeepSeek-V3's `noaux_tc`): scores
    sigmoid(x W) over ALL experts as `sigmoid_top_k`'s; the experts lie
    in `n_group` groups of equal size, a group's score is the sum of its
    two largest scores + bias, only the `topk_group` best groups stay in
    the choice, and the top_k of scores + bias among their experts are
    chosen; the weights are the chosen scores (no bias) over their sum.
    Every chip of a layer runs the group choice over all the experts,
    whichever of them it holds. -> (experts (T, k) int32, weights (T, k)
    float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    biased = scores + bias.astype(jnp.float32)
    t, e = biased.shape
    by_group = biased.reshape(t, n_group, e // n_group)
    best_two, _ = jax.lax.top_k(by_group, 2)
    _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    stays = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
    _, experts = jax.lax.top_k(jnp.where(
        stays[:, :, None], by_group, -jnp.inf).reshape(t, e), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return (experts.astype(jnp.int32),
            chosen / jnp.sum(chosen, axis=-1, keepdims=True))


ROUTINGS = ("sigmoid", "softmax_top_k", "sigmoid_grouped")


def held_experts_ffn(params: HeldExperts, x: jax.Array, *, top_k: int,
                     experts_held: int, expert_offset: int,
                     valid: jax.Array | None = None,
                     rows: jax.Array | None = None,
                     onto: jax.Array | None = None,
                     row_block: int = ROW_BLOCK,
                     routing: str = "sigmoid",
                     scale: float | None = None,
                     n_group: int | None = None,
                     topk_group: int | None = None
                     ) -> tuple[jax.Array, Routed]:
    """x (T, D) -> (y (T, D) float32, Routed): y = sum over a token's
    chosen experts e in [expert_offset, expert_offset + experts_held) of
    w_e * SwiGLU_e(x). A token none of whose choices is held gets 0.
    `routing` (static, the model's to say from its published config) is
    the rule that gives the choices and the w_e: `sigmoid`
    (`sigmoid_top_k`, with the selection bias), `softmax_top_k`, or
    `sigmoid_grouped` (`sigmoid_grouped_top_k`, with the bias, `n_group`
    and `topk_group`).
    `scale` multiplies every w_e (a model's residual multiplier, so that
    `onto` can be its residual stream).
    `valid` (T,) bool leaves padding rows out of the routing altogether.
    `rows` (a traced count) says that only the leading `rows` rows are
    real: the router then runs in blocks of `row_block` rows, as many as
    those rows fill, and the rows behind them are routed nowhere. `onto`
    (T, D) float32 is what the experts' rows are added onto in place of
    zeros (a caller's residual stream: y = onto + the layer's result).

    One algorithm in two forms, chosen by the static row count: up to
    DECODE_ROWS rows (and no `rows=`) the layer walks the experts that
    were hit (`_by_hit_expert`: on a TPU as ONE kernel that streams them
    back to back wherever `_walk_kernel_applies` reads from the shapes
    that two experts' matrices fit its VMEM, as a loop of XLA products
    otherwise); above, the (token, choice) pairs are
    sorted by held expert (`_by_sorted_pair`). Per (token, expert) every
    product, rounding and accumulation is the same in all: rows in the
    weights' dtype, float32 accumulation, SwiGLU and the combine in
    float32; only the order in which a token's terms are added differs
    (the kernel's is the loop's).
    The router reads x as it is given (float32 from the models)."""
    t, _ = x.shape
    if params.w_in.shape[0] != experts_held:
        raise ValueError("w_in holds another number of experts than held")
    if routing not in ROUTINGS:
        raise ValueError(f"unknown routing {routing!r}; known: {ROUTINGS}")

    def choose(some):
        if routing == "sigmoid":
            return sigmoid_top_k(some, params.router, params.bias, top_k)
        if routing == "sigmoid_grouped":
            return sigmoid_grouped_top_k(some, params.router, params.bias,
                                         top_k, n_group, topk_group)
        return softmax_top_k(some, params.router, top_k)

    if rows is None:
        experts, weights = choose(x)
    else:
        step = min(row_block, t)

        def route(i, found):
            lo = jnp.minimum(i * step, t - step)   # the last block may lap
            some = choose(jax.lax.dynamic_slice_in_dim(x, lo, step))
            return tuple(jax.lax.dynamic_update_slice_in_dim(all_, part, lo, 0)
                         for all_, part in zip(found, some))

        experts, weights = jax.lax.fori_loop(
            0, (rows + step - 1) // step, route,
            (jnp.zeros((t, top_k), jnp.int32),
             jnp.zeros((t, top_k), jnp.float32)))
        real = jnp.arange(t) < rows
        valid = real if valid is None else jnp.logical_and(valid, real)
    if scale is not None:
        weights = weights * scale
    local = experts - expert_offset
    held = jnp.logical_and(local >= 0, local < experts_held)
    if valid is not None:
        held = jnp.logical_and(held, valid[:, None])
    y = jnp.zeros(x.shape, jnp.float32) if onto is None else onto
    if rows is None and t <= DECODE_ROWS:
        y, load, hit = _by_hit_expert(params, x, local, weights, held, y)
    else:
        y, load, hit = _by_sorted_pair(params, x, local, weights, held, y,
                                       row_block)
    return y, Routed(held=jnp.sum(held, axis=-1, dtype=jnp.int32), load=load,
                     hit=hit)


def _by_sorted_pair(params: HeldExperts, x: jax.Array, local: jax.Array,
                    weights: jax.Array, held: jax.Array, y: jax.Array,
                    row_block: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The pairs (`local` (T, k) the chosen experts counted from the
    first held one, `held` (T, k) which of them count) are sorted by
    held expert (pairs on absent experts last), and the held ones pass
    the grouped gate/up/down products (`jax.lax.ragged_dot`) in blocks
    of `row_block` rows: as many blocks as the held pairs fill. -> (y
    with the layer's result added, `Routed.load`, `Routed.hit`)."""
    t, top_k = local.shape
    experts_held, d_ff = params.w_out.shape[:2]
    key = jnp.where(held, local, experts_held).reshape(-1)      # (T k,)
    pairs = t * top_k
    block = min(row_block, -(-pairs // 8) * 8)
    padded = -(-pairs // block) * block
    order = jnp.pad(jnp.argsort(key, stable=True), (0, padded - pairs))
    load = jnp.zeros((experts_held + 1,), jnp.int32).at[key].add(
        1)[:experts_held]
    ends = jnp.cumsum(load)
    starts = ends - load
    total = ends[-1]
    flat_weights = weights.reshape(-1)

    def rows_of(i, y):
        lo = i * block
        pair = jax.lax.dynamic_slice(order, (lo,), (block,))
        token = pair // top_k
        sizes = (jnp.clip(ends, lo, lo + block)
                 - jnp.clip(starts, lo, lo + block))
        h = jax.lax.ragged_dot(x[token].astype(params.w_in.dtype),
                               params.w_in, sizes,
                               preferred_element_type=jnp.float32)
        h = jax.nn.silu(h[:, :d_ff]) * h[:, d_ff:]
        out = jax.lax.ragged_dot(h.astype(params.w_out.dtype), params.w_out,
                                 sizes, preferred_element_type=jnp.float32)
        live = (lo + jnp.arange(block) < total)[:, None]
        out = jnp.where(live, out * flat_weights[pair][:, None], 0.0)
        return y.at[token].add(out)

    y = jax.lax.fori_loop(0, (total + block - 1) // block, rows_of, y)
    return y, load, jnp.zeros((), jnp.int32)


def _by_hit_expert(params: HeldExperts, x: jax.Array, local: jax.Array,
                   weights: jax.Array, held: jax.Array, y: jax.Array
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A few rows: ALL of them through each held expert that at least
    one row chose, and only through those (a loop over the list of hit
    experts, its trip count their number), each row's result entering
    with its weight for that expert, 0 where it did not choose it. No
    sort, no gather, no scatter: the combine weights, the load and the
    list come from comparisons and sums. -> as `_by_sorted_pair`."""
    experts_held, d_ff = params.w_out.shape[:2]
    each = jnp.arange(experts_held, dtype=jnp.int32)
    chose = jnp.logical_and(local[:, :, None] == each, held[:, :, None])
    combine = jnp.sum(jnp.where(chose, weights[:, :, None], 0.0),
                      axis=1).T                                 # (held, T)
    load = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)
    hit = load > 0
    place = jnp.cumsum(hit) - 1        # of a hit expert in the list
    listed = jnp.sum(jnp.where(
        jnp.logical_and(hit, place == each[:, None]), each, 0), axis=1)
    rows = x.astype(params.w_in.dtype)
    trips = jnp.sum(hit, dtype=jnp.int32)
    if _on_tpu() and _walk_kernel_applies(params, rows):
        return (expert_walk_kernel(rows, listed, trips, combine,
                                   params.w_in, params.w_out, y),
                load, trips)

    def one(i, y):
        e = listed[i]
        h = jnp.dot(rows, params.w_in[e], preferred_element_type=jnp.float32)
        h = jax.nn.silu(h[:, :d_ff]) * h[:, d_ff:]
        out = jnp.dot(h.astype(params.w_out.dtype), params.w_out[e],
                      preferred_element_type=jnp.float32)
        return y + combine[e][:, None] * out

    return jax.lax.fori_loop(0, trips, one, y), load, trips


# -- the walk as one kernel --------------------------------------------------
#
# The loop above is two dependent XLA products a trip, their weight
# operands sliced by `listed[i]`: trip i + 1 starts nothing before trip i
# has ended, so every hit expert pays the start and the drain of its own
# stream of weights. Where an expert is small that is a third of its time
# (22 us a hit expert of 11.8 MB at 128 held of 512, 14.4 at the v5e's
# bandwidth; PERF.md section 5). The kernel keeps ONE stream going: the
# matrices stay where they lie in HBM, expert i + 1's are on their way
# into one pair of VMEM buffers while expert i's products run out of the
# other, and an expert that no row chose costs nothing at all.

_WALK_SLOTS = 2       # experts in VMEM: the one computed, the one fetched


def _expert_walk_kernel(listed_ref, trips_ref, rows_ref, combine_ref,
                        onto_ref, w_in_hbm, w_out_hbm, y_ref, in_buf,
                        out_buf, sem):
    """y = onto + sum over i < trips of combine[:, e] * (SwiGLU(rows @
    w_in[e]) @ w_out[e]), e = listed[i], in list order. listed_ref
    (held,) and trips_ref (1,) in SMEM; rows (T, D) in the weights' dtype,
    combine (T, held) float32 and onto (T, D) float32 whole in VMEM; w_in
    (held, D, 2 F) and w_out (held, F, D) in HBM; in_buf and out_buf two
    slots of one expert's matrices, sem (2, slots) their copies'."""
    trips = trips_ref[0]
    d_ff = out_buf.shape[1]

    def copies(i, slot):
        e = listed_ref[i]
        return (pltpu.make_async_copy(w_in_hbm.at[e], in_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(w_out_hbm.at[e], out_buf.at[slot],
                                      sem.at[1, slot]))

    def start(i, slot):
        for copy in copies(i, slot):
            copy.start()

    pl.when(trips > 0)(lambda: start(0, 0))
    y_ref[...] = onto_ref[...]
    # which lane of `combine` is expert e's: its column is picked by a
    # masked sum over the lanes (one term, so exact)
    expert = jax.lax.broadcasted_iota(jnp.int32, combine_ref.shape, 1)

    def one(i, _):
        slot = i % _WALK_SLOTS
        pl.when(i + 1 < trips)(
            lambda: start(i + 1, (i + 1) % _WALK_SLOTS))
        fetched_in, fetched_out = copies(i, slot)
        fetched_in.wait()  # servelint: blocks a DMA's semaphore on the device
        h = jnp.dot(rows_ref[...], in_buf[slot],
                    preferred_element_type=jnp.float32)
        h = jax.nn.silu(h[:, :d_ff]) * h[:, d_ff:]
        fetched_out.wait()  # servelint: blocks a DMA's semaphore on the device
        out = jnp.dot(h.astype(out_buf.dtype), out_buf[slot],
                      preferred_element_type=jnp.float32)
        weight = jnp.sum(jnp.where(expert == listed_ref[i],
                                   combine_ref[...], 0.0),
                         axis=1, keepdims=True)                  # (T, 1)
        y_ref[...] += weight * out
        return None

    jax.lax.fori_loop(0, trips, one, None)


def _walk_rows(t: int, dtype) -> int:
    """Rows as the kernel sees them: whole sublane tiles of `dtype`."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return -(-t // tile) * tile


def _walk_vmem_bytes(t: int, held: int, d: int, d_ff: int,
                     itemsize: int) -> int:
    """VMEM the kernel takes: `_WALK_SLOTS` experts' matrices; the rows,
    combine, `onto` and y, each in the two buffers a call's operands
    get; the products' float32 results (T, 2 F), (T, F) and (T, D), the
    middle one again in the weights' dtype."""
    lanes = -(-held // 128) * 128
    return (_WALK_SLOTS * 3 * d * d_ff * itemsize
            + t * (2 * (d * itemsize + 4 * lanes + 2 * 4 * d)
                   + 4 * (3 * d_ff + d) + d_ff * itemsize))


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_walk_kernel(rows: jax.Array, listed: jax.Array, trips: jax.Array,
                       combine: jax.Array, w_in: jax.Array, w_out: jax.Array,
                       onto: jax.Array, *, interpret: bool = False
                       ) -> jax.Array:
    """`_by_hit_expert`'s loop as ONE Pallas call: rows (T, D) in the
    weights' dtype, `listed` (held,) the hit experts first, `trips` ()
    how many they are, `combine` (held, T) float32, `onto` (T, D)
    float32 -> onto + the listed experts' weighted rows, float32. Each
    product, rounding and accumulation is the loop's own, the experts
    added in list order. `onto`'s buffer is the result's."""
    t, d = rows.shape
    held, d_ff, _ = w_out.shape
    padded = _walk_rows(t, rows.dtype)
    # XLA plans what IT keeps in VMEM around a call by the limit the call
    # names: at 64 MiB it moved a whole recurrent state and a latent
    # cache in and out of VMEM every decode step, at the account and
    # 4 MiB for what Mosaic keeps it moved nothing (AOT for the v5e,
    # PERF.md section 6, PR 52)
    vmem_limit = _walk_vmem_bytes(
        padded, held, d, d_ff, rows.dtype.itemsize) + (4 << 20)
    pad = ((0, padded - t), (0, 0))
    rows, onto, by_row = (jnp.pad(a, pad) if padded > t else a
                          for a in (rows, onto, combine.T))
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, listed, trips: (0, 0))
    y = pl.pallas_call(
        _expert_walk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # listed, trips
            grid=(1,),
            in_specs=[whole((padded, d)), whole((padded, held)),
                      whole((padded, d)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole((padded, d)),
            scratch_shapes=[
                pltpu.VMEM((_WALK_SLOTS, d, 2 * d_ff), w_in.dtype),
                pltpu.VMEM((_WALK_SLOTS, d_ff, d), w_out.dtype),
                pltpu.SemaphoreType.DMA((2, _WALK_SLOTS)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        # operand 4: the two prefetched scalars, rows and combine first
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="_expert_walk_kernel",  # the device-trace reduction finds it
    )(listed.astype(jnp.int32), jnp.reshape(trips, (1,)).astype(jnp.int32),
      rows, by_row, onto, w_in, w_out)
    return y[:t]


# What the kernel may hold in VMEM by its own account (the v5e has 128
# MiB). On the v5e, 32 rows, the walk alone (tests/tpu/ling_pieces.py
# and PERF.md section 6, PR 52): experts of 11.8 MB (2 x 11.8 in flight)
# 15.7-16.1 us a hit expert where the loop takes 22.2-22.5; of 18.9 MB
# (2 x 18.9) 25.5-26.0 where the loop takes 33.1-33.4: both inside.
# Experts of 50.3 MB would want 100.6 MB in flight and stay with the
# loop (74.4 us a hit expert, 61.4 by their bytes): cut along F they
# would fit, and were not tried.
_WALK_VMEM_BYTES = 48 << 20


def _walk_kernel_applies(params: HeldExperts, rows: jax.Array) -> bool:
    """The shapes `_expert_walk_kernel` is written for, read from the
    shapes alone: rows and gated halves of whole 128-lane tiles, one
    dtype for both matrices, and two experts' matrices beside the rows
    inside VMEM (`_walk_vmem_bytes`). One device only: XLA cannot split
    a Mosaic kernel over a mesh by itself."""
    held, d_ff, d = params.w_out.shape
    mesh = jax.sharding.get_abstract_mesh()
    return (d % 128 == 0 and d_ff % 128 == 0
            and params.w_in.dtype == params.w_out.dtype == rows.dtype
            and _walk_vmem_bytes(_walk_rows(rows.shape[0], rows.dtype),
                                 held, d, d_ff, rows.dtype.itemsize)
            <= _WALK_VMEM_BYTES
            and not set(mesh.axis_names) - set(mesh.manual_axes))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"
