"""What every decoder served as whole generations does alike, once. A
model file (models/mimo.py, models/granite_hybrid.py) writes its own
layers between these pieces: its `prefill` is `prefill_by_chunks` over a
chunk function that starts with `pack`, its `step` starts with `choose`
and ends with `advance`; servables/decode_signatures.generation_signature
serves the two (docs/MIGRATING.md, "To add a decoder family").

The state a generation carries: `caches` (a model's own tree, one row an
example), `length` (B,), `logits` (B, V) the next token is chosen from,
`token` (B, 1) the last one chosen, `finished` (B,), and `counts`: what
the prefill and the steps counted, a row an example or one figure for the
batch. A prompt of length 0 pads the batch: no request owns that row.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

PREFILL_ROW_BLOCK = 512   # packed rows the per-token work takes at a time


@dataclasses.dataclass(frozen=True)
class Packing:
    """A chunk's (b, s) tokens PACKED: the `total` real ones first, in
    (example, position) order, in `t` rows (b * s rounded up to whole
    blocks). What treats rows one by one runs over the `blocks` blocks of
    `block` rows they fill; what mixes an example's rows (attention, a
    scan) sees the (example, position) grid.

    lengths, starts, ends (b,): example e's rows are starts[e]..ends[e];
    position (t,): a packed row's position in its example; on_grid (t,):
    where it lies on the flattened grid (rows past the last: anywhere);
    tokens (t,): the packed token ids, pad_id past the last real one."""

    b: int
    s: int
    block: int
    t: int
    lengths: jax.Array
    starts: jax.Array
    ends: jax.Array
    total: jax.Array
    blocks: jax.Array
    position: jax.Array
    on_grid: jax.Array
    tokens: jax.Array

    def over_blocks(self, body: Callable, carry):
        """`body(lo, carry) -> carry` for the first row `lo` of each block
        the real tokens fill."""
        return jax.lax.fori_loop(
            0, self.blocks, lambda i, c: body(i * self.block, c), carry)

    def cut(self, x: jax.Array, lo) -> jax.Array:
        return jax.lax.dynamic_slice_in_dim(x, lo, self.block)

    def put(self, x: jax.Array, part: jax.Array, lo) -> jax.Array:
        return jax.lax.dynamic_update_slice_in_dim(x, part, lo, 0)

    def grid(self, packed: jax.Array) -> jax.Array:
        """(t, width) packed -> (b, s, width): example e's s rows from
        its first (starts[e] + s <= (e + 1) s: inside the buffer; the
        rows behind its last are whatever lies there)."""
        return jnp.stack([
            jax.lax.dynamic_slice_in_dim(packed, self.starts[e], self.s)
            for e in range(self.b)])

    def back(self, on_the_grid: jax.Array, lo) -> jax.Array:
        """The block's rows of what the grid's reader gave, (b * s,
        width) by row index; rows past the last real one read zeros."""
        real = (lo + jnp.arange(self.block) < self.total)[:, None]
        return jnp.where(real, on_the_grid[self.cut(self.on_grid, lo)], 0)

    def held_by_example(self, routed_held: jax.Array,
                        onto: jax.Array) -> jax.Array:
        """A count a packed row (t,) -> its sum over each example's rows,
        added onto `onto` (b,), the caller's running sums."""
        counted = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(routed_held)])
        return onto + counted[self.ends] - counted[self.starts]

    def last_rows(self, h: jax.Array) -> jax.Array:
        """Each example's last real row of h (t, D) -> (b, D); an example
        of no token: zeros, whatever its neighbours are."""
        return jnp.where(self.lengths[:, None] > 0,
                         h[jnp.maximum(self.ends - 1, 0)], 0.0)


def pack(ids: jax.Array, pad_id: int, row_block: int) -> Packing:
    """The packing of a chunk (b, s) of prompts right-padded with pad_id."""
    b, s = ids.shape
    block = min(row_block, b * s)
    t = -(-b * s // block) * block
    lengths = jnp.sum((ids != pad_id).astype(jnp.int32), axis=-1)
    ends = jnp.cumsum(lengths)
    starts, total = ends - lengths, ends[-1]
    blocks = (total + block - 1) // block
    row = jnp.arange(t)
    example = jnp.minimum(jnp.searchsorted(ends, row, side="right"), b - 1)
    position = row - starts[example]
    on_grid = jnp.clip(example * s + position, 0, b * s - 1)
    tokens = jnp.where(row < total, ids.reshape(-1)[on_grid], pad_id)
    return Packing(b, s, block, t, lengths, starts, ends, total, blocks,
                   position, on_grid, tokens)


def prefill_by_chunks(chunk_fn: Callable, input_ids, *, rows: int,
                      pad_id: int, extra_counts: tuple = ()) -> dict:
    """The prompts (B, seq_len), right-padded with pad_id, `rows` examples
    at a time through `chunk_fn` -> the state a generation carries.
    `chunk_fn(ids (b, seq_len))` gives its caches (leaves (b, ...)), the
    logits at each example's last position (b, V), the pairs its held
    experts took (b,), their load (expert layers, held experts), the rows
    of per-token work run (blocks x block) and its own counts an example,
    {name: (b,)}. `extra_counts`: the batch's counts its `step` adds to."""
    ids = jnp.asarray(input_ids, jnp.int32)
    b, s = ids.shape
    rows = min(rows, b)
    if b % rows:
        rows = b
    caches, logits, held, load, ran, own = jax.lax.map(
        chunk_fn, ids.reshape(b // rows, rows, s))
    merge = lambda x: x.reshape(b, *x.shape[2:])  # noqa: E731
    load = jnp.sum(load, axis=0)
    lengths = jnp.sum((ids != pad_id).astype(jnp.int32), axis=-1)
    return {
        "caches": jax.tree_util.tree_map(merge, caches),
        "length": lengths,
        "logits": merge(logits),
        "token": jnp.full((b, 1), pad_id, jnp.int32),
        "finished": jnp.zeros((b,), jnp.bool_),
        "counts": {"prompt_tokens": lengths, "held_prefill": merge(held),
                   "held_decode": jnp.zeros((b,), jnp.int32),
                   "steps": jnp.zeros((b,), jnp.int32),
                   "max_load": jnp.max(load, initial=0),
                   "load_total": jnp.sum(load),
                   "prefill_rows": jnp.sum(ran),
                   "hit_decode": jnp.zeros((), jnp.int32),
                   **{name: merge(count).astype(jnp.int32)
                      for name, count in own.items()},
                   **{name: jnp.zeros((), jnp.int32)
                      for name in extra_counts}},
    }


def choose(state: dict, pad_id: int, eos_id: int):
    """The head of a step -> (token (B,), finished, position, owned): each
    example's next token from the state's logits (greedy; pad_id once
    finished), whether it has finished with it, the position it is fed
    at, and whether the row is a request's (a prompt of length 0 pads
    the batch: no expert and no state is for it)."""
    token = jnp.argmax(state["logits"], axis=-1).astype(jnp.int32)
    token = jnp.where(state["finished"], pad_id, token)
    finished = jnp.logical_or(state["finished"], token == eos_id)
    return (token, finished, state["length"],
            state["counts"]["prompt_tokens"] > 0)


def advance(state: dict, caches, logits: jax.Array, token: jax.Array,
            finished: jax.Array, *, held_decode=None, hit_decode=None,
            **added) -> dict:
    """The tail of a step -> the next state: the caches as the step left
    them, the logits of the token after `token`, one more step counted
    with its held pairs and hit experts (a model with no expert layer:
    none), and `added` onto the model's own counts of those names."""
    counts = dict(state["counts"])
    more = {"held_decode": held_decode, "hit_decode": hit_decode,
            "steps": 1, **added}
    for name, count in more.items():
        if count is not None:
            counts[name] = counts[name] + count
    return {"caches": caches, "length": state["length"] + 1,
            "logits": logits, "token": token[:, None],
            "finished": finished, "counts": counts}


# The expert layers' columns, one row an example: its prompt tokens, the
# (token, expert) pairs its prefill and its decode steps chose and those
# that fell on held experts; and of its BATCH, on every row: the fullest
# held expert's pairs and all held pairs of the prefill, the packed rows
# the prefill ran, and the (step, expert layer, hit expert) products the
# decode steps ran (0 where the pairs were sorted).
ROUTE_COLUMNS = ("prompt_tokens", "pairs_prefill", "held_prefill",
                 "pairs_decode", "held_decode", "max_load", "load_total",
                 "prefill_rows", "hit_decode")


def route_table(pairs_per_token: int):
    """The count table of a model with expert layers (`pairs_per_token`:
    top_k times their number), over the counts `prefill_by_chunks` and
    `advance(held_decode=, hit_decode=)` keep. The batch's rows and trips
    are shared out by a request's share of the batch's held pairs, the
    one count whose batch total a row carries, so that the requests of a
    batch add up to the batch's figure."""
    from min_tfs_client_tpu.servables.decode_signatures import CountTable

    return CountTable(
        output="route_counts", span="generate/route", section="route",
        columns=ROUTE_COLUMNS,
        derived={"pairs_prefill": ("prompt_tokens", pairs_per_token),
                 "pairs_decode": ("steps", pairs_per_token)},
        batch=("max_load", "load_total", "prefill_rows", "hit_decode"),
        uncounted=("max_load", "load_total"),
        shared={name: ("held_prefill", "load_total")
                for name in ("prefill_rows", "hit_decode")})
