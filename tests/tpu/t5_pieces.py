"""Device times of a T5 whole generation's decode loop and of its two
attention reads alone on the chip, at the widths of
perfbench/configs/t5-large.json (PERF.md section 5 quotes them). Not a
test and not part of the benchmark: run it on a machine with the chip,

    python tests/tpu/t5_pieces.py [--pieces decode,cross,self]
        [--out FILE] [--parent DIR]

and read chiprun_out/t5_pieces.json (or FILE). `decode`: the decode step
as `greedy_decode` runs it, a `lax.scan` of 16 steps with the caches
donated, from position 0 and from position 128 (where a step's
self-attention reads a second block), timed over 5 calls and captured
once for its device time by operation (a `while` spans its body's
operations: what the loop costs a step without what XLA hoists out of
it). `self`: a step's 24 self-attention reads alone at 32 rows, chained,
over a cache of 256 positions under a shared bias, scans of 16 steps from
positions 0, 128 and 240: microseconds a call from the capture and the
share of 819 GB/s by the rows a call needs (its positions' keys to the
16-row tile); with `--parent DIR` (a `git archive` checkout of the parent
commit) the tree's kernel against the parent's, to the bit, at every one
of those positions. `cross`: a step's 24
cross-attention reads alone, chained (each layer's output is the next
one's query) over 24 distinct K and V, 16 steps a call: as the parent
formulates them (`attention_reference` over rows split into heads: the
float32 multiply and reduce over all padded rows) and, where the tree
has it, `rows_flash_attention` at 128- and 256-row blocks over the
layers' K and V in one array each, and at 128 over a leaf a layer (which
XLA stages whole in its fast memory). Both on two
batches of 32: rows of the traffic's own lengths, and 21 such rows + 11
that pad the batch (length 0).

To set a parent against a change, run it from a `git archive` checkout
of each in ONE call, `--out <a file of its own>` (copy this script into
the parent's checkout; what a tree lacks is left out).
"""

import argparse
import importlib
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from min_tfs_client_tpu.models import layers as nn  # noqa: E402
from min_tfs_client_tpu.models import t5  # noqa: E402
from mimo_pieces import SCAN, ops_a_step  # noqa: E402  (beside this file)
from perfbench import children  # noqa: E402

attention = importlib.import_module("min_tfs_client_tpu.ops.attention")

BATCH, SEQ_LEN, MAX_DECODE_LEN = 32, 512, 256


def batches(grid) -> dict:
    """name -> lengths (32,): the traffic's own, and 21 of them + 11
    rows that pad the batch."""
    lengths = np.random.default_rng(0).permutation(grid)[:BATCH].astype(
        np.int32)
    padded = lengths.copy()
    padded[21:] = 0
    return {"32_real_rows": lengths, "21_real_11_padding_rows": padded}


def timed_and_captured(out: dict, name: str, steps, carried, *fixed) -> None:
    """`steps(carried, *fixed) -> carried`, SCAN steps a call: ms a step
    over 5 calls after one that compiles, then one call inside a
    profiler capture."""
    carried = jax.block_until_ready(steps(carried, *fixed))
    clock = time.perf_counter()
    for _ in range(5):
        carried = steps(carried, *fixed)
    jax.block_until_ready(carried)
    out[f"{name}_ms_a_step"] = (time.perf_counter() - clock) / 5 / SCAN * 1e3
    with tempfile.TemporaryDirectory() as capture:
        jax.profiler.start_trace(capture)
        jax.block_until_ready(steps(carried, *fixed))
        jax.profiler.stop_trace()
        out[f"{name}_ops"] = ops_a_step(capture)


def decode(out: dict, name: str, params, config, encoded, lengths,
           position: int = 0) -> None:
    """The loop of `greedy_decode`: SCAN steps from `position` on, the
    caches donated (the tree's own: a rows cache where it has one). A
    tree that projects the cross K and V before the loop gets them made
    outside the timed program, as its whole generation makes them once;
    the parent's loop projects `encoded` itself and XLA hoists that out
    of the `while` (its time a call is in the operations outside the
    `while`)."""
    caches = [{"self": nn.init_cache(BATCH, config.num_heads, MAX_DECODE_LEN,
                                     config.d_kv)}
              for _ in range(config.num_decoder_layers)]
    if hasattr(t5, "_project_cross"):
        source = jax.jit(t5._project_cross)(params, encoded)
        caches = nn.init_rows_cache(
            config.num_decoder_layers, BATCH, MAX_DECODE_LEN,
            config.num_heads * config.d_kv)

        def one(p, token, step, caches, source):
            return t5._decoder_step(p, config, token, step, caches, None,
                                    lengths, source)
    else:
        source = encoded

        def one(p, token, step, caches, source):
            return t5._decoder_step(p, config, token, step, caches, source,
                                    lengths)

    def steps(carried, p, source):
        def step_fn(carry, step):
            token, caches = carry
            logits, caches = one(p, token, step, caches, source)
            return (jnp.argmax(logits, -1).astype(jnp.int32)[:, None],
                    caches), None

        return jax.lax.scan(step_fn, carried,
                            position + jnp.arange(SCAN))[0]

    timed_and_captured(
        out, f"decode_{name}" + (f"_from_{position}" if position else ""),
        jax.jit(steps, donate_argnums=(0,)),
        (jnp.zeros((BATCH, 1), jnp.int32), caches), params, source)


def cross(out: dict, name: str, config, rows, lengths) -> None:
    """A step's cross reads alone: layer i's output is layer i + 1's
    query, SCAN steps a call. `rows`: {"key", "value"} of (24, B, S,
    H * D)."""
    h = config.num_heads
    layers = range(rows["key"].shape[0])
    q0 = jnp.ones((BATCH, 1, h * config.d_kv), jnp.bfloat16)

    def by_reference(q, rows, i, lengths):
        found = attention.attention_reference(
            nn.heads(q, h), nn.heads(rows["key"][i], h),
            nn.heads(rows["value"][i], h), lengths=lengths, scale=1.0)
        return nn.unheads(found)

    forms = {"reference": (by_reference, rows)}
    if hasattr(attention, "rows_flash_attention"):
        def by_kernel(q, rows, i, lengths, block, **layer):
            return attention.rows_flash_attention(
                q, rows["key"], rows["value"], lengths, num_heads=h,
                scale=1.0, block=block, **layer)

        for block in (128, 256):
            forms[f"rows_{block}"] = (
                lambda q, rows, i, lengths, block=block:
                by_kernel(q, rows, i, lengths, block, layer=i), rows)
        # a leaf of its own for each layer's K and V, as XLA's own
        # hoisting keeps them: what it stages of them is in the capture
        leaves = [{"key": rows["key"][i], "value": rows["value"][i]}
                  for i in layers]
        forms["rows_128_a_leaf_a_layer"] = (
            lambda q, leaves, i, lengths:
            by_kernel(q, leaves[i], i, lengths, 128), leaves)
        # the kernel against the parent's form on the chip's own numbers
        # (bfloat16 rows; the weights rounded to bfloat16 on both sides)
        one = jax.random.normal(jax.random.PRNGKey(4), q0.shape, q0.dtype)
        out[f"cross_max_abs_diff_rows_128_vs_reference_{name}"] = float(
            jnp.max(jnp.abs(
                forms["rows_128"][0](one, rows, 5, jnp.asarray(lengths))
                .astype(jnp.float32)
                - by_reference(one, rows, 5, jnp.asarray(lengths))
                .astype(jnp.float32))))
    for form, (read, held) in forms.items():
        def steps(q, held, lengths, read=read):
            def step_fn(q, _):
                for i in layers:
                    q = read(q, held, i, lengths)
                return q, None

            return jax.lax.scan(step_fn, q, None, length=SCAN)[0]

        timed_and_captured(out, f"cross_24_reads_{form}_{name}",
                           jax.jit(steps), q0, held, jnp.asarray(lengths))


SELF_POSITIONS = (0, 128, 240)


def self_reads(out: dict, config, parent: str | None) -> None:
    """A step's self reads alone: layer i's output is layer i + 1's
    query, SCAN steps a call from each of SELF_POSITIONS, every row of
    the batch real. The rows a call needs are its position's keys to the
    16-row tile, of K and of V."""
    h, f = config.num_heads, config.num_heads * config.d_kv
    layers = config.num_decoder_layers
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    rows = {name: jax.random.normal(
                key, (layers, BATCH, MAX_DECODE_LEN, f), jnp.bfloat16)
            for name, key in zip(("key", "value"), keys)}
    bias = jax.random.normal(keys[2], (1, h, 1, MAX_DECODE_LEN), jnp.float32)
    q0 = jax.random.normal(keys[3], (BATCH, 1, f), jnp.bfloat16)
    peak = json.loads((ROOT / "perfbench/peaks.json").read_text())[
        out["device"]]["hbm_bytes_per_s"]

    def read(kernel, q, rows, bias, i, position):
        return kernel(
            q, rows["key"], rows["value"],
            jnp.full((BATCH,), position + 1, jnp.int32), num_heads=h,
            scale=1.0, layer=i, bias=bias, q_start=position)

    def steps(q, rows, bias, first):
        def step_fn(q, position):
            for i in range(layers):
                q = read(attention.rows_flash_attention, q, rows, bias, i,
                         position)
            return q, None

        return jax.lax.scan(step_fn, q, first + jnp.arange(SCAN))[0]

    for first in SELF_POSITIONS:
        name = f"self_24_reads_from_{first}"
        timed_and_captured(out, name, jax.jit(steps), q0, rows, bias,
                           jnp.int32(first))
        us = (out[f"{name}_ops"]["ops"]["_rows_kernel"]["ms_a_step"]
              * 1e3 / layers)
        needed = sum(2 * BATCH * f * 2 * (-(-(first + i + 1) // 16) * 16)
                     for i in range(SCAN)) / SCAN
        out[f"{name}_us_a_call"] = us
        out[f"{name}_share_of_peak_bytes"] = needed / peak / (
            us * 1e-6)
    if parent is None:
        return
    spec = importlib.util.spec_from_file_location(
        "parent_attention",
        pathlib.Path(parent) / "min_tfs_client_tpu/ops/attention.py")
    before = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(before)
    both = jax.jit(lambda q, rows, bias, i, position: tuple(
        read(module.rows_flash_attention, q, rows, bias, i, position)
        for module in (attention, before)))
    differing = [
        first + i for first in SELF_POSITIONS for i in range(SCAN)
        if not np.array_equal(*map(np.asarray, both(
            q0, rows, bias, (first + i) % layers, jnp.int32(first + i))))]
    out["self_positions_where_the_kernel_differs_from_the_parents"] = (
        differing)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pieces", default="decode,cross,self")
    parser.add_argument("--out", default=str(
        ROOT / "chiprun_out/t5_pieces.json"))
    parser.add_argument("--parent", default=None)
    args = parser.parse_args()
    pieces = set(args.pieces.split(","))
    out = {"device": str(jax.devices()[0].device_kind)}
    config = t5.T5Config(**children.program_config_kwargs(json.loads(
        (ROOT / "perfbench/configs/t5-large.json").read_text())))
    grid = json.loads((ROOT / "perfbench/traffic/generate.json")
                      .read_text())["input_length_grid"]
    # bfloat16 leaves: what the loop of a whole generation reads, once
    # XLA has hoisted the float32 weights' cast out of it.
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16), t5.init_params(k, config)))(
        jax.random.PRNGKey(1))
    encoded = jax.random.normal(
        jax.random.PRNGKey(2), (BATCH, SEQ_LEN, config.d_model), jnp.bfloat16)
    rows = jax.jit(lambda p, e: {
        name: jnp.stack([nn.dense(layer["cross_attention"][name], e)
                         for layer in p["decoder"]["layers"]])
        for name in ("key", "value")})(params, encoded)
    for name, lengths in batches(grid).items():
        out[f"lengths_{name}"] = lengths.tolist()
        if "decode" in pieces:
            for position in (0, 128):
                decode(out, name, params, config, encoded,
                       jnp.asarray(lengths), position)
        if "cross" in pieces:
            cross(out, name, config, rows, lengths)
    if "self" in pieces and hasattr(attention, "rows_flash_attention"):
        self_reads(out, config, args.parent)
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
