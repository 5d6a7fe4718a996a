"""Device times of models/mimo.py's pieces alone on the chip, at the
widths of perfbench/configs/mimo-v2.5.json (PERF.md section 5 quotes
them). Not a test and not part of the benchmark: run it on a machine
with the chip,

    python tests/tpu/mimo_pieces.py [--pieces experts,flash,prefill,decode]
                                    [--row-block N] [--out FILE]

and read chiprun_out/mimo_pieces.json (or FILE): the expert layer in
both its forms (the walk over hit experts beside the sorted pairs) at
32, 64, 128 and 256 rows and at the cell's mean batch (13 distinct rows
and 19 identical ones that pad it), against a dense product over the
held experts, and at the prefill shape (16,384 rows); `_flash_kernel`
in a full and a window layer on full and mixed lengths; the prefill
alone on three batches of 32 (the traffic's mixed lengths; 20 such rows
and 12 rows that pad the batch; 32 prompts of 2,048, where there is
nothing to pack); and the decode step as the loop of a whole generation
runs it: a `lax.scan` of 16 steps with the state donated, timed over 16
and captured once for its device time by operation (with what each
`copy` moves), on 32 real rows and on 13 real rows + 19 that pad.

To set a parent against a change, run it from a `git archive` checkout
of each in ONE call, `--pieces ... --out <a file of its own>` (copy this
script into the parent's checkout; what a tree lacks is left out:
`--row-block` and the walk over hit experts are the change's alone).
"""

import argparse
import json
import pathlib
import re
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from min_tfs_client_tpu.models import mimo  # noqa: E402
from min_tfs_client_tpu.ops.attention import flash_attention  # noqa: E402
from min_tfs_client_tpu.parallel import moe  # noqa: E402
from min_tfs_client_tpu.parallel.moe import (  # noqa: E402
    HeldExperts,
    held_experts_ffn,
    sigmoid_top_k,
)
from perfbench import children  # noqa: E402
from perfbench.trace_reduce import OPS_LINE, base_name  # noqa: E402

D, F, HELD, ROUTER, TOP_K = 4096, 2048, 16, 256, 8


def timed(fn, *args, n=5) -> float:
    """Milliseconds a call, after one call that compiles."""
    jax.block_until_ready(fn(*args))
    clock = time.perf_counter()
    for _ in range(n):
        found = fn(*args)
    jax.block_until_ready(found)
    return (time.perf_counter() - clock) / n * 1e3


def dense_over_held(p: HeldExperts, x):
    """Every held expert on every row, weighted after: what
    `ragged_dot` is measured against."""
    experts, weights = sigmoid_top_k(x, p.router, p.bias, TOP_K)
    w = jnp.sum(jnp.where(experts[:, :, None] == jnp.arange(HELD)[None, None],
                          weights[:, :, None], 0.0), 1)        # (T, HELD)
    h = jnp.einsum("td,edf->etf", x.astype(jnp.bfloat16), p.w_in,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h[..., :F]) * h[..., F:]).astype(jnp.bfloat16)
    y = jnp.einsum("etf,efd->etd", h, p.w_out,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, w)


def prefill_batches(grid, vocab_size: int) -> dict:
    """name -> ids (32, 2048): rows of the traffic's own lengths."""
    rng = np.random.default_rng(0)
    mixed = np.zeros((32, 2048), np.int32)
    for row, n in enumerate(rng.permutation(grid)[:32]):
        mixed[row, :n] = rng.integers(2, vocab_size, n)
    padded = mixed.copy()
    padded[20:] = 0
    return {"mixed": mixed, "20_real_12_padding_rows": padded,
            "full_length": rng.integers(2, vocab_size, (32, 2048),
                                        dtype=np.int32)}


def experts(out: dict, ks) -> None:
    p = HeldExperts(
        router=jax.random.normal(ks[0], (D, ROUTER), jnp.float32) / 64,
        bias=jnp.zeros((ROUTER,), jnp.float32),
        w_in=(jax.random.normal(ks[1], (HELD, D, 2 * F), jnp.float32)
              / 64).astype(jnp.bfloat16),
        w_out=(jax.random.normal(ks[2], (HELD, F, D), jnp.float32)
               / 45).astype(jnp.bfloat16))

    def layer():
        """A new jit: the form is chosen when the layer is traced."""
        return jax.jit(lambda p, x, valid: held_experts_ffn(
            p, x, top_k=TOP_K, experts_held=HELD, expert_offset=0,
            valid=valid))

    x256 = jax.random.normal(ks[3], (256, D), jnp.float32)
    # the cell's mean batch: 13 requests' rows and 19 rows that pad it
    cell = jnp.concatenate([x256[:13], jnp.tile(x256[13:14], (19, 1))])
    owned = jnp.arange(32) < 13
    # a tree without the walk has the sorted form alone and reads no constant
    crossing = getattr(moe, "DECODE_ROWS", None)
    forms = {"sorted": 0, **({} if crossing is None else {"hit": 1 << 30})}
    for form, up_to in forms.items():
        moe.DECODE_ROWS = up_to
        for t in (32, 64, 128, 256):
            x, valid, ffn = x256[:t], jnp.ones((t,), bool), layer()
            out[f"experts_{t}rows_{form}_ms"] = timed(ffn, p, x, valid, n=20)
            out[f"experts_{t}rows_hit_experts"] = int(jnp.sum(
                ffn(p, x, valid)[1].load > 0))
        ffn = layer()
        for name, valid in (("all_routed", jnp.ones((32,), bool)),
                            ("padding_routed_nowhere", owned)):
            out[f"experts_cell_batch_{name}_{form}_ms"] = timed(
                ffn, p, cell, valid, n=20)
            out[f"experts_cell_batch_{name}_hit_experts"] = int(jnp.sum(
                ffn(p, cell, valid)[1].load > 0))
    moe.DECODE_ROWS = crossing
    out["experts_decode_32rows_dense_over_held_ms"] = timed(
        jax.jit(dense_over_held), p, x256[:32], n=20)
    out["expert_bytes_floor_ms"] = 3 * D * F * 2 / 819e9 * 1e3
    ffn = layer()
    xp = jax.random.normal(ks[4], (16384, D), jnp.float32)
    out["experts_prefill_16384rows_39pct_valid_ms"] = timed(
        ffn, p, xp, (jnp.arange(16384) % 2048) < 805, n=3)
    out["experts_prefill_all_valid_ms"] = timed(
        ffn, p, xp, jnp.ones((16384,), bool), n=3)


def flash(out: dict, ks) -> None:
    for name, kv, window in (("full", 4, None), ("window", 8, 128)):
        q = jax.random.normal(ks[5], (8, 64, 2048, 192), jnp.bfloat16)
        k = jax.random.normal(ks[6], (8, kv, 2048, 192), jnp.bfloat16)
        v = jax.random.normal(ks[7], (8, kv, 2048, 128), jnp.bfloat16)
        sink = jnp.zeros((64,), jnp.float32) if window else None
        fn = jax.jit(lambda q, k, v, n, s=sink, w=window: flash_attention(
            q, k, v, causal=True, lengths=n, causal_offset=0, window=w,
            sink=s, queries_ragged=True))
        for label, lengths in (
                ("full_lengths", [2048] * 8),
                ("mixed", [93, 231, 366, 521, 692, 930, 1332, 2048])):
            out[f"flash_{name}_{label}_ms"] = timed(
                fn, q, k, v, jnp.asarray(lengths, jnp.int32))
        del q, k, v


SCAN = 16      # decode steps a timed call runs


def decode(out: dict, name: str, params, pc, state) -> None:
    """One decode step alone (its state NOT donated: what this script
    read before PR 41), then as the loop runs it: a scan of SCAN steps,
    the state donated and handed on from call to call (7 calls = 112 of
    the 128 positions the caches have room for), and the last call
    inside a profiler capture: device ms a step by operation."""
    step = jax.jit(lambda p, s: mimo.step(p, pc, s)[0])
    out[f"decode_step_undonated_{name}_ms"] = timed(step, params, state,
                                                   n=20)
    steps = jax.jit(
        lambda p, s: jax.lax.scan(lambda s, _: (mimo.step(p, pc, s)[0], None),
                                  s, None, length=SCAN)[0],
        donate_argnums=(1,))
    state = jax.block_until_ready(steps(params, state))
    clock = time.perf_counter()
    for _ in range(5):
        state = steps(params, state)
    jax.block_until_ready(state)
    out[f"decode_step_in_scan_{name}_ms"] = (
        (time.perf_counter() - clock) / 5 / SCAN * 1e3)
    with tempfile.TemporaryDirectory() as capture:
        jax.profiler.start_trace(capture)
        state = jax.block_until_ready(steps(params, state))
        jax.profiler.stop_trace()
        out[f"decode_step_ops_{name}"] = ops_a_step(capture)
    counts = state["counts"]
    if "hit_decode" in counts:
        out[f"decode_hit_experts_a_layer_{name}"] = float(
            counts["hit_decode"]) / (int(counts["steps"][0])
                                     * sum(pc.moe_pattern))


def ops_a_step(capture: str, most: int = 40, steps: int = SCAN) -> dict:
    """The capture's device operations by name (XLA's instances of one
    operation summed; a `copy*` or `slice*` by the shape it moves too,
    as the event's HLO text gives it): ms and calls a decode step, of
    the `steps` the captured call ran. A `while` spans its body's
    operations: it is listed and not summed."""
    (path,) = pathlib.Path(capture).rglob("*.xplane.pb")
    found: dict = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if "/device:" not in plane.name:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for event in line.events:
                name = base_name(event.name)
                if name.startswith(("copy", "slice")):
                    moved = re.search(r"\w+\[[\d,]*\]",
                                      event.name.partition(" = ")[2])
                    name += " " + (moved.group(0) if moved else "?")
                entry = found.setdefault(name, {"ms_a_step": 0.0,
                                                "calls_a_step": 0.0})
                entry["ms_a_step"] += event.duration_ns / 1e6 / steps
                entry["calls_a_step"] += 1 / steps
    listed = sorted(found.items(), key=lambda kv: -kv[1]["ms_a_step"])
    return {"sum_without_while_ms": sum(
                e["ms_a_step"] for n, e in listed if not n.startswith("while")),
            "ops": dict(listed[:most])}


def latent_step_faults(ops: dict, batch: int, positions: int) -> list:
    """What a step's by-operation table (`ops_a_step`) may not hold once
    the latent caches are `_latent_step_kernel`'s: a copy, slice or
    update of an array shaped like a cache, (batch, [1,] positions, .);
    and the kernel itself must be among the operations."""
    cache = re.compile(rf"\[{batch},(1,)?{positions},\d+\]")
    faults = [name for name in ops["ops"]
              if name.startswith(("copy", "slice", "dynamic-update-slice"))
              and cache.search(name)]
    if "_latent_step_kernel" not in ops["ops"]:
        faults.append("no _latent_step_kernel among the step's operations")
    return faults


def fail_on_latent_step_faults(out: dict) -> None:
    """Ends a pieces script with an error where any `*_faults` entry of
    what it measured (`latent_step_faults`) names one."""
    faults = {name: found for name, found in out.items()
              if name.endswith("_faults") and found}
    if faults:
        sys.exit(f"a step still moves a latent cache: {faults}")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pieces", default="experts,flash,prefill,decode")
    parser.add_argument("--row-block", type=int, default=None)
    parser.add_argument("--out", default=str(
        ROOT / "chiprun_out/mimo_pieces.json"))
    args = parser.parse_args()
    pieces = set(args.pieces.split(","))
    out = {"device": str(jax.devices()[0].device_kind)}
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    if "experts" in pieces:
        experts(out, ks)
    if "flash" in pieces:
        flash(out, ks)
    if pieces & {"prefill", "decode"}:
        config = json.loads(
            (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
        pc = mimo.MimoConfig(**children.program_config_kwargs(config))
        params = jax.jit(lambda k: mimo.init_params(k, pc))(
            jax.random.PRNGKey(1))
        grid = json.loads((ROOT / "perfbench/traffic/mixed-generate.json")
                          .read_text())["input_length_grid"]
        batches = prefill_batches(grid, pc.vocab_size)
        block = ({} if args.row_block is None
                 else {"row_block": args.row_block})
        prefill = jax.jit(lambda p, ids: mimo.prefill(
            p, pc, ids, max_decode_len=128, **block))
        if "prefill" in pieces:
            for name, ids in batches.items():
                out[f"prefill_32x2048_{name}_ms"] = timed(
                    prefill, params, ids, n=3)
        if "decode" in pieces:
            padded = batches["mixed"].copy()
            padded[13:] = 0
            for name, ids in (("32_real_rows", batches["mixed"]),
                              ("13_real_19_padding_rows", padded)):
                decode(out, name, params, pc, prefill(params, ids))
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
