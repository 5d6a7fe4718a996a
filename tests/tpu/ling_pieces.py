"""Device times of models/ling_hybrid.py's decode step, of its expert
layer alone and of its one-token delta-rule step alone on the chip, at
the widths of perfbench/configs/ling-3.0-flash.json (PERF.md section 5
and parallel/moe.py's header quote them). Not a test and not part of the
benchmark: run it on a machine with the chip,

    python tests/tpu/ling_pieces.py [--pieces decode,experts,step,prefill]
                                    [--out FILE]

and read chiprun_out/ling_pieces.json (or FILE). `decode`: the decode
step as the loop of a whole generation runs it, a `lax.scan` of 16 steps
with the state a real prefill left, donated, timed over 5 calls and
captured once for its device time by operation (a `while` spans its
body's operations), on 12, 20 and 32 real rows of the traffic's own
lengths, the rest rows that pad the batch (length 0); the table names
`_latent_step_kernel`, and the script FAILS where a step still copies,
slices or updates an array shaped like the latent cache
(`mimo_pieces.latent_step_faults`). `experts`:
`held_experts_ffn` alone under the group-limited rule, 128 held of 512,
top 8, on 12, 20 and 32 valid rows of 32: the walk over the hit experts
in both its forms side by side (`walk`, as the tree runs it: the kernel
`_expert_walk_kernel` where the gate admits the shapes; `walk_loop`, the
`fori_loop` of XLA products with the gate held shut by this script) and
the sorted pairs: ms a call (50 calls from the host, as PR 51 read it:
under about 0.56 ms it is the host's dispatch that is timed; and
`_in_a_scan`, 16 applications a call, each added onto the one before),
the experts hit, us a hit expert and the share of 819 GB/s the hit
experts' 11.8 MB each come to; and the kernel's result against the
loop's there. `prefill` (run by no other
piece's default): the prefill on 12, 20 and 32 real rows, ms a call and
one call captured by operation; and the chunked delta rule alone on each
of the prefill's groups of 4 examples, x 6 layers, in both its forms
side by side (`kda.kda_chunked`, plain jnp run to a group's longest
example, and `kda.kda_prefill`, as the tree's prefill runs it: the kernel
`_kda_chunk_kernel` over each example's own chunks): ms a group, us a
(chunk, head), what the delta rule takes of a prefill, and each form's
largest difference from `kda_reference` on the chip.
`step`: `kda.kda_step` alone, a step's 6 calls over 6 states of (32, 32,
128, 128) float32 donated and handed on, 16 steps a call, with 32, 20, 12
and 1 of the rows owned: ms a call, and the share of 819 GB/s the owned
rows' states (read and written once) come to; and the kernel's result
against `kda_step_reference` there.
"""

import argparse
import functools
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

from min_tfs_client_tpu.models import ling_hybrid as lh  # noqa: E402
from min_tfs_client_tpu.ops import kda  # noqa: E402
from min_tfs_client_tpu.parallel import moe  # noqa: E402
from mimo_pieces import (  # noqa: E402  (beside this file)
    SCAN,
    fail_on_latent_step_faults,
    latent_step_faults,
    ops_a_step,
)
from perfbench import children  # noqa: E402
from t5_pieces import timed_and_captured  # noqa: E402  (beside this file)

BATCH, SEQ_LEN, MAX_DECODE_LEN = 32, 2048, 256
REAL = (12, 20, 32)
HBM_BYTES_PER_S = 819e9


def prompts(grid, vocab_size: int) -> dict:
    """name -> ids (32, 2048): `real` rows of the traffic's own lengths,
    the rest rows that pad the batch."""
    rng = np.random.default_rng(0)
    mixed = np.zeros((BATCH, SEQ_LEN), np.int32)
    for row, n in enumerate(rng.permutation(grid)[:BATCH]):
        mixed[row, :n] = rng.integers(2, vocab_size, n)
    out = {}
    for real in REAL:
        ids = mixed.copy()
        ids[real:] = 0
        out[f"{real}_real_rows"] = ids
    return out


def decode(out: dict, name: str, params, pc, state) -> None:
    steps = jax.jit(
        lambda s, p: jax.lax.scan(lambda s, _: (lh.step(p, pc, s)[0], None),
                                  s, None, length=SCAN)[0],
        donate_argnums=(0,))
    timed_and_captured(out, f"decode_{name}", steps, state, params)


def experts(out: dict, pc) -> None:
    """One expert layer alone on a step's rows: `held_experts_ffn`
    under the group-limited rule, the walk as kernel and as loop and the
    sorted pairs."""
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    d, f, held = pc.hidden_size, pc.moe_intermediate_size, pc.experts_held
    params = moe.HeldExperts(
        jax.random.normal(k[0], (d, pc.num_experts)) * d ** -0.5,
        jax.random.normal(k[1], (pc.num_experts,)) * 0.02,
        (jax.random.normal(k[2], (held, d, 2 * f)) * d ** -0.5).astype(
            jnp.bfloat16),
        (jax.random.normal(k[3], (held, f, d)) * f ** -0.5).astype(
            jnp.bfloat16))
    x = jax.random.normal(k[4], (BATCH, d))
    common = dict(top_k=pc.top_k, experts_held=held, expert_offset=0,
                  routing="sigmoid_grouped", n_group=pc.n_group,
                  topk_group=pc.topk_group, scale=pc.routed_scaling_factor)

    def ffn(p, x, valid, **more):
        return moe.held_experts_ffn(p, x, valid=valid, **common, **more)

    def gate_shut(p, x, valid, **more):
        """The walk as the loop: traced with the kernel's gate answering
        no (a tree without the kernel has no gate, and is the loop)."""
        gate = getattr(moe, "_walk_kernel_applies", None)
        moe._walk_kernel_applies = lambda *_: False
        try:
            return ffn(p, x, valid, **more)
        finally:
            if gate is None:
                del moe._walk_kernel_applies
            else:
                moe._walk_kernel_applies = gate

    plain = {"walk": ffn, "walk_loop": gate_shut,
             "sorted": functools.partial(ffn, rows=jnp.asarray(BATCH))}
    forms = {name: jax.jit(form) for name, form in plain.items()}
    # SCAN applications a call, each added onto the one before (so that
    # none can be left out), the device never waiting for the host; what
    # does not follow from the carried rows (the router) may be computed
    # once a call
    scans = {name: jax.jit(
        lambda y, p, x, valid, form=form: jax.lax.scan(
            lambda y, _: (form(p, x, valid, onto=y)[0], None), y, None,
            length=SCAN)[0], donate_argnums=(0,))
        for name, form in plain.items()}
    out["experts_walk_is_the_kernel"] = "_expert_walk_kernel" in forms[
        "walk"].lower(params, x, jnp.arange(BATCH) < BATCH).as_text()
    expert_bytes = 3 * d * f * params.w_in.dtype.itemsize
    for real in REAL:
        valid = jnp.arange(BATCH) < real
        found = {}
        for name, form in forms.items():
            y, routed = jax.block_until_ready(form(params, x, valid))
            found[name] = y
            clock = time.perf_counter()
            for _ in range(50):
                y, routed = form(params, x, valid)
            jax.block_until_ready(y)
            from_the_host = (time.perf_counter() - clock) / 50 * 1e3
            hit = int(jnp.sum(routed.load > 0))
            carried = jax.block_until_ready(
                scans[name](jnp.zeros_like(y), params, x, valid))
            scan_clock = time.perf_counter()
            for _ in range(5):
                carried = scans[name](carried, params, x, valid)
            jax.block_until_ready(carried)
            for how, ms in (
                    ("", from_the_host),
                    ("_in_a_scan", (time.perf_counter() - scan_clock)
                     / 5 / SCAN * 1e3)):
                out[f"experts_{name}_{real}_rows{how}_ms"] = ms
                out[f"experts_{name}_{real}_rows{how}_us_a_hit_expert"] = (
                    ms * 1e3 / hit)
                out[f"experts_{name}_{real}_rows{how}_share_of_hbm_peak"] = (
                    hit * expert_bytes / HBM_BYTES_PER_S / (ms / 1e3))
            out[f"experts_{real}_rows_hit"] = hit
            out[f"experts_{real}_rows_held_pairs"] = int(
                jnp.sum(routed.held))
        out[f"experts_{real}_rows_walk_vs_loop_max_abs_diff"] = float(
            jnp.max(jnp.abs(found["walk"] - found["walk_loop"])))
        out[f"experts_{real}_rows_max_abs"] = float(
            jnp.max(jnp.abs(found["walk_loop"])))


def delta_rule_inputs(k, shape) -> tuple:
    """(q, k, v, g, beta) of `shape` (..., heads, d): k of unit length,
    q of unit length times d ** -0.5, g under 0, beta under 1."""
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(k[0], shape)) * shape[-1] ** -0.5,
            unit(jax.random.normal(k[1], shape)),
            jax.random.normal(k[2], shape),
            -5.0 * jax.nn.sigmoid(jax.random.normal(k[3], shape) - 3),
            jax.nn.sigmoid(jax.random.normal(k[4], shape[:-1])))


def step(out: dict, pc) -> None:
    """A decode step's state steps alone: one `kda.kda_step` a KDA layer,
    each over its own state, the token's inputs the same every step (k of
    unit length, beta under 1: the states settle, nothing overflows)."""
    layers = pc.layer_types.count("kda")
    h, d = pc.num_heads, pc.head_dim
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    token = delta_rule_inputs(k, (BATCH, h, d))
    scattered = np.zeros((BATCH,), bool)
    scattered[np.random.default_rng(1).permutation(BATCH)[:20]] = True
    masks = {f"{rows}_owned": np.arange(BATCH) < rows
             for rows in (32, 20, 12, 1)}
    masks["20_owned_scattered"] = scattered

    # the kernel against the plain form on the chip's own numbers
    state = jax.random.normal(k[0], (BATCH, h, d, d), jnp.float32)
    want, want_o = kda.kda_step_reference(state, *token, scattered)
    got, got_o = jax.jit(kda.kda_step)(state, *token, scattered)
    out["step_kernel_vs_reference_max_abs_diff"] = {
        "state": float(jnp.max(jnp.abs(got - want))),
        "o": float(jnp.max(jnp.abs(got_o - want_o)))}
    out["step_kernel_leaves_other_rows_as_they_were"] = bool(
        jnp.array_equal(got[~scattered], state[~scattered])
        and not jnp.any(got_o[~scattered]))
    del state, want, got

    def steps(carried, token, owned):
        def step_fn(carry, _):
            states, o = carry
            for i, state in enumerate(states):
                states[i], found = kda.kda_step(state, *token, owned=owned)
                o = o + found
            return (states, o), None

        return jax.lax.scan(step_fn, carried, None, length=SCAN)[0]

    run = jax.jit(steps, donate_argnums=(0,))
    for name, owned in masks.items():
        carried = ([jnp.ones((BATCH, h, d, d), jnp.float32)
                    for _ in range(layers)],
                   jnp.zeros((BATCH, h, d), jnp.float32))
        timed_and_captured(out, f"step_{name}", run, carried, token,
                           jnp.asarray(owned))
        call_ms = out[f"step_{name}_ms_a_step"] / layers
        moved = 2 * int(owned.sum()) * h * d * d * 4
        out[f"step_{name}_ms_a_call"] = call_ms
        out[f"step_{name}_owned_states_share_of_hbm_peak"] = (
            moved / HBM_BYTES_PER_S / (call_ms / 1e3))


def kda_alone(out: dict, pc, ids) -> None:
    """The chunked delta rule alone on each group of `prefill_rows`
    examples as the prefill meets them (the 32 prompts' own lengths;
    inputs as `step`'s; g handed in and o handed back as rows of every
    head's channels, as `ling_hybrid` has them): `kda.kda_chunked`, and
    beside it on the same groups `kda.kda_prefill` (what the prefill
    calls: `_kda_chunk_kernel` where its gate admits the shapes; left out
    on a tree without it), ms a group and us a (chunk, head) of the
    chunks each form runs; a
    prefill of `real` rows runs its first groups, once a KDA layer. And
    on the first group the largest difference of each form's output (the
    real rows) and final state from `kda_reference`'s, and of the two
    forms from each other."""
    rows, h, d, c = pc.prefill_rows, pc.num_heads, pc.head_dim, pc.kda_chunk
    token = delta_rule_inputs(jax.random.split(jax.random.PRNGKey(7), 5),
                              (rows, SEQ_LEN, h, d))

    def as_the_prefill_calls_it(form):
        # g comes from, and o goes to, rows of every head's channels
        def call(q, k, v, g, beta, lengths):
            o, state, ran = form(q, k, v, g.reshape(q.shape), beta, lengths,
                                 chunk=c)
            return o.reshape(g.shape), state, ran
        return jax.jit(call)

    forms = {name: as_the_prefill_calls_it(getattr(kda, name))
             for name in ("kda_chunked", "kda_prefill") if hasattr(kda, name)}
    token = (*token[:3], token[3].reshape(rows, SEQ_LEN, h * d), token[4])
    lengths = np.sum(ids > 0, axis=1).reshape(-1, rows)
    chunks = -(-lengths // c)
    ran = {"kda_chunked": rows * chunks.max(1), "kda_prefill": chunks.sum(1)}
    found = {}
    for name, run in forms.items():
        groups = []
        for group in lengths:
            group = jnp.asarray(group, jnp.int32)
            got = jax.block_until_ready(run(*token, group))
            found.setdefault(name, got)
            clock = time.perf_counter()
            for _ in range(3):
                got = run(*token, group)
            jax.block_until_ready(got)
            groups.append((time.perf_counter() - clock) / 3 * 1e3)
        out[f"prefill_{name}_alone_ms_a_group"] = groups
        out[f"prefill_{name}_alone_us_a_chunk_head"] = (
            sum(groups) * 1e3 / (int(ran[name].sum()) * h))
        for real in REAL:
            out[f"prefill_{real}_real_rows_{name}_alone_ms"] = (
                pc.layer_types.count("kda") * sum(groups[:-(-real // rows)]))
    out["prefill_group_longest_example"] = lengths.max(1).tolist()
    out["prefill_group_own_chunks_of_run_to_the_longest"] = (
        chunks.sum(1) / (rows * chunks.max(1))).tolist()
    first = jnp.asarray(lengths[0], jnp.int32)
    real = (jnp.arange(SEQ_LEN)[None, :] < first[:, None])[..., None]
    found["kda_reference"] = as_the_prefill_calls_it(
        lambda *a, chunk: (*kda.kda_reference(*a), None))(*token, first)
    for a, b in (("kda_chunked", "kda_reference"),
                 ("kda_prefill", "kda_reference"),
                 ("kda_prefill", "kda_chunked")):
        if a in found:
            out[f"prefill_{a}_vs_{b}_max_abs_diff"] = {
                "o": float(jnp.max(jnp.abs(
                    jnp.where(real, found[a][0] - found[b][0], 0.0)))),
                "state": float(jnp.max(jnp.abs(found[a][1] - found[b][1])))}
    out["prefill_kda_reference_max_abs"] = {
        "o": float(jnp.max(jnp.abs(jnp.where(
            real, found["kda_reference"][0], 0.0)))),
        "state": float(jnp.max(jnp.abs(found["kda_reference"][1])))}
    if "kda_prefill" in forms:
        out["prefill_kda_prefill_is_the_kernel"] = (
            "_kda_chunk_kernel" in forms["kda_prefill"].lower(
                *token, first).as_text())


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pieces", default="decode,experts,step")
    parser.add_argument("--out", default=str(
        ROOT / "chiprun_out/ling_pieces.json"))
    args = parser.parse_args()
    pieces = set(args.pieces.split(","))
    out = {"device": str(jax.devices()[0].device_kind)}
    config = json.loads(
        (ROOT / "perfbench/configs/ling-3.0-flash.json").read_text())
    pc = lh.LingHybridConfig(**children.program_config_kwargs(config))
    if "step" in pieces:
        step(out, pc)
    if "experts" in pieces:
        experts(out, pc)
    if pieces & {"decode", "prefill"}:
        params = jax.jit(lambda k: lh.init_params(k, pc))(
            jax.random.PRNGKey(1))
        grid = json.loads((ROOT / "perfbench/traffic/long-answers.json")
                          .read_text())["input_length_grid"]
        prefill = jax.jit(lambda p, ids: lh.prefill(
            p, pc, ids, max_decode_len=MAX_DECODE_LEN))
        given = prompts(grid, pc.vocab_size)
        if "prefill" in pieces:
            kda_alone(out, pc, given[f"{BATCH}_real_rows"])
        for name, ids in given.items():
            clock = time.perf_counter()
            state = jax.block_until_ready(prefill(params, ids))
            out[f"prefill_{name}_first_call_s"] = time.perf_counter() - clock
            clock = time.perf_counter()
            state = jax.block_until_ready(prefill(params, ids))
            out[f"prefill_{name}_ms"] = (time.perf_counter() - clock) * 1e3
            if "prefill" in pieces:
                with tempfile.TemporaryDirectory() as capture:
                    jax.profiler.start_trace(capture)
                    state = jax.block_until_ready(prefill(params, ids))
                    jax.profiler.stop_trace()
                    out[f"prefill_{name}_ops"] = ops_a_step(
                        capture, most=30, steps=1)
            if "decode" in pieces:
                decode(out, name, params, pc, state)
                out[f"decode_{name}_faults"] = latent_step_faults(
                    out[f"decode_{name}_ops"], BATCH,
                    SEQ_LEN + MAX_DECODE_LEN)
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    fail_on_latent_step_faults(out)


if __name__ == "__main__":
    main()
