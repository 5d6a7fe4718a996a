"""Device times of models/ling_hybrid.py's decode step, of its expert
layer alone and of its one-token delta-rule step alone on the chip, at
the widths of perfbench/configs/ling-3.0-flash.json (PERF.md section 5
and parallel/moe.py's header quote them). Not a test and not part of the
benchmark: run it on a machine with the chip,

    python tests/tpu/ling_pieces.py [--pieces decode,experts,step] [--out FILE]

and read chiprun_out/ling_pieces.json (or FILE). `decode`: the decode
step as the loop of a whole generation runs it, a `lax.scan` of 16 steps
with the state a real prefill left, donated, timed over 5 calls and
captured once for its device time by operation (a `while` spans its
body's operations), on 12, 20 and 32 real rows of the traffic's own
lengths, the rest rows that pad the batch (length 0). `experts`:
`held_experts_ffn` alone under the group-limited rule, 128 held of 512,
top 8, on 12, 20 and 32 valid rows of 32 in both forms (the walk over
hit experts and the sorted pairs): ms a call and the experts hit.
`step`: `kda.kda_step` alone, a step's 6 calls over 6 states of (32, 32,
128, 128) float32 donated and handed on, 16 steps a call, with 32, 20, 12
and 1 of the rows owned: ms a call, and the share of 819 GB/s the owned
rows' states (read and written once) come to; and the kernel's result
against `kda_step_reference` there.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from min_tfs_client_tpu.models import ling_hybrid as lh  # noqa: E402
from min_tfs_client_tpu.ops import kda  # noqa: E402
from min_tfs_client_tpu.parallel import moe  # noqa: E402
from mimo_pieces import SCAN  # noqa: E402  (beside this file)
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
    """One expert layer alone on a step's rows: the two forms of
    `held_experts_ffn` under the group-limited rule."""
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
    forms = {
        "walk": jax.jit(lambda p, x, valid: moe.held_experts_ffn(
            p, x, valid=valid, **common)),
        "sorted": jax.jit(lambda p, x, valid: moe.held_experts_ffn(
            p, x, valid=valid, rows=jnp.asarray(BATCH), **common))}
    for real in REAL:
        valid = jnp.arange(BATCH) < real
        for name, form in forms.items():
            y, routed = jax.block_until_ready(form(params, x, valid))
            clock = time.perf_counter()
            for _ in range(50):
                y, routed = form(params, x, valid)
            jax.block_until_ready(y)
            out[f"experts_{name}_{real}_rows_ms"] = (
                time.perf_counter() - clock) / 50 * 1e3
            out[f"experts_{real}_rows_hit"] = int(
                jnp.sum(routed.load > 0))
            out[f"experts_{real}_rows_held_pairs"] = int(
                jnp.sum(routed.held))


def step(out: dict, pc) -> None:
    """A decode step's state steps alone: one `kda.kda_step` a KDA layer,
    each over its own state, the token's inputs the same every step (k of
    unit length, beta under 1: the states settle, nothing overflows)."""
    layers = pc.layer_types.count("kda")
    h, d = pc.num_heads, pc.head_dim
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    token = (unit(jax.random.normal(k[0], (BATCH, h, d))) * d ** -0.5,
             unit(jax.random.normal(k[1], (BATCH, h, d))),
             jax.random.normal(k[2], (BATCH, h, d)),
             -5.0 * jax.nn.sigmoid(jax.random.normal(k[3], (BATCH, h, d)) - 3),
             jax.nn.sigmoid(jax.random.normal(k[4], (BATCH, h))))
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
    if "decode" in pieces:
        params = jax.jit(lambda k: lh.init_params(k, pc))(
            jax.random.PRNGKey(1))
        grid = json.loads((ROOT / "perfbench/traffic/long-answers.json")
                          .read_text())["input_length_grid"]
        prefill = jax.jit(lambda p, ids: lh.prefill(
            p, pc, ids, max_decode_len=MAX_DECODE_LEN))
        for name, ids in prompts(grid, pc.vocab_size).items():
            clock = time.perf_counter()
            state = jax.block_until_ready(prefill(params, ids))
            out[f"prefill_{name}_first_call_s"] = time.perf_counter() - clock
            clock = time.perf_counter()
            state = jax.block_until_ready(prefill(params, ids))
            out[f"prefill_{name}_ms"] = (time.perf_counter() - clock) * 1e3
            decode(out, name, params, pc, state)
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
