"""Device times of models/granite_hybrid.py's decode step and of its
one-token state step alone on the chip, at the widths of
perfbench/configs/granite-4.0-h-small.json (PERF.md section 5 quotes
them). Not a test and not part of the benchmark: run it on a machine
with the chip,

    python tests/tpu/granite_pieces.py [--pieces decode,step] [--out FILE]

and read chiprun_out/granite_pieces.json (or FILE). `decode`: the decode
step as the loop of a whole generation runs it, a `lax.scan` of 16 steps
with the state a real prefill left, donated, timed over 5 calls and
captured once for its device time by operation (a `while` spans its
body's operations), on 32 real rows of the traffic's own lengths and on
19 such rows + 13 that pad the batch (length 0): the cell's mean batch.
`step`: `ssm.ssm_step` alone, a step's 9 calls over 9 states of (32,
128, 8,192) float32 donated and handed on, 16 steps a call, with 32, 19
(first rows, and scattered), 12 and 1 of the rows owned: ms a call, and
the share of 819 GB/s the owned rows' states (read and written once)
come to; and the kernel's result against `ssm_step_reference` there.

To set a parent against a change, run it from a `git archive` checkout
of each in ONE call, `--out <a file of its own>` (copy this script into
the parent's checkout; a tree whose step takes no `owned` moves every
row whatever the batch holds, and is timed so).
"""

import argparse
import inspect
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from min_tfs_client_tpu.models import granite_hybrid as gh  # noqa: E402
from min_tfs_client_tpu.ops import ssm  # noqa: E402
from mimo_pieces import SCAN  # noqa: E402  (beside this file)
from perfbench import children  # noqa: E402
from t5_pieces import timed_and_captured  # noqa: E402  (beside this file)

BATCH, SEQ_LEN, MAX_DECODE_LEN, REAL = 32, 2048, 128, 19
HBM_BYTES_PER_S = 819e9
TAKES_OWNED = "owned" in inspect.signature(ssm.ssm_step).parameters


def prompts(grid, vocab_size: int) -> dict:
    """name -> ids (32, 2048): rows of the traffic's own lengths, and
    19 of them + 13 rows that pad the batch."""
    rng = np.random.default_rng(0)
    mixed = np.zeros((BATCH, SEQ_LEN), np.int32)
    for row, n in enumerate(rng.permutation(grid)[:BATCH]):
        mixed[row, :n] = rng.integers(2, vocab_size, n)
    padded = mixed.copy()
    padded[REAL:] = 0
    return {"32_real_rows": mixed,
            f"{REAL}_real_{BATCH - REAL}_padding_rows": padded}


def decode(out: dict, name: str, params, pc, state) -> None:
    steps = jax.jit(
        lambda s, p: jax.lax.scan(lambda s, _: (gh.step(p, pc, s)[0], None),
                                  s, None, length=SCAN)[0],
        donate_argnums=(0,))
    timed_and_captured(out, f"decode_{name}", steps, state, params)


def step(out: dict, pc) -> None:
    """A decode step's state steps alone: one `ssm.ssm_step` a
    state-space layer, each over its own state, the token's inputs the
    same every step (a decay under 1: the states settle, nothing
    overflows)."""
    layers = pc.layer_types.count("mamba")
    di, n, heads = pc.d_inner, pc.mamba_d_state, pc.mamba_n_heads
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    token = (jax.random.normal(k[0], (BATCH, di), jnp.bfloat16),
             jax.nn.softplus(jax.random.normal(k[1], (BATCH, heads)) - 2.0),
             -jnp.exp(jax.random.uniform(k[2], (heads,), maxval=2.7)),
             jax.random.normal(k[3], (BATCH, n), jnp.bfloat16) * 0.3,
             jax.random.normal(k[4], (BATCH, n), jnp.bfloat16) * 0.3,
             jax.random.normal(k[5], (heads,)))
    scattered = np.zeros((BATCH,), bool)
    scattered[np.random.default_rng(1).permutation(BATCH)[:REAL]] = True
    masks = {"32_owned": np.ones((BATCH,), bool)}
    if TAKES_OWNED:
        masks.update({f"{rows}_owned": np.arange(BATCH) < rows
                      for rows in (REAL, 12, 1)})
        masks[f"{REAL}_owned_scattered"] = scattered

        # the kernel against the plain form on the chip's own numbers
        state = jax.random.normal(k[0], (BATCH, n, di), jnp.float32)
        want, want_y = ssm.ssm_step_reference(state, *token, scattered)
        got, got_y = jax.jit(ssm.ssm_step)(state, *token, scattered)
        out["step_kernel_vs_reference_max_abs_diff"] = {
            "state": float(jnp.max(jnp.abs(got - want))),
            "y": float(jnp.max(jnp.abs(got_y - want_y)))}
        out["step_kernel_leaves_other_rows_as_they_were"] = bool(
            jnp.array_equal(got[~scattered], state[~scattered])
            and not jnp.any(got_y[~scattered]))
        del state, want, got

    def steps(carried, token, owned):
        mask = {"owned": owned} if TAKES_OWNED else {}

        def step_fn(carry, _):
            states, y = carry
            for i, state in enumerate(states):
                states[i], found = ssm.ssm_step(state, *token, **mask)
                y = y + found
            return (states, y), None

        return jax.lax.scan(step_fn, carried, None, length=SCAN)[0]

    run = jax.jit(steps, donate_argnums=(0,))
    for name, owned in masks.items():
        carried = ([jnp.ones((BATCH, n, di), jnp.float32)
                    for _ in range(layers)],
                   jnp.zeros((BATCH, di), jnp.float32))
        timed_and_captured(out, f"step_{name}", run, carried, token,
                           jnp.asarray(owned))
        call_ms = out[f"step_{name}_ms_a_step"] / layers
        moved = 2 * int(owned.sum()) * n * di * 4
        out[f"step_{name}_ms_a_call"] = call_ms
        out[f"step_{name}_owned_states_share_of_hbm_peak"] = (
            moved / HBM_BYTES_PER_S / (call_ms / 1e3))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pieces", default="decode,step")
    parser.add_argument("--out", default=str(
        ROOT / "chiprun_out/granite_pieces.json"))
    args = parser.parse_args()
    pieces = set(args.pieces.split(","))
    out = {"device": str(jax.devices()[0].device_kind),
           "step_takes_owned": TAKES_OWNED}
    config = json.loads(
        (ROOT / "perfbench/configs/granite-4.0-h-small.json").read_text())
    pc = gh.GraniteHybridConfig(**children.program_config_kwargs(config))
    if "step" in pieces:
        step(out, pc)
    if "decode" in pieces:
        params = jax.jit(lambda k: gh.init_params(k, pc))(
            jax.random.PRNGKey(1))
        grid = json.loads((ROOT / "perfbench/traffic/short-chat.json")
                          .read_text())["input_length_grid"]
        prefill = jax.jit(lambda p, ids: gh.prefill(
            p, pc, ids, max_decode_len=MAX_DECODE_LEN))
        for name, ids in prompts(grid, pc.vocab_size).items():
            decode(out, name, params, pc, prefill(params, ids))
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
