"""Device times of models/xing.py's prefill and decode step on the chip,
at the widths of perfbench/configs/xing4.0-29b-a4b.json, with the
hyper-connected residual path as it is and with its maps HELD CONSTANT
(PERF.md section 5 quotes them). Not a test and not part of the
benchmark: run it on a machine with the chip,

    python tests/tpu/xing_pieces.py [--pieces decode,prefill,maps,latent]
                                    [--out FILE]

and read chiprun_out/xing_pieces.json (or FILE). `prefill`: a batch's
prefill on 12, 20 and 32 real rows of the traffic's own lengths, the
rest rows that pad the batch (length 0): ms a call, and on 32 rows one
call captured by operation. `decode`: the decode step as the loop of a
whole generation runs it, a `lax.scan` of 16 steps with the state a real
prefill left, donated, timed over 5 calls and captured once for its
device time by operation (a `while` spans its body's operations), on the
same three batches; the table names `_latent_step_kernel`, and the script
FAILS where a step still copies, slices or updates an array shaped like a
latent cache (`latent_step_faults`). `latent`: a step's 20 latent
attentions alone (`latent.absorbed_attention` over 20 caches donated and
handed on, 16 steps a call), at the three batches' own positions, as the
tree runs it and with the gate held shut by this script (the row's
scatter and `attend_cache`): ms a step, the blocks' bytes and their share
of 819 GB/s. `_maps_constant`: the same two with H_pre = 1/n,
H_post = 1, H_res = I in every sub-layer (this script swaps
`xing._maps`: no product with phi, no Sinkhorn round; the pre-mix and
the post-mix still pass over the streams), on 32 rows: what the maps
cost is the difference, what the streams' traffic costs is in the
captured operations.
"""

import argparse
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

from min_tfs_client_tpu.models import latent, xing  # noqa: E402
from mimo_pieces import (  # noqa: E402  (beside this file)
    SCAN,
    fail_on_latent_step_faults,
    latent_step_faults,
    ops_a_step,
)
from perfbench import children  # noqa: E402
from t5_pieces import timed_and_captured  # noqa: E402  (beside this file)

BATCH, SEQ_LEN, MAX_DECODE_LEN = 32, 2048, 128
REAL = (12, 20, 32)
FULL = f"{BATCH}_real_rows"     # the batch whose prefill is captured
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


def constant_maps(config, hc, x):
    """H_pre = 1/n, H_post = 1, H_res = I for the streams x (n, T, C)."""
    n, t = config.hc_mult, x.shape[1]
    return (jnp.full((n, t), 1.0 / n), jnp.ones((n, t)),
            jnp.broadcast_to(jnp.eye(n)[:, :, None], (n, n, t)))


def measure(out: dict, tag: str, pieces: set, params, pc, given: dict) -> None:
    """Traces `xing.prefill` and `xing.step` as the module stands NOW (the
    maps' swap is made before the call)."""
    prefill = jax.jit(lambda p, ids: xing.prefill(
        p, pc, ids, max_decode_len=MAX_DECODE_LEN))
    steps = jax.jit(
        lambda s, p: jax.lax.scan(lambda s, _: (xing.step(p, pc, s)[0], None),
                                  s, None, length=SCAN)[0],
        donate_argnums=(0,))
    for name, ids in given.items():
        clock = time.perf_counter()
        state = jax.block_until_ready(prefill(params, ids))
        out[f"prefill_{name}{tag}_first_call_s"] = time.perf_counter() - clock
        clock = time.perf_counter()
        for _ in range(2):
            state = jax.block_until_ready(prefill(params, ids))
        out[f"prefill_{name}{tag}_ms"] = (time.perf_counter() - clock) / 2 * 1e3
        if "prefill" in pieces and name == FULL:
            with tempfile.TemporaryDirectory() as capture:
                jax.profiler.start_trace(capture)
                state = jax.block_until_ready(prefill(params, ids))
                jax.profiler.stop_trace()
                out[f"prefill_{name}{tag}_ops"] = ops_a_step(
                    capture, most=40, steps=1)
        if "decode" in pieces:
            timed_and_captured(out, f"decode_{name}{tag}", steps, state,
                               params)
            out[f"decode_{name}{tag}_faults"] = latent_step_faults(
                out[f"decode_{name}{tag}_ops"], BATCH,
                SEQ_LEN + MAX_DECODE_LEN)


def latent_alone(out: dict, pc, given: dict) -> None:
    """A step's latent attentions alone: one `absorbed_attention` a layer
    over its own cache, each example at its prompt's length plus the
    steps gone, SCAN steps a call, the caches donated and handed on."""
    layers, heads, rank = pc.num_layers, pc.num_heads, pc.kv_lora_rank
    width = latent.cache_width(pc.latent_width)
    positions = SEQ_LEN + MAX_DECODE_LEN
    dtype = jnp.dtype(pc.dtype)
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    kvb = (jax.random.normal(keys[0], (rank, heads * (
        pc.qk_nope_head_dim + pc.v_head_dim))) * rank ** -0.5).astype(dtype)
    q = jax.random.normal(keys[1], (BATCH, heads, pc.qk_head_dim)).astype(
        dtype)
    row = jax.random.normal(keys[2], (BATCH, width)).astype(dtype)
    sizes = dict(nope=pc.qk_nope_head_dim, v_head_dim=pc.v_head_dim,
                 scale=pc.attention_scale)

    def steps(carried, kvb, q, row, owned):
        def one(carried, _):
            caches, position, total = carried
            after = []
            for cache in caches:
                mixed, cache, _ = latent.absorbed_attention(
                    kvb, q, cache, row, position, owned, **sizes)
                after.append(cache)
                total = total + jnp.sum(mixed)
            return (after, position + 1, total), None
        return jax.lax.scan(one, carried, None, length=SCAN)[0]

    sound = latent._on_tpu
    for name, ids in given.items():
        lengths = np.sum(ids > 0, axis=-1).astype(np.int32)
        owned = jnp.asarray(lengths > 0)
        blocks = int(np.sum(-(-(lengths[lengths > 0] + SCAN // 2) // 128)))
        for form, on_tpu in (("", sound), ("_gate_shut", lambda: False)):
            latent._on_tpu = on_tpu
            try:
                # a function of its own a form: the gate is read as the
                # function is traced, and one function is traced once
                run = jax.jit(lambda *a: steps(*a), donate_argnums=(0,))
                carried = ([jnp.zeros((BATCH, 1, positions, width), dtype)
                            for _ in range(layers)], jnp.asarray(lengths),
                           jnp.zeros((), jnp.float32))
                carried = jax.block_until_ready(
                    run(carried, kvb, q, row, owned))
                clock = time.perf_counter()
                for _ in range(3):
                    carried = (carried[0], jnp.asarray(lengths), carried[2])
                    carried = run(carried, kvb, q, row, owned)
                jax.block_until_ready(carried)
            finally:
                latent._on_tpu = sound
            ms = (time.perf_counter() - clock) / 3 / SCAN * 1e3
            out[f"latent_{name}{form}_ms_a_step"] = ms
            del carried
        needed = layers * blocks * 128 * width * dtype.itemsize
        out[f"latent_{name}_blocks_a_layer"] = blocks
        out[f"latent_{name}_share_of_bandwidth"] = (
            needed / HBM_BYTES_PER_S / (
                out[f"latent_{name}_ms_a_step"] / 1e3))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pieces", default="decode,prefill,maps")
    parser.add_argument("--out", default=str(
        ROOT / "chiprun_out/xing_pieces.json"))
    args = parser.parse_args()
    pieces = set(args.pieces.split(","))
    out = {"device": str(jax.devices()[0].device_kind)}
    config = json.loads(
        (ROOT / "perfbench/configs/xing4.0-29b-a4b.json").read_text())
    pc = xing.XingConfig(**children.program_config_kwargs(config))
    params = jax.jit(lambda k: xing.init_params(k, pc))(jax.random.PRNGKey(1))
    grid = json.loads((ROOT / "perfbench/traffic/document-answers.json")
                      .read_text())["input_length_grid"]
    given = prompts(grid, pc.vocab_size)
    out["prompt_tokens"] = {name: int(np.sum(ids > 0))
                            for name, ids in given.items()}
    if "latent" in pieces:
        latent_alone(out, pc, given)
    measure(out, "", pieces, params, pc, given)
    if "maps" in pieces:
        sound = xing._maps
        xing._maps = constant_maps
        try:
            measure(out, "_maps_constant", pieces, params, pc,
                    {FULL: given[FULL]})
        finally:
            xing._maps = sound
    print(json.dumps(out, indent=1))
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    fail_on_latent_step_faults(out)


if __name__ == "__main__":
    main()
