"""The decode tick as the TPU compiler lays it out: no copy of an arena.

PR 28's finding, guarded: with a page unit of (H, block_size, 64) XLA gave
the donated arenas one layout, the append's scatter wanted a second and the
Pallas operand a third, and every tick re-laid every arena three times (24
of 50 ms at T5-large). `PagedKV.arena` is token-major and lane-dense so
that all three agree. These tests compile the pool's programs for the v5e
ahead of time (nothing runs: no chip is needed, only libtpu) and read the
optimized module."""

import importlib
import os
import re

import jax
import numpy as np
import pytest

from min_tfs_client_tpu.models import t5

attention_module = importlib.import_module("min_tfs_client_tpu.ops.attention")

BLOCK, SLOTS, MAXDEC = 16, 8, 64


@pytest.fixture(scope="module")
def v5e():
    """One device of an ahead-of-time v5e topology, or a skip that says
    why there is none."""
    from jax.experimental import topologies

    env = {"TPU_ACCELERATOR_TYPE": "v5litepod-4",
           "TPU_WORKER_HOSTNAMES": "localhost"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu, or one that knows no v5e
        pytest.skip(f"no ahead-of-time TPU topology here: {exc!r:.200}")
    finally:
        for k, v in old.items():
            os.environ.pop(k) if v is None else os.environ.update({k: v})
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pool():
    """A tiny T5 at T5-large's head size: d_kv 64, the width at which the
    old unit filled half a lane tile and showed the fault."""
    config = t5.T5Config.tiny(d_kv=64, num_heads=4, d_model=64)
    params = t5.init_params(jax.random.PRNGKey(0), config)
    sigs = t5.build_session_signatures(
        params, config, seq_len=16, max_decode_len=MAXDEC,
        max_sessions=SLOTS, continuous_batching=True, kv_block_size=BLOCK,
        kv_prefill_chunk=BLOCK)
    return sigs["decode_init"]._kv_pool


def _compiled(pool, v5e, jitted, extra, monkeypatch):
    """`jitted` of (params, dense pool, arenas, tables, *extra) compiled
    for the v5e at the pool's widest table, with the kernel's gate open
    as it is on the chip."""
    monkeypatch.setattr(attention_module, "_on_tpu", lambda: True)

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e)

    slots, width = pool.max_slots, pool.pages_per_session
    args = jax.tree_util.tree_map(
        struct, (pool._params, pool._dense_pool, pool._arenas))
    more = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in [((slots, width), np.int32)] + extra]
    return jitted.__wrapped__.lower(*args, *more).compile()


def _arena_faults(pool, compiled):
    """What the finding looked like, read off a compiled module: `copy`
    instructions that produce an array of an arena's dims (in any order),
    and parameters of the argument `arenas` that are not aliased input to
    output."""
    text = compiled.as_text()
    assert "_paged_kernel" in text  # the kernel, not the reference
    dims = sorted(pool._arenas[0].shape)
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if (shape := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))
        and sorted(int(n) for n in shape.group(1).split(",")) == dims]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, may-alias\)", text[:text.index("\n")])}
    arenas = {int(n) for n in re.findall(
        r"%arenas_\d+_\S* = \S+ parameter\((\d+)\)", text)}
    assert len(arenas) == len(pool._arenas)
    return copies, arenas - aliased


def _program(pool, which):
    """The pool's jitted program and what it takes after the tables."""
    slots = pool.max_slots
    if which == "tick":
        return pool._tick_jit, [((slots,), np.bool_), ((slots,), np.int32)]
    return pool._chunk_jit, [
        ((slots, pool.prefill_chunk), np.int32), ((slots,), np.int32),
        ((slots, 1), np.int32), ((slots,), np.int32)]


@pytest.mark.parametrize("which", ["tick", "prefill_chunk"])
def test_program_copies_no_arena(which, pool, v5e, monkeypatch):
    compiled = _compiled(pool, v5e, *_program(pool, which), monkeypatch)
    copies, unaliased = _arena_faults(pool, compiled)
    assert not copies, copies
    assert not unaliased, unaliased


# -- the state-space kernels at the published widths --------------------------
#
# In this file because it is the one that describes the v5e (one worker
# loads libtpu; a second file's fixture would skip in silence).


def _ssm_shapes(v5e, batch, seq):
    """granite-4.0-h-small's state-space layer: 128 heads x 64, state 128."""
    import jax.numpy as jnp

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    heads, channels, state = 128, 8192, 128
    lead = (batch,) if seq is None else (batch, seq)
    return {"x": struct(lead + (channels,), jnp.bfloat16),
            "dt": struct(lead + (heads,), jnp.float32),
            "a": struct((heads,), jnp.float32),
            "bm": struct(lead + (state,), jnp.bfloat16),
            "cm": struct(lead + (state,), jnp.bfloat16),
            "d": struct((heads,), jnp.float32),
            "lengths": struct((batch,), jnp.int32),
            "state": struct((batch, state, channels), jnp.float32)}


def test_the_chunked_scan_compiles_for_the_v5e_at_the_published_widths(v5e):
    from min_tfs_client_tpu.ops import ssm

    s = _ssm_shapes(v5e, 4, 2048)
    compiled = jax.jit(lambda *a: ssm.ssd_scan(*a, chunk=256)).lower(
        s["x"], s["dt"], s["a"], s["bm"], s["cm"], s["d"],
        s["lengths"]).compile()
    assert "_ssd_kernel" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True],
                         ids=["every_row", "owned_rows"])
def test_the_step_compiles_for_the_v5e_and_writes_the_state_in_place(
        v5e, masked):
    from min_tfs_client_tpu.ops import ssm

    s = _ssm_shapes(v5e, 32, None)
    owned = ([jax.ShapeDtypeStruct((32,), np.bool_, sharding=v5e)]
             if masked else [])
    compiled = jax.jit(ssm.ssm_step_kernel, donate_argnums=(0,)).lower(
        s["state"], s["x"], s["dt"], s["a"], s["bm"], s["cm"],
        s["d"], *owned).compile()
    text = compiled.as_text()
    assert "_ssm_step_kernel" in text
    # the donated state is the output's buffer, and no copy of it is made
    assert "may-alias" in text[:text.index("\n")] \
        or "must-alias" in text[:text.index("\n")]
    assert not re.search(r"= f32\[32,128,8192\]\S* copy\(", text)


# -- the delta-rule step at the published widths ------------------------------


@pytest.mark.parametrize("masked", [False, True],
                         ids=["every_row", "owned_rows"])
def test_the_kda_step_compiles_for_the_v5e_and_writes_the_state_in_place(
        v5e, masked):
    """ling-3.0-flash's KDA layer: 32 heads, a state of 128 x 128 a head.
    The decay, k and q come in as lane-dense rows (turned into columns in
    the kernel): no operand one lane wide, so nothing but the state and
    one small array of rows is moved."""
    import jax.numpy as jnp

    from min_tfs_client_tpu.ops import kda

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    row = struct((32, 32, 128))
    owned = [struct((32,), np.bool_)] if masked else []
    compiled = jax.jit(kda.kda_step_kernel, donate_argnums=(0,)).lower(
        struct((32, 32, 128, 128)), row, row, row, row, struct((32, 32)),
        *owned).compile()
    text = compiled.as_text()
    assert "_kda_step_kernel" in text
    # the donated state is the output's buffer, and no copy of it is made
    assert "may-alias" in text[:text.index("\n")] \
        or "must-alias" in text[:text.index("\n")]
    assert not re.search(r"= f32\[32,32,128,128\]\S* copy\(", text)
    # beside the state: the rows and o, not three lane-padded columns
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_the_chunked_delta_rule_compiles_for_the_v5e_where_its_operands_lie(
        v5e):
    """ling-3.0-flash's prefill group: 4 examples x 2,048 positions x 32
    heads x 128, float32. q, k and v come as the heads' own arithmetic
    leaves them, (B, S, H, d), and the kernel reads a head's rows with a
    stride of H sublanes; g comes from, and o goes to, rows of every
    head's channels, (B, S, H x d), as `ling_hybrid` has them, and a head
    is a lane slice: no operand of 134 MB is laid out again around the
    call (with every operand a lane slice of a `(B, S, H x d)` view q, k
    and v were: 537 MB of temporaries with o's)."""
    import jax.numpy as jnp

    from min_tfs_client_tpu.ops import kda

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    heads, rows = struct((4, 2048, 32, 128)), struct((4, 2048, 4096))
    assert kda._chunk_kernel_applies(heads, heads, heads, heads, 64)

    def as_the_prefill_calls_it(q, k, v, g, beta, lengths):
        o, state, ran = kda.kda_chunk_kernel(
            q, k, v, g.reshape(q.shape), beta, lengths, chunk=64)
        return o.reshape(g.shape), state, ran

    compiled = jax.jit(as_the_prefill_calls_it).lower(
        heads, heads, heads, rows, struct((4, 2048, 32)),
        struct((4,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "_kda_chunk_kernel" in text
    assert not re.search(
        r"= f32\[4,(2048,32,128|2048,4096|65536,128)\]\S* "
        r"(copy|reshape|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the walk over the hit experts at the published widths ---------------------


def test_the_expert_walk_compiles_for_the_v5e_and_adds_onto_its_input(v5e):
    """ling-3.0-flash's expert layer as a decode step meets it: 128 held
    experts of (2560, 2 x 768) and (768, 2560) bfloat16, 32 rows. Two
    experts' matrices (23.6 MB) stand in VMEM under the limit the call
    names; the matrices themselves stay where they lie (no temporary of
    their size), and `onto`, donated, is the result's buffer."""
    import jax.numpy as jnp

    from min_tfs_client_tpu.parallel import moe

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    held, d, f, rows = 128, 2560, 768, 32
    params = moe.HeldExperts(None, None,
                             struct((held, d, 2 * f), jnp.bfloat16),
                             struct((held, f, d), jnp.bfloat16))
    assert moe._walk_kernel_applies(params, struct((rows, d), jnp.bfloat16))
    compiled = jax.jit(moe.expert_walk_kernel, donate_argnums=(6,)).lower(
        struct((rows, d), jnp.bfloat16), struct((held,), jnp.int32),
        struct((), jnp.int32), struct((held, rows)), params.w_in,
        params.w_out, struct((rows, d))).compile()
    text = compiled.as_text()
    assert "_expert_walk_kernel" in text
    assert "may-alias" in text[:text.index("\n")] \
        or "must-alias" in text[:text.index("\n")]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the hyper-connected residual path at the published widths -------------------


def test_a_sub_layer_s_maps_compile_for_the_v5e_as_a_few_fusions(v5e):
    """xing4.0-29b-a4b's residual path as a decode step meets it: 4
    streams of (32, 3584) float32 through phi (14336, 24), the three maps
    with 20 Sinkhorn rounds, the pre-mix and the post-mix. A round is
    written over planes of one shape, so the TPU compiler fuses it: five
    rounds (a trip of the loop) are 9 fusions and the rest of the
    sub-layer 23, where the rounds alone, as slices, broadcasts and sums
    of a (4, 4, 32) array, compiled to 78 (PERF.md section 6, PR 54). And
    the walk's gate admits this model's experts (two of 22.0 MB inside 48
    MiB)."""
    import jax.numpy as jnp

    from min_tfs_client_tpu.ops import mhc
    from min_tfs_client_tpu.parallel import moe

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    n, rows, d = 4, 32, 3584

    def sub_layer(x, phi, alpha, bias, y):
        pre, post, res = mhc.mhc_maps(
            mhc.mhc_project(x, phi, 1e-6), alpha, bias, n=n, iters=20,
            eps=1e-6, clamp=(-30.0, 30.0))
        return mhc.mhc_post(x, y + mhc.mhc_pre(x, pre), post, res)

    compiled = jax.jit(sub_layer).lower(
        struct((n, rows, d)), struct((n * d, n * (n + 2))), struct((3,)),
        struct((n * (n + 2),)), struct((rows, d))).compile()
    text = compiled.as_text()
    fusions = len(re.findall(r" fusion\(", text[text.index("ENTRY"):]))
    in_a_trip = max(len(re.findall(r" fusion\(", body))
                    for body in text.split("\n\n") if "ENTRY" not in body
                    and not body.lstrip().startswith("%fused"))
    assert 0 < fusions <= 30 and 0 < in_a_trip <= 12
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
    held, f = 8, 1024
    params = moe.HeldExperts(None, None,
                             struct((held, d, 2 * f), jnp.bfloat16),
                             struct((held, f, d), jnp.bfloat16))
    assert moe._walk_kernel_applies(params, struct((rows, d), jnp.bfloat16))
