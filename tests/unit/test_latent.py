"""models/latent.py, latent attention's one home: the absorbed decode
form against the decompressed one at each caller's sizes (Ling's plain
scale, Xing's low-rank query and YaRN's scale); the decode step's kernel
(ops/attention.py:_latent_step_kernel, interpreted on the CPU) against
both at the two cells' own widths, the rows it writes and the rows it
leaves, and what its gate admits; YaRN's frequencies against
DeepSeek-V3's formula written out again, the rotary, and the programs
that lower to the text they lowered to."""

import functools
import hashlib
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import latent, ling_hybrid, xing

# (the package's `attention` is the dispatcher, not the module)
rows_ops = importlib.import_module("min_tfs_client_tpu.ops.attention")

# (heads, nope, rope, v, rank, scale): Ling's MLA layer at a small size
# (no stretch: qk_head_dim ** -0.5), and Xing's, whose scale carries
# YaRN's temperature squared
CALLERS = {
    "ling": (4, 16, 8, 16, 32, 24 ** -0.5),
    "xing": (4, 16, 8, 16, 32,
             24 ** -0.5 * latent.yarn_mscale(64.0, 1.0) ** 2),
    "other_sizes": (2, 8, 16, 24, 16, 0.3),
}


@pytest.mark.parametrize("caller", list(CALLERS))
def test_the_absorbed_decode_is_the_decompressed_form(caller):
    """Latent attention's two forms on the same rows: a query at each
    example's last position over the latent rows, in the latent space
    (the step's) and over decompressed K and V (the prefill's)."""
    h, nope, rope, dv, rank, scale = CALLERS[caller]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s = 3, 40
    kvb = jax.random.normal(keys[2], (rank, h * (nope + dv))) * rank ** -0.5
    q = jax.random.normal(keys[0], (b, s, h, nope + rope))
    rows = jax.random.normal(keys[1], (b, s, rank + rope))
    lengths = jnp.asarray([40, 17, 1], jnp.int32)
    sizes = dict(nope=nope, v_head_dim=dv, scale=scale)
    with jax.default_matmul_precision("highest"):
        whole = latent.decompressed_attention(kvb, q, rows, lengths, **sizes)
        last = lengths - 1
        each = jnp.arange(b)
        # the cache before the step lacks the step's own row
        before = rows.at[each, last].set(0)[:, None]
        step = (kvb, q[each, last], before, rows[each, last], last,
                jnp.ones((b,), bool))
        one, after, copied = latent.absorbed_attention(*step, **sizes)
        twice, _, _ = latent.absorbed_attention(
            *step, **dict(sizes, scale=2 * scale))
    assert whole.shape == (b, s, h * dv)
    np.testing.assert_array_equal(after[:, 0], rows)
    assert copied.tolist() == [s] * b          # the jnp form reads them all
    np.testing.assert_allclose(one, whole[jnp.arange(b), last], atol=1e-5)
    assert float(jnp.std(one)) > 0.05
    # the scale is the caller's and it matters (but where one key is seen)
    assert float(jnp.max(jnp.abs(twice - one)[:2])) > 1e-3
    np.testing.assert_allclose(twice[2], one[2], atol=1e-6)


def test_xing_s_query_goes_through_its_low_rank_and_its_norm():
    """models/xing.py's inputs of the two forms: q = W_qb RMSNorm(W_qa x)
    rotated on its rope lanes, the cached row RMSNorm(c) | rope(k_r);
    prefill form and step form agree on them."""
    pc = xing.XingConfig(
        vocab_size=32, hidden_size=32, num_layers=1, ffn_types=("dense",),
        num_heads=2, q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=16,
        rope_original_positions=16, dtype="float32")
    p = xing.init_params(jax.random.PRNGKey(0), pc)["layers"][0]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    positions = jnp.arange(24)
    with jax.default_matmul_precision("highest"):
        q, rows = xing._mla_inputs(pc, p, x, positions)
        low = x @ p["qa"]["kernel"]
        low = low * jax.lax.rsqrt(jnp.mean(low * low, -1, keepdims=True)
                                  + pc.eps)
        want = (low @ p["qb"]["kernel"]).reshape(24, 2, 16)
        np.testing.assert_allclose(q[..., :8], want[..., :8], atol=1e-5)
        # a rotation keeps a pair's length, and position 0 is not rotated
        np.testing.assert_allclose(
            jnp.sum(q[..., 8:] ** 2, -1), jnp.sum(want[..., 8:] ** 2, -1),
            rtol=1e-4)
        np.testing.assert_allclose(q[0], want[0], atol=1e-5)
        assert float(jnp.max(jnp.abs(q[5, :, 8:] - want[5, :, 8:]))) > 0.01
        sizes = dict(nope=8, v_head_dim=8, scale=pc.attention_scale)
        whole = latent.decompressed_attention(
            p["kvb"]["kernel"], q[None], rows[None], jnp.asarray([24]),
            **sizes)
        one, _, _ = latent.absorbed_attention(
            p["kvb"]["kernel"], q[None, 23], rows[None, None], rows[None, 23],
            jnp.asarray([23]), jnp.ones((1,), bool), **sizes)
    np.testing.assert_allclose(one[0], whole[0, 23], atol=1e-5)
    # 16 + 8 values, then zeros to whole lane tiles
    assert rows.shape == (24, 128) and not np.any(np.asarray(rows[:, 24:]))
    assert latent.cache_width(576) == 640 and latent.cache_width(512) == 512
    assert pc.attention_scale == pytest.approx(
        16 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)



# -- the decode step's kernel -------------------------------------------------

# (scale, positions of the cache): both cells' MLA layers are 32 heads of
# nope 128 + rope 64 over a rank of 512, values of 128; Ling's scale is
# plain, Xing's carries YaRN's temperature squared; the caches are whole
# blocks of 128 positions (2,304 and 2,176 as served)
HEADS, NOPE, ROPE, DV, RANK = 32, 128, 64, 128, 512
CELLS = {"ling": (192 ** -0.5, 768),
         "xing": (192 ** -0.5 * latent.yarn_mscale(64.0, 1.0) ** 2, 640)}
# an example's position this step -> its own row is the last it sees: the
# first position, both sides of a tile's border, of a block's and of a
# group's (4 blocks go through the softmax together), the cache's last
# position (-1: a group of its own, of one block or of two); None: a row
# nobody owns. Eleven rows: no whole group of any size a kernel of this
# file takes
POSITIONS = (0, 15, 16, 17, None, 127, 128, 511, 512, -1, None)


def through_the_kernel(monkeypatch, block=128, admit_any_shape=False):
    """`absorbed_attention` takes its kernel body, interpreted: as on a
    TPU whose gate admits the shapes (or, for a model at a test's size,
    any shape, in blocks of `block` positions)."""
    monkeypatch.setattr(latent, "_on_tpu", lambda: True)
    monkeypatch.setattr(latent, "latent_step_attention", functools.partial(
        rows_ops.latent_step_attention, block=block, interpret=True))
    monkeypatch.setattr(latent, "latent_rows_copied", functools.partial(
        rows_ops.latent_rows_copied, block=block))
    if admit_any_shape:
        monkeypatch.setattr(latent, "_latent_step_applies",
                            lambda q, cache, rank: True)


def answers_through_both_bodies(module, params, config, ids, *, seq_len,
                                steps, model):
    """A whole generation of `steps` steps as an answer of `module`'s
    signature, through both bodies of the step's latent attention: the
    jnp one, and the kernel (interpreted, in blocks of 16 positions).
    {form: {"out", "span": `generate/latent`'s arguments, "counted":
    the label's `latent` section}}."""
    from min_tfs_client_tpu.observability import runtime, tracing

    found = {}
    for form in ("jnp", "kernel"):
        label = f"{model}-{form}:1:serving_default"
        with pytest.MonkeyPatch.context() as patch:
            if form == "kernel":
                through_the_kernel(patch, block=16, admit_any_shape=True)
            signature = module.build_signatures(
                params, config, seq_len=seq_len, max_decode_len=steps,
                batch_buckets=(len(ids),))["serving_default"]
            signature.telemetry_label = label
            with tracing.request_trace(
                    "predict", model=model,
                    signature="serving_default") as trace:
                out = signature.run({"input_ids": ids})
                signature.on_answer(signature, out)
        found[form] = {
            "out": out,
            "span": {name: args for name, _, _, args in trace.spans}[
                "generate/latent"],
            "counted": runtime.snapshot()["latent"][label]}
    return found


def check_the_kernel_s_generation_is_the_jnp_one(answers, row, atol):
    jnp_out, kernel_out = answers["jnp"]["out"], answers["kernel"]["out"]
    assert kernel_out["output_ids"][row].tolist() \
        == jnp_out["output_ids"][row].tolist()
    for name in ("first_logits", "last_logits"):
        np.testing.assert_allclose(kernel_out[name][row], jnp_out[name][row],
                                   atol=atol)
    assert np.std(jnp_out["last_logits"][row]) > 0.05


def check_the_rows_an_answer_brought_in(answers, form, columns, lengths, *,
                                        layers, seq_len, steps):
    """`latent_rows_copied` on the counts, the span and
    `/monitoring/runtime`: whole blocks of what each step's read needed,
    between the rows read and the rows held; all of them on the jnp
    path. `layers`: the model's latent caches."""
    index = {name: i for i, name in enumerate(columns)}
    rows = answers[form]["out"]["latent_counts"]
    read, held, copied = (rows[:, index[f"latent_rows_{name}"]]
                          for name in ("read", "held", "copied"))
    assert held.tolist() == [
        layers * steps * (seq_len + steps) if n else 0 for n in lengths]
    assert np.all(read <= copied) and np.all(copied <= held)
    if form == "jnp":
        assert np.array_equal(copied, held)
    else:
        # a step at position p brings in ceil((p + 1) / 16) blocks of 16
        assert copied.tolist() == [
            layers * sum(-(-(n + step + 1) // 16) * 16
                         for step in range(steps)) if n else 0
            for n in lengths]
        assert np.all(copied % 16 == 0) and copied.sum() < held.sum()
    for where in ("span", "counted"):
        assert answers[form][where]["latent_rows_copied"] == copied.sum()
        assert answers[form][where]["latent_rows_read"] == read.sum()
        assert answers[form][where]["latent_rows_held"] == held.sum()


@pytest.fixture(scope="module", params=list(CELLS))
def a_step(request):
    """A decode step at a cell's sizes, float32: the prompt's rows in
    the cache, the step's row beside it, and what both jnp forms say."""
    scale, s = CELLS[request.param]
    b = len(POSITIONS)
    owned = np.asarray([p is not None for p in POSITIONS])
    position = np.asarray([(p or 0) % s for p in POSITIONS])
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    kvb = jax.random.normal(keys[0], (RANK, HEADS * (NOPE + DV))) \
        * RANK ** -0.5
    q = jax.random.normal(keys[1], (b, s, HEADS, NOPE + ROPE))
    kva = jax.random.normal(keys[2], (b * s, RANK + ROPE))
    rows = latent.latent_row(
        kva, {"scale": jnp.ones((RANK,))}, jnp.tile(jnp.arange(s), b),
        latent.plain_frequencies(1e4), rank=RANK, eps=1e-6).reshape(b, s, -1)
    each = jnp.arange(b)
    # what lies at and behind the position before the step: not zeros
    before = jnp.where(
        jnp.arange(s)[None, :, None] < position[:, None, None], rows,
        jax.random.normal(keys[3], rows.shape))[:, None]
    sizes = dict(nope=NOPE, v_head_dim=DV, scale=scale)
    step = (kvb, q[each, position], before, rows[each, position],
            jnp.asarray(position), jnp.asarray(owned))
    with jax.default_matmul_precision("highest"):
        whole = latent.decompressed_attention(
            kvb, q, rows, jnp.asarray(position + 1), **sizes)
        plain = latent.absorbed_attention(*step, **sizes)
        with pytest.MonkeyPatch.context() as patch:
            through_the_kernel(patch)
            kernel = latent.absorbed_attention(*step, **sizes)
    return {"s": s, "owned": owned, "position": position, "rows": rows,
            "before": before, "plain": plain, "kernel": kernel,
            "decompressed": whole[each, position]}


@pytest.mark.parametrize("row", range(len(POSITIONS)))
def test_the_kernel_is_the_jnp_step_and_the_prefill_s_last_row(a_step, row):
    out, _, copied = a_step["kernel"]
    if not a_step["owned"][row]:
        # nothing read, zeros out
        assert not np.any(np.asarray(out[row])) and int(copied[row]) == 0
        return
    np.testing.assert_allclose(out[row], a_step["plain"][0][row], atol=2e-5)
    np.testing.assert_allclose(out[row], a_step["decompressed"][row],
                               atol=2e-5)
    assert float(jnp.std(out[row])) > 0.02
    # whole blocks, the one that holds the step's own row the last
    seen = int(a_step["position"][row]) + 1
    assert int(copied[row]) == -(-seen // 128) * 128
    assert seen <= int(copied[row]) <= a_step["s"]
    assert int(rows_ops.latent_rows_copied(seen, a_step["s"])) \
        == int(copied[row])


@pytest.mark.parametrize("row", range(len(POSITIONS)))
def test_a_step_changes_the_written_row_and_no_other(a_step, row):
    before = np.asarray(a_step["before"][row, 0])
    after = np.asarray(a_step["kernel"][1][row, 0])
    if not a_step["owned"][row]:
        np.testing.assert_array_equal(after, before)
        return
    at = int(a_step["position"][row])
    changed = np.flatnonzero(np.any(after != before, axis=-1))
    assert changed.tolist() == [at]
    np.testing.assert_array_equal(after[at], a_step["rows"][row, at])
    # ... which is what the jnp form's scatter leaves
    np.testing.assert_array_equal(after, a_step["plain"][1][row, 0])


def test_the_kernel_rounds_where_the_jnp_form_rounds():
    """As served: a bfloat16 cache and query, float32 scores, softmax
    and sums, the weights in the cache's dtype for the value product."""
    b, s, width = 4, 256, 640
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    dtype = jnp.bfloat16
    q = jax.random.normal(keys[0], (b, HEADS, width)).astype(dtype)
    row = jax.random.normal(keys[1], (b, width)).astype(dtype)
    cache = jax.random.normal(keys[2], (b, 1, s, width)).astype(dtype)
    position = jnp.asarray([3, 130, 255, 77])
    out, after = rows_ops.latent_step_attention(
        q, row, cache, position + 1, rank=RANK, scale=0.1, interpret=True)
    want_cache = cache.at[jnp.arange(b), 0, position].set(row)
    want = latent.nn.attend_cache(
        q, {"k": want_cache, "v": want_cache[..., :RANK]},
        jnp.arange(s)[None, :] <= position[:, None], None, scale=0.1)
    assert out.dtype == dtype and after.dtype == dtype
    np.testing.assert_array_equal(after, want_cache)
    # two roundings to bfloat16 apart (the weights before or after their
    # division by the sum), at values of size 1
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(b, -1),
        np.asarray(want, np.float32), atol=0.02)


def shapes(batch=32, positions=2176, width=640, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((batch, HEADS, width), dtype),
            jax.ShapeDtypeStruct((batch, 1, positions, width), dtype))


@pytest.mark.parametrize("case, admitted", [
    ("xing_s_cell", True), ("ling_s_cell", True), ("one_example", True),
    ("a_length_that_is_no_whole_block", False),
    ("rows_that_are_no_whole_lane_tiles", False),
    ("values_that_are_no_whole_lane_tiles", False),
    ("a_cache_of_another_dtype", False), ("several_kv_heads", False),
    ("a_sharded_cache", False)])
def test_the_gate_reads_the_shapes_and_nothing_else(case, admitted):
    rank = RANK
    q, cache = shapes()
    if case == "ling_s_cell":
        q, cache = shapes(positions=2304)
    elif case == "one_example":
        q, cache = shapes(batch=1)
    elif case == "a_length_that_is_no_whole_block":
        q, cache = shapes(positions=2176 + 64)
    elif case == "rows_that_are_no_whole_lane_tiles":
        q, cache = shapes(width=576)
    elif case == "values_that_are_no_whole_lane_tiles":
        rank = 448
    elif case == "a_cache_of_another_dtype":
        cache = shapes(dtype=jnp.float32)[1]
    elif case == "several_kv_heads":
        cache = jax.ShapeDtypeStruct((32, 2, 2176, 640), jnp.bfloat16)
    if case == "a_sharded_cache":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
        with jax.set_mesh(mesh):
            assert not rows_ops._latent_step_applies(q, cache, rank)
        return
    assert rows_ops._latent_step_applies(q, cache, rank) is admitted


def deepseek_v3_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies as DeepSeek-V3's modelling code has
    them (find_correction_dim / range, linear_ramp_mask), written out."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def test_yarn_s_frequencies_are_deepseek_v3_s():
    got = latent.yarn_frequencies(
        1e4, factor=64.0, original=4096, beta_fast=32.0, beta_slow=1.0)(32)
    want = deepseek_v3_inv_freq(64, 1e4, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(latent.plain_frequencies(1e4)(32))
    # the quick pairs keep their frequency, the slow ones have it divided
    # by the factor, between the correction dimensions (10 and 23) a ramp
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    assert np.all(got[11:23] < plain[11:23])
    assert np.all(got[11:23] > plain[11:23] / 64)
    assert latent.yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert latent.yarn_mscale(1.0, 1.0) == 1.0


def test_the_rotary_turns_interleaved_pairs_by_position_times_frequency():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 8)),
                    jnp.float32)
    positions = jnp.asarray([0, 1, 2, 7, 100])
    law = latent.plain_frequencies(100.0)
    got = np.asarray(latent.rope(x, positions, law))
    inv = np.asarray(law(4))
    for t, position in enumerate(np.asarray(positions)):
        for pair in range(4):
            angle = position * inv[pair]
            a, b = np.asarray(x[t, :, 2 * pair]), np.asarray(x[t, :, 2 * pair + 1])
            np.testing.assert_allclose(
                got[t, :, 2 * pair], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-5)
            np.testing.assert_allclose(
                got[t, :, 2 * pair + 1], b * np.cos(angle) + a * np.sin(angle),
                atol=1e-5)


# -- what lowers as it did ------------------------------------------------------

# sha256 of the StableHLO text (`.lower(...).as_text()`) of each
# generation program at its own test file's small size, on this JAX. The
# three that run no latent attention (MiMo and Granite through
# `layers.attend_cache`, T5 through `_rows_kernel`'s dispatcher) were
# taken on the tree BEFORE the decode step's kernel (commit 18728f5): PR 55
# left their text as it was. Ling's were taken after it: its cached rows
# are whole lane tiles now and its counts have a column more, the
# arithmetic is what commit 7c4bf55 lowered (PR 54's digests, which pinned
# the move of the latent forms into models/latent.py, stood until then).
# A change of a program's own arithmetic moves its digests, and then they
# are taken again.
LOWERED = {
    "mimo": {
        "prefill": (427545, "5c829654d38339b356711314da0f7837d47c2544860ccc"
                            "89a0d7ff43925de25a"),
        "step": (198770, "fc4b3041fb27976c0baedd198fd976ed1c65514f03df0096d3"
                         "24094ee2cc5907")},
    "granite": {
        "prefill": (239311, "7bf59800aeb11e7f9fcbf352492e9c966af00ef8a740d2"
                            "120cda33ca5867d60d"),
        "step": (83007, "5f0200a6521012da7f553c0e65a52a741eb1bc23ddfd71e63b"
                        "36114d5ddffeea")},
    "t5": {
        "generate": (88978, "15c0e4bd9bc53ca3e1978636188a98e76ef8fa631bea0d"
                            "af97fa8db3b57372b4")},
    "ling": {
        "prefill": (360495, "379f28c81f30cd568abe64732ac7fc1b8a7d18d35e77c8"
                            "7750afef9b665a272f"),
        "step": (121719, "5a43b8d909929ddc37a796d1df6542e6f1d571c3f857ab5957"
                         "94493906b902ab")},
}


def lowered_decoder(module, config_class, small, **prefill_kwargs):
    """{"prefill", "step"}: the StableHLO text of a decoder family's two
    programs at its test file's small size, a batch of 4."""
    from perfbench import children

    config = (small.published if hasattr(small, "published")
              else small.tiny_config)()
    pc = config_class(**children.program_config_kwargs(config))
    params = jax.eval_shape(
        lambda: module.init_params(jax.random.PRNGKey(7), pc))
    ids = jax.ShapeDtypeStruct((4, small.SEQ), jnp.int32)
    prefill = jax.jit(lambda p, i: module.prefill(
        p, pc, i, max_decode_len=small.STEPS, **prefill_kwargs))
    state = jax.eval_shape(prefill, params, ids)
    return {"prefill": prefill.lower(params, ids).as_text(),
            "step": jax.jit(lambda p, s: module.step(p, pc, s)).lower(
                params, state).as_text()}


@pytest.fixture(scope="module")
def lowered():
    from min_tfs_client_tpu.models import granite_hybrid, mimo, t5
    from tests.unit import test_granite_hybrid, test_ling_hybrid, test_mimo

    config = t5.T5Config.tiny()
    params = jax.eval_shape(
        lambda: t5.init_params(jax.random.PRNGKey(7), config))
    return {
        "mimo": lowered_decoder(mimo, mimo.MimoConfig, test_mimo),
        "granite": lowered_decoder(
            granite_hybrid, granite_hybrid.GraniteHybridConfig,
            test_granite_hybrid, row_block=32),
        "ling": lowered_decoder(
            ling_hybrid, ling_hybrid.LingHybridConfig, test_ling_hybrid,
            row_block=32),
        "t5": {"generate": jax.jit(lambda p, ids, n: t5.greedy_decode(
            p, config, ids, n, max_decode_len=16)).lower(
                params, jax.ShapeDtypeStruct((4, 24), jnp.int32),
                jax.ShapeDtypeStruct((4,), jnp.int32)).as_text()}}


@pytest.mark.parametrize("family, program", [
    (family, program) for family, programs in LOWERED.items()
    for program in programs])
def test_a_program_lowers_to_the_text_it_lowered_to(lowered, family, program):
    text = lowered[family][program]
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == LOWERED[family][program]


def test_ling_keeps_no_copy_of_the_latent_forms():
    for name in ("decompressed_attention", "absorbed_attention", "_rope"):
        assert not hasattr(ling_hybrid, name)
        assert not hasattr(xing, name)
