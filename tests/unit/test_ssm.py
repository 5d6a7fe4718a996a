"""ops/ssm.py: the chunked scan against the token-by-token recurrence, the
Pallas kernels (interpret mode) against both, and the one-token step
against a recurrence that goes one token further."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.ops import ssm

HEADS, HEAD_DIM, STATE, CHUNK = 4, 64, 32, 128   # two heads a lane tile
SEQ = 300                                        # not whole chunks


def operands(batch, seq, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (batch, seq, HEADS * HEAD_DIM)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, HEADS)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (HEADS,), minval=0.0, maxval=2.7))
    bm = (jax.random.normal(k[3], (batch, seq, STATE)) * 0.3).astype(dtype)
    cm = (jax.random.normal(k[4], (batch, seq, STATE)) * 0.3).astype(dtype)
    return x, dt, a, bm, cm, jax.random.normal(k[5], (HEADS,))


def real_rows(lengths, seq):
    return (np.arange(seq)[None, :] < np.asarray(lengths)[:, None])[..., None]


def chunked(*args, **kw):
    return ssm.ssd_chunked(*args, chunk=CHUNK, **kw)


def kernel(*args, **kw):
    return ssm.ssd_scan(*args, chunk=CHUNK, interpret=True, **kw)


# lengths on both sides of a chunk's edge, a whole buffer, rows of length 0
LENGTHS = [(300, 0, 130), (127, 128, 129), (1, 256, 257), (0, 0, 3)]


@pytest.mark.parametrize("form", [chunked, kernel], ids=["jnp", "pallas"])
@pytest.mark.parametrize("lengths", LENGTHS)
def test_the_chunked_scan_is_the_recurrence(form, lengths):
    args = operands(len(lengths), SEQ)
    lengths = jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = ssm.ssd_reference(*args, lengths)
        got_y, got_h, _ = form(*args, lengths)
    real = real_rows(lengths, SEQ)
    np.testing.assert_allclose(np.where(real, got_y, 0),
                               np.where(real, want_y, 0), atol=5e-5)
    # the state handed on is the state after each example's last REAL
    # token: the padding behind it moved nothing, a row of length 0 is 0
    np.testing.assert_allclose(got_h, want_h, atol=5e-6, rtol=1e-4)
    assert not np.any(np.asarray(got_h)[np.asarray(lengths) == 0])


def test_both_chunked_forms_are_one_arithmetic_in_bfloat16():
    """What is served: bfloat16 operands into the products, float32
    decays, state and accumulation. The two forms round alike, and stay
    near the float32 recurrence."""
    args = operands(2, SEQ, seed=3, dtype=jnp.bfloat16)
    lengths = jnp.asarray([300, 77], jnp.int32)
    want_y, want_h = ssm.ssd_reference(*args, lengths)
    y1, h1, _ = chunked(*args, lengths)
    y2, h2, _ = kernel(*args, lengths)
    real = real_rows(lengths, SEQ)
    assert y1.dtype == y2.dtype == jnp.bfloat16 and h2.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want_y)))
    for y, h in ((y1, h1), (y2, h2)):
        assert np.max(np.abs(np.where(real, y.astype(np.float32) - want_y,
                                      0))) < 0.02 * scale
        np.testing.assert_allclose(h, want_h, atol=0.02)
    np.testing.assert_allclose(np.where(real, y1, 0).astype(np.float32),
                               np.where(real, y2, 0).astype(np.float32),
                               atol=0.02 * scale)


def test_each_form_says_what_its_scan_ran():
    args = operands(3, SEQ)
    lengths = jnp.asarray([300, 0, 130], jnp.int32)
    # plain jnp: every example to the batch's longest, in whole chunks
    assert chunked(*args, lengths)[2].tolist() == [384, 384, 384]
    # the kernel: each example's own chunks and no more
    assert kernel(*args, lengths)[2].tolist() == [384, 0, 256]


@pytest.mark.parametrize("interpret", [None, True], ids=["jnp", "pallas"])
def test_a_step_is_one_more_token_of_the_recurrence(interpret):
    x, dt, a, bm, cm, d = operands(3, 41, seed=5)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = ssm.ssd_reference(x, dt, a, bm, cm, d)
        _, before = ssm.ssd_reference(x[:, :40], dt[:, :40], a, bm[:, :40],
                                      cm[:, :40], d)
        _, chunked_before, _ = ssm.ssd_chunked(
            x[:, :40], dt[:, :40], a, bm[:, :40], cm[:, :40], d, chunk=16)
    np.testing.assert_allclose(chunked_before, before, atol=5e-6, rtol=1e-4)
    last = (x[:, 40], dt[:, 40], a, bm[:, 40], cm[:, 40], d)
    if interpret:
        after, y = ssm.ssm_step_kernel(before, *last, interpret=True)
    else:
        after, y = ssm.ssm_step_reference(before, *last)
    np.testing.assert_allclose(after, want_h, atol=2e-6, rtol=1e-4)
    np.testing.assert_allclose(y, want_y[:, 40], atol=2e-5)


# -- the step moves the states of the rows a batch owns -----------------------

# (batch, heads, head size, state): a small shape of one channel block, and
# the published state (128 columns) over two blocks of 2,048 channels
STEP_SHAPES = {"small": (5, 4, 64, 32), "published_blocks": (5, 64, 64, 128)}
MASKS = {"all": (1, 1, 1, 1, 1), "none": (0, 0, 0, 0, 0),
         "prefix": (1, 1, 1, 0, 0), "scattered": (0, 1, 0, 1, 1),
         "one_row": (0, 0, 0, 1, 0)}


STEP_FORMS = {"jnp": ssm.ssm_step_reference,
              "pallas": lambda *args: ssm.ssm_step_kernel(*args,
                                                          interpret=True)}


@pytest.fixture(scope="module")
def step_cases():
    """shape name -> (operands, {form: its unmasked step})."""
    found = {}
    for name, (batch, heads, p, n) in STEP_SHAPES.items():
        k = jax.random.split(jax.random.PRNGKey(11), 7)
        args = (jax.random.normal(k[0], (batch, n, heads * p)),
                jax.random.normal(k[1], (batch, heads * p)),
                jax.nn.softplus(jax.random.normal(k[2], (batch, heads)) - 2),
                -jnp.exp(jax.random.uniform(k[3], (heads,), maxval=2.7)),
                jax.random.normal(k[4], (batch, n)) * 0.3,
                jax.random.normal(k[5], (batch, n)) * 0.3,
                jax.random.normal(k[6], (heads,)))
        found[name] = (args, {form: jax.tree_util.tree_map(
            np.asarray, step(*args)) for form, step in STEP_FORMS.items()})
    return found


@pytest.mark.parametrize("form", ["jnp", "pallas"])
@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("shape", list(STEP_SHAPES))
def test_a_step_moves_the_owned_rows_and_no_other(step_cases, shape, mask,
                                                  form):
    args, unmasked = step_cases[shape]
    want_state, want_y = unmasked[form]
    owned = np.asarray(MASKS[mask], bool)
    state, y = STEP_FORMS[form](*args, jnp.asarray(owned))
    state, y = np.asarray(state), np.asarray(y)
    # an owned row: the unmasked step's, to the bit
    assert np.array_equal(state[owned], want_state[owned])
    assert np.array_equal(y[owned], want_y[owned])
    # any other: its state as it came, to the bit, and y = 0
    assert np.array_equal(state[~owned], np.asarray(args[0])[~owned])
    assert not np.any(y[~owned])


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_no_mask_is_every_row_owned(step_cases, form):
    args, unmasked = step_cases["small"]
    state, y = STEP_FORMS[form](*args, None)
    assert np.array_equal(state, unmasked[form][0])
    assert np.array_equal(y, unmasked[form][1])
    # the two forms are one arithmetic
    np.testing.assert_allclose(unmasked["pallas"][0], unmasked["jnp"][0],
                               atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(unmasked["pallas"][1], unmasked["jnp"][1],
                               atol=2e-5)


def test_the_kernel_updates_a_donated_state_where_it_lies(step_cases):
    """The state's buffer is the output's: a donated state comes back
    with the owned rows moved and the other rows' bytes as they were."""
    args, unmasked = step_cases["small"]
    owned = np.asarray(MASKS["scattered"], bool)
    kept = np.asarray(args[0])
    step = jax.jit(lambda *a: ssm.ssm_step_kernel(*a, interpret=True),
                   donate_argnums=(0,))
    state, _ = step(jnp.array(kept), *args[1:], jnp.asarray(owned))
    assert np.array_equal(np.asarray(state)[owned],
                          unmasked["pallas"][0][owned])
    assert np.array_equal(np.asarray(state)[~owned], kept[~owned])


def test_off_the_tpu_the_dispatch_takes_the_plain_forms():
    args = operands(2, 64)
    y, h, ran = ssm.ssd(*args, jnp.asarray([64, 5], jnp.int32), chunk=32)
    assert y.shape == args[0].shape and ran.tolist() == [64, 64]
    after, out = ssm.ssm_step(h, args[0][:, 0], args[1][:, 0], args[2],
                              args[3][:, 0], args[4][:, 0], args[5])
    assert after.shape == h.shape and out.shape == args[0][:, 0].shape


def test_the_kernels_gates_name_the_published_shape():
    x = jax.ShapeDtypeStruct((4, 2048, 8192), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((4, 2048, 128), jnp.float32)
    assert ssm._ssd_kernel_applies(x, dt, 256)
    assert ssm._ssd_group_heads(128, 64) == 32
    assert ssm._step_kernel_applies(
        jax.ShapeDtypeStruct((32, 128, 8192), jnp.float32))
    # a head wider than a lane tile, or a chunk that is no whole tile
    assert not ssm._ssd_kernel_applies(
        jax.ShapeDtypeStruct((1, 64, 4 * 192), jnp.float32),
        jax.ShapeDtypeStruct((1, 64, 4), jnp.float32), 256)
    assert not ssm._ssd_kernel_applies(x, dt, 96)
