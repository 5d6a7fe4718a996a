"""ops/kda.py: the chunked delta rule, in plain jnp and as the Pallas
kernel (interpret mode), against the token-by-token recurrence; the
one-token step against a recurrence that goes one token further, and the
step's Pallas kernel (interpret mode) against both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.ops import kda

HEADS, DK, DV, CHUNK = 2, 16, 128, 64
SEQ = 150                                        # not whole chunks


def operands(batch, seq, seed=0, decay=None, dk=DK):
    """q and k of unit length a head (q times d_k ** -0.5), v of unit
    scale, the log decay a channel in (-5, 0) (`decay`: that value on
    every channel), beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (batch, seq, HEADS, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, HEADS, dk)))
    v = jax.random.normal(ks[2], (batch, seq, HEADS, DV))
    g = -5.0 * jax.nn.sigmoid(
        4.0 * jax.random.normal(ks[3], (batch, seq, HEADS, dk)))
    if decay is not None:
        g = jnp.full_like(g, decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, HEADS)))
    return q, k, v, g, beta


def real_rows(lengths, seq):
    return (np.arange(seq)[None, :]
            < np.asarray(lengths)[:, None])[..., None, None]


# lengths under, at and over a chunk's edge and a sub-block's (16), a whole
# buffer, rows of length 0
LENGTHS = [(150, 0, 65), (63, 64, 65), (1, 128, 129), (0, 0, 3),
           (15, 16, 17)]


def chunk_kernel(*args, **kw):
    return kda.kda_chunk_kernel(*args, **kw, interpret=True)


# the prefill's two chunked forms, each with the key channels it is tried
# at: the kernel at the whole lane tile its gate asks for
PREFILL = [pytest.param(kda.kda_chunked, DK, id="jnp"),
           pytest.param(chunk_kernel, 128, id="pallas")]


@pytest.mark.parametrize("form, dk", PREFILL)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_the_chunked_form_is_the_recurrence(lengths, form, dk):
    args = operands(len(lengths), SEQ, dk=dk)
    lengths = jnp.asarray(lengths, jnp.int32)
    want_o, want_s = kda.kda_reference(*args, lengths)
    got_o, got_s, _ = form(*args, lengths, chunk=CHUNK)
    real = real_rows(lengths, SEQ)
    np.testing.assert_allclose(np.where(real, got_o, 0),
                               np.where(real, want_o, 0), atol=2e-5)
    # the state handed on is the state after each example's last REAL
    # token: the padding behind it moved nothing, a row of length 0 is 0
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    assert not np.any(np.asarray(got_s)[np.asarray(lengths) == 0])
    assert float(jnp.std(want_o)) > 100 * 2e-5     # not zeros


@pytest.mark.parametrize("form, dk", PREFILL)
@pytest.mark.parametrize("decay", [-4.999, -2.5, -1e-4],
                         ids=["forgets_at_once", "middle", "never_forgets"])
def test_a_decay_at_either_end_of_its_range_stays_finite_and_right(
        decay, form, dk):
    """-5 a token is -320 a chunk: exp(-G) alone overflows float32, the
    sub-blocks' own references keep every exponent under 75; and a decay
    of nearly 1 keeps the whole chunk's history."""
    args = operands(2, SEQ, seed=1, decay=decay, dk=dk)
    lengths = jnp.asarray([150, 70], jnp.int32)
    want_o, want_s = kda.kda_reference(*args, lengths)
    got_o, got_s, _ = form(*args, lengths, chunk=CHUNK)
    assert np.isfinite(np.asarray(got_o)).all()
    real = real_rows(lengths, SEQ)
    np.testing.assert_allclose(np.where(real, got_o, 0),
                               np.where(real, want_o, 0), atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_the_chunk_size_moves_nothing_and_the_form_says_what_it_ran():
    args = operands(3, SEQ, seed=2)
    lengths = jnp.asarray([40, 100, 0], jnp.int32)
    o64, s64, ran64 = kda.kda_chunked(*args, lengths, chunk=64)
    o32, s32, ran32 = kda.kda_chunked(*args, lengths, chunk=32)
    real = real_rows(lengths, SEQ)
    np.testing.assert_allclose(np.where(real, o64, 0), np.where(real, o32, 0),
                               atol=2e-5)
    np.testing.assert_allclose(s64, s32, atol=2e-5)
    # every example of the group runs to the group's longest, in whole
    # chunks: 100 tokens are 2 chunks of 64 and 4 of 32
    assert ran64.tolist() == [128] * 3 and ran32.tolist() == [128] * 3
    with pytest.raises(ValueError, match="whole sub-blocks"):
        kda.kda_chunked(*args, lengths, chunk=24)


@pytest.mark.parametrize("chunk", [64, 32, 128])
def test_the_kernel_runs_each_example_its_own_chunks(chunk):
    """... and says so: the third result is ceil(length / chunk) chunks
    an example, whatever the group's longest; the chunk size moves
    nothing (a chunk of 32 is two sub-blocks, one of 128 eight: the
    forward substitution over them at each depth)."""
    args = operands(4, SEQ, seed=2, dk=128)
    lengths = jnp.asarray([40, 100, 0, 150], jnp.int32)
    want_o, want_s, _ = kda.kda_chunked(*args, lengths, chunk=64)
    o, s, ran = chunk_kernel(*args, lengths, chunk=chunk)
    assert ran.tolist() == [-(-n // chunk) * chunk for n in (40, 100, 0, 150)]
    real = real_rows(lengths, SEQ)
    np.testing.assert_allclose(np.where(real, o, 0), np.where(real, want_o, 0),
                               atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("form, dk", PREFILL)
def test_an_example_of_length_0_beside_full_ones_stays_zero(form, dk):
    args = operands(3, SEQ, seed=6, dk=dk)
    lengths = jnp.asarray([SEQ, 0, SEQ], jnp.int32)
    o, s, _ = form(*args, lengths, chunk=CHUNK)
    assert not np.any(np.asarray(o)[1]) and not np.any(np.asarray(s)[1])
    want_o, want_s = kda.kda_reference(*(x[::2] for x in args))
    np.testing.assert_allclose(np.asarray(o)[::2], want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s)[::2], want_s, atol=2e-5)


def test_the_kernel_reads_no_chunk_past_an_example_s_last():
    """A NaN planted in q, k and v behind an example's last chunk spreads
    nowhere: the chunk is neither fetched nor run, its rows of o are
    zeros."""
    args = operands(3, SEQ, seed=7, dk=128)
    lengths = jnp.asarray([100, 0, 64], jnp.int32)
    ran = np.asarray([128, 0, 64])
    past = np.arange(SEQ)[None, :, None, None] >= ran[:, None, None, None]
    q, k, v, g, beta = args
    planted = tuple(jnp.where(past, jnp.nan, x) for x in (q, k, v))
    want = chunk_kernel(*args, lengths, chunk=CHUNK)
    got = chunk_kernel(*planted, g, beta, lengths, chunk=CHUNK)
    assert np.isnan(np.asarray(planted[0])).any()
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert not np.any(np.where(past, np.asarray(got[0]), 0))


def test_the_chunk_kernel_takes_the_shapes_it_is_written_for():
    """Ling's (4, 2048, 32, 128) float32; not half a lane tile of key
    channels, a chunk that is not whole sub-blocks, heads that are not
    whole groups or operands that are not float32: those take the jnp
    form, which off the TPU is what `kda_prefill` IS."""
    def shaped(h=32, dk=128, dtype=jnp.float32):
        key = jax.ShapeDtypeStruct((4, 2048, h, dk), dtype)
        return key, key, jax.ShapeDtypeStruct((4, 2048, h, 128), dtype), key

    assert kda._chunk_kernel_applies(*shaped(), 64)
    assert not kda._chunk_kernel_applies(*shaped(dk=64), 64)
    assert not kda._chunk_kernel_applies(*shaped(), 24)
    assert not kda._chunk_kernel_applies(*shaped(h=12), 64)
    assert not kda._chunk_kernel_applies(*shaped(dtype=jnp.bfloat16), 64)
    args = operands(3, SEQ, seed=8, dk=128)
    lengths = jnp.asarray([40, 100, 0], jnp.int32)
    got = kda.kda_prefill(*args, lengths, chunk=CHUNK)
    want = kda.kda_chunked(*args, lengths, chunk=CHUNK)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))


def step(state, args, at, owned=None, form=kda.kda_step_reference):
    q, k, v, g, beta = (x[:, at] for x in args)
    return form(state, q, k, v, g, beta, owned)


def kernel(*args):
    return kda.kda_step_kernel(*args, interpret=True)


@pytest.mark.parametrize("form", [kda.kda_step_reference, kernel],
                         ids=["jnp", "pallas"])
def test_a_step_is_one_more_token_of_the_recurrence(form):
    args = operands(3, 41, seed=3)
    want_o, want_s = kda.kda_reference(*args)
    before = kda.kda_reference(*(x[:, :40] for x in args))[1]
    state, o = step(before, args, 40, form=form)
    np.testing.assert_allclose(state, want_s, atol=1e-6)
    np.testing.assert_allclose(o, want_o[:, 40], atol=1e-6)


@pytest.mark.parametrize("form", [kda.kda_step_reference, kernel],
                         ids=["jnp", "pallas"])
@pytest.mark.parametrize("owned", [(True, False, True), (False, False, False),
                                   (False, True, False)])
def test_a_row_that_is_not_owned_keeps_its_bytes(form, owned):
    """`owned=`: a row that pads the batch is neither read nor written:
    its state comes back bit for bit (a NaN planted there stays one and
    spreads nowhere), its output is 0."""
    args = operands(3, 8, seed=4)
    before = np.array(kda.kda_reference(*args)[1])
    owned = np.asarray(owned)
    before[~owned, 0, 0, 0] = np.nan
    state, o = step(jnp.asarray(before), args, 7, jnp.asarray(owned), form)
    want_s, want_o = step(jnp.asarray(np.nan_to_num(before)), args, 7)
    state, o = np.asarray(state), np.asarray(o)
    assert np.array_equal(state[~owned], before[~owned], equal_nan=True)
    assert not np.any(o[~owned])
    np.testing.assert_allclose(state[owned], np.asarray(want_s)[owned],
                               atol=1e-6)
    np.testing.assert_allclose(o[owned], np.asarray(want_o)[owned],
                               atol=1e-6)


def test_the_kernel_takes_the_shapes_it_is_written_for():
    """Whole lane tiles of key and value channels (the turn of a row into
    columns is of whole tiles); anything else takes the jnp step."""
    assert kda._step_kernel_applies(jnp.zeros((2, 32, 128, 128)))
    assert not kda._step_kernel_applies(jnp.zeros((2, 32, 64, 128)))
    assert not kda._step_kernel_applies(jnp.zeros((2, 12, 128, 128)))
    # ... and off the TPU `kda_step` is the jnp step
    args = operands(2, 4, seed=5)
    state = kda.kda_reference(*args)[1]
    got = step(state, args, 3, form=kda.kda_step)
    want = step(state, args, 3)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
