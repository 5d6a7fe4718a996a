"""Flash attention kernel vs jnp reference (interpret mode on CPU mesh),
plus the ragged paged variants (block-table KV) vs the dense path."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.ops.attention import (
    PagedKV,
    _flash_kernel_applies,
    _paged_head_group,
    _paged_kernel_applies,
    attention,
    attention_reference,
    flash_attention,
    gather_kv_pages,
    paged_attention_reference,
    paged_flash_attention,
    paged_prefill_attention,
)


# `ops.attention` the module: the package exports the function under that name.
attention_module = importlib.import_module("min_tfs_client_tpu.ops.attention")


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    b, h, s, d = 2, 3, 256, 64
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    want = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_respects_lengths():
    b, h, s, d = 2, 2, 128, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    lengths = jnp.asarray([37, 128], jnp.int32)
    want = attention_reference(q, k, v, lengths=lengths)
    got = flash_attention(q, k, v, lengths=lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_unaligned_seq_padding():
    # Sequence not a multiple of the KV block: internal pad + mask.
    b, h, s, d = 1, 2, 200, 64
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    want = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.shape == (b, h, s, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_single_query_right_aligned():
    # KV-cache decode: one query attends to all 64 cached keys (causal
    # right-aligned), not just index 0.
    b, h, skv, d = 2, 2, 64, 32
    k, v = _rand((b, h, skv, d), 1), _rand((b, h, skv, d), 2)
    q = _rand((b, h, 1, d), 0)
    want = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_attention_dispatch_with_bias_uses_reference():
    b, h, s, d = 1, 2, 16, 8
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    bias = _rand((1, h, s, s), 9)
    out = attention(q, k, v, bias=bias)
    want = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def _paged_case(seed, *, b, h, d, block_size, max_len, sq=1):
    """Random ragged case: contiguous K/V, the same values scattered into
    a shuffled page arena + block tables, and per-example lengths."""
    rng = np.random.default_rng(seed)
    pages_per_seq = -(-max_len // block_size)
    padded = pages_per_seq * block_size
    k = rng.standard_normal((b, h, padded, d)).astype(np.float32)
    v = rng.standard_normal((b, h, padded, d)).astype(np.float32)
    lengths = rng.integers(sq, max_len + 1, (b,)).astype(np.int32)
    n_pages = b * pages_per_seq
    perm = rng.permutation(n_pages)
    k_pages = np.empty((n_pages, h, block_size, d), np.float32)
    v_pages = np.empty((n_pages, h, block_size, d), np.float32)
    tables = np.empty((b, pages_per_seq), np.int32)
    for i in range(b):
        for p in range(pages_per_seq):
            page = int(perm[i * pages_per_seq + p])
            tables[i, p] = page
            sl = slice(p * block_size, (p + 1) * block_size)
            k_pages[page] = k[i, :, sl]
            v_pages[page] = v[i, :, sl]
    q = rng.standard_normal((b, h, sq, d)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables), jnp.asarray(lengths))


class TestPagedAttention:
    def test_gather_reconstructs_layout(self):
        q, k, v, k_pages, v_pages, tables, _ = _paged_case(
            0, b=2, h=2, d=8, block_size=4, max_len=16)
        np.testing.assert_array_equal(
            np.asarray(gather_kv_pages(k_pages, tables)), np.asarray(k))
        np.testing.assert_array_equal(
            np.asarray(gather_kv_pages(v_pages, tables)), np.asarray(v))

    @pytest.mark.parametrize("block_size", [1, 8, 64])
    def test_oracle_token_exact_vs_dense(self, block_size):
        """Divisible page sizes: the gathered view IS the dense layout, so
        the oracle must be BITWISE equal to the dense reference."""
        for seed in range(4):
            q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
                seed, b=3, h=2, d=16, block_size=block_size, max_len=64)
            want = attention_reference(q, k, v, lengths=lengths)
            got = paged_attention_reference(q, k_pages, v_pages, tables,
                                            lengths)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("block_size,max_len", [(3, 13), (8, 20),
                                                    (64, 70)])
    def test_oracle_with_non_divisible_tail(self, block_size, max_len):
        """Non-divisible tails pad the gathered view past max_len; the
        padded keys are masked, so outputs match the dense reference over
        the same padded length."""
        for seed in range(4):
            q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
                seed, b=2, h=2, d=16, block_size=block_size,
                max_len=max_len)
            want = attention_reference(q, k, v, lengths=lengths)
            got = paged_attention_reference(q, k_pages, v_pages, tables,
                                            lengths)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("block_size", [4, 8])
    def test_pallas_kernel_matches_oracle(self, block_size):
        for seed in range(3):
            q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
                seed, b=2, h=3, d=16, block_size=block_size, max_len=32)
            want = paged_attention_reference(q, k_pages, v_pages, tables,
                                            lengths)
            got = paged_flash_attention(q, k_pages, v_pages, tables,
                                        lengths, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5, rtol=2e-5)

    def test_pallas_kernel_multi_query_block(self):
        """Sq>1 (a speculative verify block): row r attends keys
        < lengths - (Sq-1-r); the kernel must agree with the oracle."""
        q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
            5, b=2, h=2, d=16, block_size=4, max_len=24, sq=3)
        want = paged_attention_reference(q, k_pages, v_pages, tables,
                                         lengths)
        got = paged_flash_attention(q, k_pages, v_pages, tables, lengths,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_fuzz_ragged_mixes(self):
        """Random (batch, heads, block size, ragged lengths) mixes: the
        oracle stays exact vs dense and the kernel stays within kernel
        tolerance of the oracle."""
        rng = np.random.default_rng(1234)
        for _ in range(8):
            b = int(rng.integers(1, 4))
            h = int(rng.integers(1, 4))
            block_size = int(rng.choice([1, 2, 4, 8]))
            max_len = int(rng.integers(block_size, 40))
            q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
                int(rng.integers(1 << 30)), b=b, h=h, d=8,
                block_size=block_size, max_len=max_len)
            want = attention_reference(q, k, v, lengths=lengths)
            got = paged_attention_reference(q, k_pages, v_pages, tables,
                                            lengths)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            kern = paged_flash_attention(q, k_pages, v_pages, tables,
                                         lengths, interpret=True)
            np.testing.assert_allclose(np.asarray(kern), np.asarray(got),
                                       atol=2e-5, rtol=2e-5)

    def test_bias_parity_kernel_vs_oracle(self):
        """Additive bias (T5's relative position bias over gathered key
        positions) streams per page through the kernel; interpret-mode
        parity against the oracle's post-scale add."""
        rng = np.random.default_rng(21)
        q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
            21, b=2, h=2, d=16, block_size=4, max_len=24, sq=2)
        bias = jnp.asarray(rng.standard_normal(
            (2, 2, 2, tables.shape[1] * 4)), jnp.float32)
        want = paged_attention_reference(q, k_pages, v_pages, tables,
                                         lengths, bias=bias)
        got = paged_flash_attention(q, k_pages, v_pages, tables, lengths,
                                    bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def _chunk_case(self, seed, *, sq, starts, lens_valid, block_size=4,
                    max_len=24, with_bias=False):
        """Chunked-prefill fixture: q rows are chunk positions starting at
        `starts`, only the first `lens_valid` rows real per example."""
        rng = np.random.default_rng(seed)
        q, _, _, k_pages, v_pages, tables, _ = _paged_case(
            seed, b=len(starts), h=2, d=16, block_size=block_size,
            max_len=max_len, sq=sq)
        starts = jnp.asarray(starts, jnp.int32)
        lens_valid = jnp.asarray(lens_valid, jnp.int32)
        bias = None
        if with_bias:
            bias = jnp.asarray(rng.standard_normal(
                (len(starts), 2, sq, tables.shape[1] * block_size)),
                jnp.float32)
        return q, k_pages, v_pages, tables, starts, lens_valid, bias

    def test_chunked_prefill_parity_smoke(self):
        """Tier-1 smoke for the Sq>1 chunked-prefill path: a divisible
        chunk, a NON-DIVISIBLE final chunk (valid rows < Sq), and a
        zero-length row, kernel (interpret) vs oracle."""
        q, kp, vp, tbl, starts, lens_valid, bias = self._chunk_case(
            31, sq=4, starts=[0, 9, 4], lens_valid=[4, 2, 0],
            with_bias=True)
        want = paged_prefill_attention(q, kp, vp, tbl, starts, lens_valid,
                                       bias=bias)
        got = paged_flash_attention(
            q, kp, vp, tbl, starts + lens_valid, bias=bias,
            q_start=starts, interpret=True)
        # Rows past lens_valid are padding whose outputs the pool
        # discards; compare the real rows only.
        lv = np.asarray(lens_valid)
        for i in range(len(lv)):
            np.testing.assert_allclose(
                np.asarray(got)[i, :, :lv[i]],
                np.asarray(want)[i, :, :lv[i]], atol=2e-5, rtol=2e-5)
        # Zero-length rows emit finite zeros on both paths.
        np.testing.assert_array_equal(np.asarray(want)[2, :, :0], 0.0)
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.slow
    @pytest.mark.parametrize("block_size,sq", [(2, 3), (4, 4), (4, 8),
                                               (8, 5)])
    def test_chunked_prefill_parity_sweep(self, block_size, sq):
        """Full sweep across chunk sizes/page sizes incl. ragged starts —
        slow; tier-1 keeps the smoke above."""
        rng = np.random.default_rng(block_size * 100 + sq)
        for seed in range(4):
            b = int(rng.integers(1, 4))
            starts = rng.integers(0, 12, (b,)).tolist()
            lens_valid = rng.integers(0, sq + 1, (b,)).tolist()
            q, kp, vp, tbl, st, lv, bias = self._chunk_case(
                seed, sq=sq, starts=starts, lens_valid=lens_valid,
                block_size=block_size, with_bias=bool(seed % 2))
            want = paged_attention_reference(q, kp, vp, tbl, st + lv,
                                             bias=bias, q_start=st)
            got = paged_flash_attention(q, kp, vp, tbl, st + lv, bias=bias,
                                        q_start=st, interpret=True)
            lvn = np.asarray(lv)
            for i in range(b):
                np.testing.assert_allclose(
                    np.asarray(got)[i, :, :lvn[i]],
                    np.asarray(want)[i, :, :lvn[i]], atol=2e-5, rtol=2e-5)

    def _ragged_case(self, sq, with_bias, *, h=3, trash_fill=0.0):
        """Live slots among slots that do not ride: lengths 0 with every
        table entry the trash page, one live slot ending exactly on a
        page boundary and one a token past it. The trash page (the
        arena's last, where live rows' trailing entries point too) holds
        `trash_fill`."""
        bs, d = 8, 16
        lengths = np.asarray([0, 16, 17, 0, 40, 0], np.int32)
        q, _, _, k_pages, v_pages, tables, _ = _paged_case(
            40 + sq, b=len(lengths), h=h, d=d, block_size=bs, max_len=48,
            sq=sq)
        trash = k_pages.shape[0]
        fill = jnp.full((1, h, bs, d), trash_fill, jnp.float32)
        k_pages = jnp.concatenate([k_pages, fill])
        v_pages = jnp.concatenate([v_pages, fill])
        used = -(-lengths // bs)
        tables = jnp.where(
            jnp.arange(tables.shape[1])[None, :] < used[:, None],
            tables, trash)
        bias = None
        if with_bias:
            bias = _rand((len(lengths), h, sq, tables.shape[1] * bs),
                         seed=sq)
        return q, k_pages, v_pages, tables, jnp.asarray(lengths), bias

    @pytest.mark.parametrize("with_bias", [False, True])
    @pytest.mark.parametrize("sq", [1, 5, 16])
    def test_kernel_reads_no_entry_past_a_slots_keys(self, sq, with_bias):
        """The kernel must agree with the oracle row for row and emit
        zeros for the slots that do not ride — with the trash page full
        of NaN: an entry past a slot's last page is neither fetched nor
        computed on (the oracle, which gathers every entry, sees a clean
        trash page)."""
        q, kp, vp, tbl, lengths, bias = self._ragged_case(sq, with_bias)
        want = np.asarray(paged_attention_reference(
            q, kp, vp, tbl, lengths, bias=bias))
        _, kp_nan, vp_nan, _, _, _ = self._ragged_case(
            sq, with_bias, trash_fill=np.nan)
        got = np.asarray(paged_flash_attention(
            q, kp_nan, vp_nan, tbl, lengths, bias=bias, interpret=True))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(got[np.asarray(lengths) == 0], 0.0)

    @pytest.mark.parametrize("sq", [1, 5])
    def test_head_groups_when_a_step_cannot_hold_every_head(
            self, sq, monkeypatch):
        """Where all heads of a page do not fit the step's VMEM bound the
        grid keeps a head-group axis: same answers."""
        h = 4
        q, kp, vp, tbl, lengths, bias = self._ragged_case(sq, True, h=h)
        want = paged_flash_attention(q, kp, vp, tbl, lengths, bias=bias,
                                     interpret=True)
        one_head = attention_module._paged_step_vmem_bytes(
            1, sq, q.shape[-1], kp.shape[-2], kp.dtype.itemsize)
        monkeypatch.setattr(attention_module, "_PAGED_STEP_VMEM_BYTES",
                            2 * one_head)
        assert _paged_head_group(h, sq, q.shape[-1], kp.shape[-2],
                                 kp.dtype.itemsize) == 2
        got = paged_flash_attention(q, kp, vp, tbl, lengths, bias=bias,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(paged_attention_reference(
                q, kp, vp, tbl, lengths, bias=bias)), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("explicit", [False, True])
    def test_attend_gives_slots_that_do_not_ride_length_zero(self, explicit):
        """A handle that carries `active` attends with length 0 for the
        other slots, on the default lengths and on a caller's own (T5
        passes its own): their rows are zeros, the riders' unchanged."""
        q, kp, vp, tbl, lengths, bias = self._ragged_case(1, True)
        written = jnp.maximum(lengths - 1, 0)  # tokens before this step
        active = jnp.asarray([False, True, False, False, True, False])
        kv = PagedKV({"k": kp, "v": vp}, tbl, written, block_size=8,
                     trash=kp.shape[0] - 1, row_axes={"k": 2, "v": 2},
                     active=active)
        kwargs = {"lengths": written + 1, "q_start": written} \
            if explicit else {}
        got = np.asarray(kv.attend(q, "k", "v", bias=bias, **kwargs))
        want = np.asarray(paged_attention_reference(
            q, kp, vp, tbl, written + 1, bias=bias))
        np.testing.assert_array_equal(got[np.asarray(active)],
                                      want[np.asarray(active)])
        np.testing.assert_array_equal(got[~np.asarray(active)], 0.0)
        assert np.abs(want[2]).max() > 0  # slot 2 is live, but idle

    def test_zero_length_rows_are_zero(self):
        q, k, v, k_pages, v_pages, tables, lengths = _paged_case(
            7, b=2, h=2, d=8, block_size=4, max_len=16)
        lengths = jnp.asarray([0, 9], jnp.int32)
        ref = np.asarray(paged_attention_reference(
            q, k_pages, v_pages, tables, lengths))
        kern = np.asarray(paged_flash_attention(
            q, k_pages, v_pages, tables, lengths, interpret=True))
        assert np.isfinite(ref).all() and np.isfinite(kern).all()
        np.testing.assert_array_equal(ref[0], 0.0)
        np.testing.assert_array_equal(kern[0], 0.0)


def test_fully_masked_rows_are_zero_in_both_paths():
    # lengths[b]=0 (e.g. cross-attention over an empty input) must yield
    # zeros — not NaN, not a mean over masked V — identically on both paths.
    b, h, s, d = 2, 2, 64, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    lengths = jnp.asarray([0, 40], jnp.int32)
    ref = np.asarray(attention_reference(q, k, v, lengths=lengths))
    fl = np.asarray(flash_attention(q, k, v, lengths=lengths, interpret=True))
    assert np.isfinite(ref).all() and np.isfinite(fl).all()
    np.testing.assert_array_equal(ref[0], 0.0)
    np.testing.assert_array_equal(fl[0], 0.0)
    np.testing.assert_allclose(fl[1], ref[1], atol=2e-5, rtol=2e-5)


# -- the kernels as the TPU compiler sees them --------------------------------
#
# Interpret mode never meets Mosaic's block-shape rules, and the dispatcher
# serves the jnp reference on this backend, so a kernel the chip refuses
# passes every test above. Cross-lowering for the TPU platform applies
# those rules here, at the shapes the server really sends.


def _lower_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def test_flash_lowers_for_tpu_at_bert_base_shapes():
    b, h, s, d = 32, 12, 128, 64
    qkv = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32)
    assert _flash_kernel_applies(qkv, qkv)
    text = _lower_for_tpu(
        lambda q, k, v, n: flash_attention(q, k, v, lengths=n),
        qkv, qkv, qkv, lengths).as_text()
    assert "tpu_custom_call" in text


# (b, h, d, block_size, width, sq): the page sizes the gate admits at
# table widths 1, 2 and 4; the served shape of t5-large.sessions (64 slots
# of 16 heads, 16-token pages, width 16) for the decode tick (sq 1) and a
# prefill chunk (sq 16); and a shape whose heads the VMEM bound splits
# into groups of 16.
_PAGED_LOWERING_CASES = [
    (4, 8, 64, block_size, width, sq)
    for block_size in (8, 16, 32, 128) for width in (1, 2, 4)
    for sq in (1, 5)
] + [(64, 16, 64, 16, 16, 1), (64, 16, 64, 16, 16, 16),
     (4, 64, 128, 128, 4, 16)]


@pytest.mark.parametrize("b,h,d,block_size,width,sq", _PAGED_LOWERING_CASES)
def test_paged_with_bias_lowers_for_tpu(b, h, d, block_size, width, sq):
    """T5's only path: bias, page sizes the gate admits, table wider than
    one page (the case Mosaic refused while the bias rode as one
    (Sq, P*bs) row per head)."""
    pages = b * width + 1
    q = jax.ShapeDtypeStruct((b, h, sq, d), jnp.bfloat16)
    arena = jax.ShapeDtypeStruct((pages, h, block_size, d), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((b, width), jnp.int32)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32)
    bias = jax.ShapeDtypeStruct((b, h, sq, width * block_size), jnp.float32)
    assert _paged_kernel_applies(q, arena, tables)
    text = _lower_for_tpu(
        lambda q, k, v, t, n, bias: paged_flash_attention(
            q, k, v, t, n, bias=bias),
        q, arena, arena, tables, lengths, bias).as_text()
    assert "tpu_custom_call" in text


def test_gates_refuse_what_the_kernels_cannot_hold():
    # 16k keys x 64 dims in bf16, double-buffered K and V: past VMEM.
    long_kv = jax.ShapeDtypeStruct((1, 2, 16384, 64), jnp.bfloat16)
    assert not _flash_kernel_applies(long_kv, long_kv)
    # 8192 sessions x 30 pages of table: past the 1 MiB of SMEM.
    q = jax.ShapeDtypeStruct((8192, 8, 1, 64), jnp.bfloat16)
    arena = jax.ShapeDtypeStruct((64, 8, 16, 64), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((8192, 30), jnp.int32)
    assert not _paged_kernel_applies(q, arena, tables)
    # A step of `_paged_kernel` holds a page of K and of V for a group of
    # heads: 64 heads of 128-token x 128-dim pages fit 16 at a time...
    assert _paged_head_group(64, 16, 128, 128, 2) == 16
    # ...and one head of a 4096-token page does not fit at all.
    q = jax.ShapeDtypeStruct((4, 8, 1, 128), jnp.bfloat16)
    arena = jax.ShapeDtypeStruct((17, 8, 4096, 128), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((4, 4), jnp.int32)
    assert _paged_head_group(8, 1, 128, 4096, 2) == 0
    assert not _paged_kernel_applies(q, arena, tables)


def test_flash_splits_over_the_ambient_serving_mesh():
    """Under `jax.set_mesh` the kernel runs per shard — batch over the
    data axis, heads over the model axis — inside a sharded jit, which
    XLA cannot arrange for a Mosaic kernel by itself."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    b, h, s, d = 8, 4, 32, 16
    q, k, v = (jax.device_put(
        _rand((b, h, s, d), i),
        NamedSharding(mesh, P("data", "model"))) for i in range(3))
    lengths = jnp.asarray([s, 5, 17, 1, 32, 9, 0, 20], jnp.int32)
    fn = jax.jit(lambda q, k, v, n: flash_attention(
        q, k, v, lengths=n, interpret=True))
    with jax.set_mesh(mesh):
        got = fn(q, k, v, lengths)
        jaxpr = str(jax.make_jaxpr(fn)(q, k, v, lengths))
    assert "shard_map" in jaxpr
    want = attention_reference(q, k, v, lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # The paged kernel has no such wrapper: under a mesh its gate says no.
    paged_args = (jax.ShapeDtypeStruct((4, 8, 1, 64), jnp.bfloat16),
                  jax.ShapeDtypeStruct((9, 8, 16, 64), jnp.bfloat16),
                  jax.ShapeDtypeStruct((4, 2), jnp.int32))
    assert _paged_kernel_applies(*paged_args)
    with jax.set_mesh(mesh):
        assert not _paged_kernel_applies(*paged_args)
