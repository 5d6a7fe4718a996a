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
    _rows_kernel_applies,
    attention,
    attention_reference,
    flash_attention,
    gather_kv_pages,
    paged_attention_reference,
    paged_flash_attention,
    paged_prefill_attention,
    rows_block,
    rows_copied,
    rows_flash_attention,
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


def _token_rows(x):
    """Dense (B, H, S, D) -> (B, S, H * D): one arena row a token."""
    b, h, s, d = x.shape
    return jnp.asarray(x).transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _paged_case(seed, *, b, h, d, block_size, max_len, sq=1,
                dtype=np.float32):
    """Random ragged case: contiguous K/V, the same values written through
    `PagedKV.append` into arenas from `PagedKV.arena` behind shuffled block
    tables (the arenas' last page is the trash page, all zeros), and
    per-example lengths."""
    rng = np.random.default_rng(seed)
    pages_per_seq = -(-max_len // block_size)
    padded = pages_per_seq * block_size
    k = jnp.asarray(rng.standard_normal((b, h, padded, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, padded, d)), dtype)
    lengths = rng.integers(sq, max_len + 1, (b,)).astype(np.int32)
    n_pages = b * pages_per_seq
    tables = jnp.asarray(
        rng.permutation(n_pages).reshape(b, pages_per_seq), jnp.int32)
    kv = PagedKV(
        {name: PagedKV.arena(n_pages, block_size, (h, d), dtype)
         for name in ("k", "v")},
        tables, jnp.zeros((b,), jnp.int32), block_size=block_size,
        trash=n_pages).append({"k": _token_rows(k), "v": _token_rows(v)})
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), dtype)
    return (q, k, v, kv.arenas["k"], kv.arenas["v"], tables,
            jnp.asarray(lengths))


class TestPagedAttention:
    def test_gather_reconstructs_layout(self):
        q, k, v, k_pages, v_pages, tables, _ = _paged_case(
            0, b=2, h=2, d=8, block_size=4, max_len=16)
        np.testing.assert_array_equal(
            np.asarray(gather_kv_pages(k_pages, tables, 2)), np.asarray(k))
        np.testing.assert_array_equal(
            np.asarray(gather_kv_pages(v_pages, tables, 2)), np.asarray(v))

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

    def _ragged_case(self, sq, with_bias, *, h=3, d=16, trash_fill=0.0):
        """Live slots among slots that do not ride: lengths 0 with every
        table entry the trash page, one live slot ending exactly on a
        page boundary and one a token past it. The trash page (the
        arena's last, where live rows' trailing entries point too) holds
        `trash_fill`."""
        bs = 8
        lengths = np.asarray([0, 16, 17, 0, 40, 0], np.int32)
        q, _, _, k_pages, v_pages, tables, _ = _paged_case(
            40 + sq, b=len(lengths), h=h, d=d, block_size=bs, max_len=48,
            sq=sq)
        trash = k_pages.shape[0] - 1
        k_pages = k_pages.at[trash].set(trash_fill)
        v_pages = v_pages.at[trash].set(trash_fill)
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
        q, kp, vp, tbl, lengths, bias = self._ragged_case(sq, True, h=h,
                                                          d=64)
        want = paged_flash_attention(q, kp, vp, tbl, lengths, bias=bias,
                                     interpret=True)
        two_heads = attention_module._paged_step_vmem_bytes(
            2, sq, q.shape[-1], kp.shape[1], kp.dtype.itemsize)
        monkeypatch.setattr(attention_module, "_PAGED_STEP_VMEM_BYTES",
                            two_heads)
        # Two heads of 64 lanes are one 128-lane tile of a page's rows; one
        # head alone would be half a tile, which the group never is.
        assert _paged_head_group(h, sq, q.shape[-1], kp.shape[1],
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
                     trash=kp.shape[0] - 1, active=active)
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


class TestArenaLayout:
    """`PagedKV.arena`: (pages + 1, block_size, H * D), one row a token."""

    def test_arena_is_token_major_and_lane_dense(self):
        assert PagedKV.arena_shape(1024, 16, (16, 64)) == (1025, 16, 1024)
        arena = PagedKV.arena(6, 4, (2, 8), jnp.bfloat16)
        assert arena.shape == (7, 4, 16) and arena.dtype == jnp.bfloat16
        assert not np.asarray(arena, np.float32).any()

    # (h, d, block_size, width, sq): rows of 128, 512 and 1024 lanes (T5-
    # small's and T5-large's), and the 64 heads x 128 shape whose step the
    # VMEM bound splits into groups of 16.
    @pytest.mark.parametrize("h,d,block_size,width,sq", [
        (2, 64, 16, 3, 1), (8, 64, 16, 3, 5), (16, 64, 16, 2, 1),
        (16, 64, 16, 2, 16), (64, 128, 128, 2, 16)])
    @pytest.mark.parametrize("with_bias", [False, True])
    def test_kernel_matches_oracle_on_the_served_rows(
            self, h, d, block_size, width, sq, with_bias):
        b = 2
        q, _, _, kp, vp, tables, lengths = _paged_case(
            h + sq, b=b, h=h, d=d, block_size=block_size,
            max_len=width * block_size, sq=sq, dtype=jnp.bfloat16)
        assert kp.shape[-1] == h * d
        group = _paged_head_group(h, sq, d, block_size, 2)
        assert group == (16 if h == 64 else h)
        bias = _rand((b, h, sq, width * block_size), seed=3) \
            if with_bias else None
        want = np.asarray(paged_attention_reference(
            q, kp, vp, tables, lengths, bias=bias), np.float32)
        got = np.asarray(paged_flash_attention(
            q, kp, vp, tables, lengths, bias=bias, interpret=True),
            np.float32)
        # bfloat16 operands: the oracle rounds its probabilities to
        # bfloat16 before the second matmul, the kernel keeps float32.
        np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)

    def _old_append_and_gather(self, rows, tables, lengths, keep, *, h, d,
                               block_size, n_pages):
        """The parent's unit, (pages, H, block_size, D), written the
        parent's way (`arena.at[page, :, off]`) and gathered its way."""
        b, sq, _ = rows.shape
        arena = np.zeros((n_pages + 1, h, block_size, d), np.float32)
        for i in range(b):
            for r in range(sq):
                pos = int(lengths[i]) + r
                page = int(tables[i, pos // block_size]) \
                    if keep[i, r] else n_pages
                arena[page, :, pos % block_size] = \
                    np.asarray(rows[i, r]).reshape(h, d)
        g = arena[np.asarray(tables)]              # (B, W, H, bs, D)
        return arena, g.transpose(0, 2, 1, 3, 4).reshape(
            b, h, tables.shape[1] * block_size, d)

    def test_append_writes_what_the_old_unit_held(self):
        """A prefill chunk whose rows cross a page boundary, one slot's
        tail invalid, one slot not riding: the gathered (B, H, W*bs, D)
        view is bitwise what the parent's layout gave, the invalid rows
        are on the trash page and no other page was touched."""
        b, h, d, bs, width, sq = 3, 4, 16, 4, 3, 6
        n_pages = b * width
        rng = np.random.default_rng(5)
        tables = jnp.asarray(rng.permutation(n_pages).reshape(b, width),
                             jnp.int32)
        lengths = jnp.asarray([2, 3, 5], jnp.int32)  # rows cross pages
        row_valid = jnp.asarray([6, 4, 6], jnp.int32)
        active = jnp.asarray([True, True, False])
        rows = _rand((b, sq, h * d), seed=8)
        keep = (np.arange(sq)[None, :] < np.asarray(row_valid)[:, None]) \
            & np.asarray(active)[:, None]
        old_arena, want = self._old_append_and_gather(
            rows, np.asarray(tables), np.asarray(lengths), keep, h=h, d=d,
            block_size=bs, n_pages=n_pages)

        kv = PagedKV({"k": PagedKV.arena(n_pages, bs, (h, d), jnp.float32)},
                     tables, lengths, block_size=bs, trash=n_pages,
                     active=active)
        # (B, Sq, H, D) rows and (B, Sq, H * D) rows are the same rows.
        for given in (rows, rows.reshape(b, sq, h, d)):
            arena = kv.append({"k": given}, row_valid=row_valid).arenas["k"]
            np.testing.assert_array_equal(
                np.asarray(gather_kv_pages(arena, tables, h)), want)
            np.testing.assert_array_equal(
                np.asarray(arena).reshape(n_pages + 1, bs, h, d),
                old_arena.transpose(0, 2, 1, 3))
        # Slot 2 does not ride and slot 1's last two rows are invalid:
        # their pages hold nothing, the trash page took the rows.
        untouched = np.asarray(arena)[np.asarray(tables[2])]
        assert not untouched.any()
        assert np.asarray(arena)[n_pages].any()


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


# -- a few query rows over dense rows (a whole generation's cross-attention) ---

_ROWS_SEQ = 512
# Every example of a batch of two at one length, then the lengths mixed
# in one batch with two rows that pad it.
_ROWS_LENGTHS = [(n, n) for n in (0, 1, 127, 128, 129, 512)] + [
    (129, 0, 512, 1, 128, 0, 127, 300)]


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sq", [1, 5])
@pytest.mark.parametrize("lengths", _ROWS_LENGTHS,
                         ids=lambda n: "-".join(map(str, n)))
def test_rows_kernel_matches_reference_and_reads_by_length(lengths, sq, d):
    """`_rows_kernel` (interpret mode) against `attention_reference` over
    the same rows split into heads, at float32 rounding; an example of
    length 0 gives zeros; and no block past ceil(length / block) is
    read: the kernel's K and V hold NaN there."""
    h, f, b = 2, 2 * d, len(lengths)
    q = _rand((b, sq, f), 0)
    k, v = _rand((b, _ROWS_SEQ, f), 1), _rand((b, _ROWS_SEQ, f), 2)
    lengths = jnp.asarray(lengths, jnp.int32)
    block = rows_block(_ROWS_SEQ)
    assert block == 128
    unread = (jnp.arange(_ROWS_SEQ)[None, :]
              >= (-(-lengths // block) * block)[:, None])[:, :, None]
    got = rows_flash_attention(
        q, jnp.where(unread, jnp.nan, k), jnp.where(unread, jnp.nan, v),
        lengths, num_heads=h, interpret=True)

    def heads(x):
        return x.reshape(b, -1, h, d).transpose(0, 2, 1, 3)

    want = attention_reference(heads(q), heads(k), heads(v), lengths=lengths)
    want = want.transpose(0, 2, 1, 3).reshape(b, sq, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(got)[np.asarray(lengths) == 0], 0.0)
    # and the dispatcher, on this backend, is the reference over the rows
    np.testing.assert_array_equal(
        np.asarray(attention_module.attention_rows(
            q, k, v, lengths, num_heads=h)), np.asarray(want))


def test_rows_kernel_rounds_its_weights_as_the_reference_does():
    """bfloat16 rows: products into float32, the softmax weights rounded
    to the operands' dtype before they meet V, on both paths."""
    b, h, d = 3, 4, 64
    q = _rand((b, 1, h * d), 0, jnp.bfloat16)
    k = _rand((b, _ROWS_SEQ, h * d), 1, jnp.bfloat16)
    v = _rand((b, _ROWS_SEQ, h * d), 2, jnp.bfloat16)
    lengths = jnp.asarray([300, 0, 512], jnp.int32)
    got = rows_flash_attention(q, k, v, lengths, num_heads=h, scale=1.0,
                               interpret=True)
    want = attention_module.attention_rows(q, k, v, lengths, num_heads=h,
                                           scale=1.0)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2 ** -7)


# A read over a cache: the keys its last query row sees, on both sides of
# every edge of a 16-row tile and of a 128-row block.
_CACHE_KEYS = (1, 15, 16, 17, 38, 127, 128, 129, 255, 256)


# (sq, q_start, b): every edge in a batch of 3; the edges where the copies
# change in batches of 1, 4, 5 and 32 (a grid step holds up to
# `_ROWS_GROUP` examples: batches it divides and does not).
_CACHE_CASES = (
    [(1, keys - 1, 3) for keys in _CACHE_KEYS]
    + [(5, keys - 5, 3) for keys in (5,) + _CACHE_KEYS[1:] if keys != 38]
    + [(5, 126, 3)]
    + [(1, keys - 1, b) for b in (1, 4, 5, 32)
       for keys in (1, 16, 17, 128, 129, 256)]
    + [(5, keys - 5, b) for b in (4, 5) for keys in (16, 129, 256)])


@pytest.mark.parametrize("sq, q_start, b", _CACHE_CASES)
def test_rows_kernel_over_a_cache_with_bias_sees_no_key_past_its_row(
        sq, q_start, b):
    """`q_start`: row r of the block sits at q_start + r and sees the
    rows up to its own, under a bias every example shares (a decode step
    or a verify block of T5's self-attention): the kernel against
    `attention_reference` with the same bias and a causal offset, a
    layer of a stack, in batches that the examples of a grid step divide
    and do not; the rows past the 16-row tile that holds the block's last
    row hold NaN: the kernel copies the tiles its keys lie in and no
    row more."""
    h, d, s = 4, 64, 256
    assert rows_copied(q_start + sq, s) - (q_start + sq) < 16
    q = _rand((b, sq, h * d), 0)
    k, v = _rand((2, b, s, h * d), 1), _rand((2, b, s, h * d), 2)
    bias = _rand((1, h, sq, s), 3)
    lengths = jnp.full((b,), q_start + sq, jnp.int32)
    unread = (jnp.arange(s) >= rows_copied(q_start + sq, s))[:, None]
    got = rows_flash_attention(
        q, jnp.where(unread, jnp.nan, k), jnp.where(unread, jnp.nan, v),
        lengths, num_heads=h, layer=1, bias=bias,
        q_start=jnp.int32(q_start), interpret=True)

    def heads(x):
        return x.reshape(b, -1, h, d).transpose(0, 2, 1, 3)

    want = attention_reference(
        heads(q), heads(k[1]), heads(v[1]), lengths=lengths, bias=bias,
        causal=True, causal_offset=q_start)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want.transpose(0, 2, 1, 3).reshape(
            b, sq, h * d)), atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention_module.attention_rows(
            q, k, v, lengths, num_heads=h, layer=1, bias=bias,
            q_start=jnp.int32(q_start))), atol=2e-6, rtol=2e-6)


def test_rows_kernel_reads_a_layer_of_a_stack_where_it_lies():
    """`layer=`: K and V of every layer in one array each; the kernel's
    fetches pick the layer, and the dispatcher's reference slices it."""
    b, h, d, layers = 2, 2, 64, 3
    q = _rand((b, 1, h * d), 0)
    k = _rand((layers, b, _ROWS_SEQ, h * d), 1)
    v = _rand((layers, b, _ROWS_SEQ, h * d), 2)
    lengths = jnp.asarray([200, 512], jnp.int32)
    for layer in range(layers):
        got = rows_flash_attention(q, k, v, lengths, num_heads=h,
                                   layer=layer, interpret=True)
        alone = rows_flash_attention(q, k[layer], v[layer], lengths,
                                     num_heads=h, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(attention_module.attention_rows(
                q, k, v, lengths, num_heads=h, layer=layer)),
            atol=2e-6, rtol=2e-6)


# -- the kernels as the TPU compiler sees them --------------------------------
#
# Interpret mode never meets Mosaic's block-shape rules, and the dispatcher
# serves the jnp reference on this backend, so a kernel the chip refuses
# passes every test above. Cross-lowering for the TPU platform applies
# those rules here, at the shapes the server really sends.


def _arena_struct(pages, block_size, h, d, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(
        PagedKV.arena_shape(pages, block_size, (h, d)), dtype)


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


@pytest.mark.parametrize("b", [32, 5, 1])
@pytest.mark.parametrize("sq", [1, 5])
@pytest.mark.parametrize("s, cached", [(512, False), (256, True)])
def test_rows_read_lowers_for_tpu_at_t5_large_shapes(s, cached, sq, b):
    """The two reads of `t5-large.generate`'s decode step (32 examples of
    16 heads x 64): cross-attention over 512 rows, self-attention over a
    cache of 256 under the relative position bias; a verify block of 5
    rows over each; and batches that the cache read's group of examples
    does not divide."""
    h, d = 16, 64
    q = jax.ShapeDtypeStruct((b, sq, h * d), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((24, b, s, h * d), jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32)
    bias = jax.ShapeDtypeStruct((1, h, sq, s), jnp.float32)
    assert _rows_kernel_applies(q, rows, h, bias if cached else None)

    def read(q, k, v, n, bias, q_start):
        return rows_flash_attention(
            q, k, v, n, num_heads=h, layer=23,
            **({"bias": bias, "q_start": q_start} if cached else {}))

    text = _lower_for_tpu(read, q, rows, rows, lengths, bias,
                          jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert "tpu_custom_call" in text and "_rows_kernel" in text


def test_a_cache_read_holds_the_examples_a_step_has_room_for():
    """`_rows_group`: `_ROWS_GROUP` examples a grid step at the cell's
    shapes, the batch where it is smaller, fewer where the step's VMEM
    account is short, one where only one fits; and what a generation of
    256 steps copies of one layer's 256 x 256 rows, to the tile."""
    group = attention_module._rows_group
    most = attention_module._ROWS_GROUP
    assert 2 <= most <= 8
    assert group(32, 1, 256, 1024, 2, 16, True) == most
    assert group(5, 1, 256, 1024, 2, 16, True) == min(5, most)
    assert group(1, 1, 256, 1024, 2, 16, True) == 1
    assert 2 <= group(32, 5, 256, 1024, 2, 16, True) <= most
    assert 1 <= group(32, 1, 1024, 1024, 2, 16, True) < most
    assert group(32, 1, 2048, 1024, 2, 16, True) == 1
    assert attention_module._rows_step_bytes(
        most, 1, 256, 1024, 2, 16, True) \
        <= attention_module._ROWS_GROUP_VMEM_BYTES \
        < attention_module._ROWS_VMEM_LIMIT_BYTES
    steps = np.arange(256)
    assert int(rows_copied(steps + 1, 256).sum()) == 34_816
    assert int((-(-(steps + 1) // 128) * 128).sum()) == 49_152
    assert rows_copied(250, 64) == 64


def test_rows_gate_refuses_what_the_kernel_cannot_hold():
    def applies(sq, s, f, h=16):
        return _rows_kernel_applies(
            jax.ShapeDtypeStruct((2, sq, f), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, s, f), jnp.bfloat16), h)

    assert applies(1, 512, 1024) and applies(5, 512, 1024)
    assert applies(1, 64, 128, h=2)           # one block of the whole 64
    assert not applies(1, 512, 64, h=2)       # rows under a lane tile
    assert not applies(1, 200, 1024)          # key rows not whole blocks
    assert not applies(1, 8, 128, h=2)        # a block under a sublane tile
    assert not applies(64, 512, 1024)         # 1,024 query rows: past VMEM
    assert not applies(1, 4096, 1024)         # two slots of K and V rows: too
    q = jax.ShapeDtypeStruct((2, 1, 1024), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((2, 256, 1024), jnp.bfloat16)
    assert _rows_kernel_applies(
        q, rows, 16, jax.ShapeDtypeStruct((1, 16, 1, 256), jnp.float32))
    assert not _rows_kernel_applies(      # a bias of its own an example
        q, rows, 16, jax.ShapeDtypeStruct((2, 16, 1, 256), jnp.float32))


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
    q = jax.ShapeDtypeStruct((b, h, sq, d), jnp.bfloat16)
    arena = _arena_struct(b * width, block_size, h, d)
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
    arena = _arena_struct(63, 16, 8, 64)
    tables = jax.ShapeDtypeStruct((8192, 30), jnp.int32)
    assert not _paged_kernel_applies(q, arena, tables)
    # A step of `_paged_kernel` holds a page of K and of V for a group of
    # heads: 64 heads of 128-token x 128-dim pages fit 16 at a time...
    assert _paged_head_group(64, 16, 128, 128, 2) == 16
    # ...and one head of a 4096-token page does not fit at all.
    q = jax.ShapeDtypeStruct((4, 8, 1, 128), jnp.bfloat16)
    arena = _arena_struct(16, 4096, 8, 128)
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
                  _arena_struct(8, 16, 8, 64),
                  jax.ShapeDtypeStruct((4, 2), jnp.int32))
    assert _paged_kernel_applies(*paged_args)
    with jax.set_mesh(mesh):
        assert not _paged_kernel_applies(*paged_args)
