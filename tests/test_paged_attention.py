"""Paged attention decode kernel vs numpy reference (reference analog:
test/legacy_test/test_block_multihead_attention.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.pallas.paged_attention import (
    _dequant, _xla_paged_attention, paged_attention, paged_kv_write,
    quantize_kv_pages, ragged_paged_attention)


def _np_reference(q, k_pages, v_pages, block_tables, context_lens, scale):
    bsz, n_heads, d = q.shape
    n_kv, _, page, _ = k_pages.shape
    group = n_heads // n_kv
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(bsz):
        L = int(context_lens[b])
        n_pages_used = (L + page - 1) // page
        for h in range(n_heads):
            kv_h = h // group
            ks, vs = [], []
            for pi in range(n_pages_used):
                pid = int(block_tables[b, pi])
                ks.append(k_pages[kv_h, pid])
                vs.append(v_pages[kv_h, pid])
            K = np.concatenate(ks, axis=0)[:L]
            V = np.concatenate(vs, axis=0)[:L]
            s = (q[b, h].astype(np.float32) @ K.T.astype(np.float32)) * scale
            w = np.exp(s - s.max())
            w = w / w.sum()
            out[b, h] = w @ V.astype(np.float32)
    return out


def _setup(bsz=2, n_heads=4, n_kv=2, d=64, page=128, pages_per_seq=3,
           seed=0):
    rng = np.random.RandomState(seed)
    total_pages = bsz * pages_per_seq + 1
    q = rng.randn(bsz, n_heads, d).astype(np.float32)
    k_pages = rng.randn(n_kv, total_pages, page, d).astype(np.float32)
    v_pages = rng.randn(n_kv, total_pages, page, d).astype(np.float32)
    # distinct pages per sequence (page 0 left unused)
    bt = (1 + np.arange(bsz * pages_per_seq)
          .reshape(bsz, pages_per_seq)).astype(np.int32)
    lens = np.array([page * pages_per_seq - 7, page + 3][:bsz],
                    dtype=np.int32)
    return q, k_pages, v_pages, bt, lens


class TestPagedAttention:
    def test_kernel_matches_numpy(self):
        q, kp, vp, bt, lens = _setup()
        scale = q.shape[-1] ** -0.5
        out = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                              jnp.asarray(vp), jnp.asarray(bt),
                              jnp.asarray(lens), interpret=True,
                              use_kernel=True)
        ref = _np_reference(q, kp, vp, bt, lens, scale)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)

    def test_xla_path_matches_numpy(self):
        q, kp, vp, bt, lens = _setup(n_heads=8, n_kv=8, d=32, page=16,
                                     pages_per_seq=2, seed=3)
        scale = q.shape[-1] ** -0.5
        out = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                   jnp.asarray(vp), jnp.asarray(bt),
                                   jnp.asarray(lens), scale)
        ref = _np_reference(q, kp, vp, bt, lens, scale)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)

    def test_gqa_grouping(self):
        # group=4: kernel and XLA paths agree
        q, kp, vp, bt, lens = _setup(n_heads=8, n_kv=2, seed=5)
        out_k = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                jnp.asarray(vp), jnp.asarray(bt),
                                jnp.asarray(lens), interpret=True,
                                use_kernel=True)
        out_x = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                     jnp.asarray(vp), jnp.asarray(bt),
                                     jnp.asarray(lens),
                                     q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=2e-4, atol=2e-4)

    def test_short_context_masks_tail(self):
        # context shorter than one page: tail tokens must not contribute
        q, kp, vp, bt, lens = _setup(bsz=1, pages_per_seq=2)
        lens = np.array([5], dtype=np.int32)
        out = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                              jnp.asarray(vp), jnp.asarray(bt),
                              jnp.asarray(lens), interpret=True,
                              use_kernel=True)
        ref = _np_reference(q, kp, vp, bt, lens, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)


class TestPagedKVWrite:
    def test_append_roundtrip(self):
        q, kp, vp, bt, lens = _setup(bsz=2, n_kv=2, d=64, page=128,
                                     pages_per_seq=3)
        rng = np.random.RandomState(9)
        k_new = rng.randn(2, 2, 64).astype(np.float32)
        v_new = rng.randn(2, 2, 64).astype(np.float32)
        kp2, vp2 = paged_kv_write(jnp.asarray(kp), jnp.asarray(vp),
                                  jnp.asarray(k_new), jnp.asarray(v_new),
                                  jnp.asarray(bt), jnp.asarray(lens))
        kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
        for b in range(2):
            pos = int(lens[b])
            pid = int(bt[b, pos // 128])
            slot = pos % 128
            np.testing.assert_array_equal(kp2[:, pid, slot, :], k_new[b])
            np.testing.assert_array_equal(vp2[:, pid, slot, :], v_new[b])
        # attention over the extended context sees the new token
        lens2 = lens + 1
        out = paged_attention(jnp.asarray(q), jnp.asarray(kp2),
                              jnp.asarray(vp2), jnp.asarray(bt),
                              jnp.asarray(lens2), interpret=True,
                              use_kernel=True)
        ref = _np_reference(q, kp2, vp2, bt, lens2, 64 ** -0.5)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)


class TestEmptySlots:
    """Regression: a slot with ``context_lens == 0`` (inactive or
    freshly-joined in the serving engine) must return exact zeros, not
    whatever the uninitialized pages its stale block table points at
    contain — and never NaN (the all-masked softmax)."""

    def _empty_setup(self):
        q, kp, vp, bt, lens = _setup(bsz=2, n_heads=4, n_kv=2, d=32,
                                     page=16, pages_per_seq=2, seed=7)
        # slot 1 is empty but its block table is garbage, including ids
        # beyond the pool (the engine never sanitizes dead rows)
        bt = bt.copy()
        bt[1] = [9999, -3]
        lens = np.array([19, 0], dtype=np.int32)
        # poison the pool so any leak through the mask is visible
        kp = kp + 100.0
        vp = vp + 100.0
        return q, kp, vp, bt, lens

    def test_kernel_empty_slot_zeros(self):
        q, kp, vp, bt, lens = self._empty_setup()
        out = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens), interpret=True,
            use_kernel=True))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
        # the live row is still computed correctly next to the dead one
        ref = _np_reference(q[:1], kp, vp, bt[:1], lens[:1],
                            q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out[0], ref[0], rtol=2e-4, atol=2e-4)

    def test_xla_empty_slot_zeros(self):
        q, kp, vp, bt, lens = self._empty_setup()
        out = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens), use_kernel=False))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))

    def test_all_slots_empty(self):
        q, kp, vp, bt, lens = self._empty_setup()
        lens = np.zeros(2, dtype=np.int32)
        for kern in (True, False):
            out = np.asarray(paged_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(lens), interpret=True,
                use_kernel=kern))
            np.testing.assert_array_equal(out, np.zeros_like(out))


class TestPagedKVWriteChunk:
    def test_chunk_write_matches_scalar_writes(self):
        from paddle_tpu.incubate.nn.pallas.paged_attention import \
            paged_kv_write_chunk
        rng = np.random.RandomState(4)
        n_kv, pages, page, d = 2, 6, 8, 16
        kp = np.zeros((n_kv, pages, page, d), np.float32)
        vp = np.zeros((n_kv, pages, page, d), np.float32)
        k_new = rng.randn(1, 5, n_kv, d).astype(np.float32)
        v_new = rng.randn(1, 5, n_kv, d).astype(np.float32)
        bt = np.array([[2, 4, 0]], np.int32)
        pos = np.array([[6, 7, 8, 9, 10]], np.int32)  # spans 2 pages
        kp2, vp2 = paged_kv_write_chunk(
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(k_new),
            jnp.asarray(v_new), jnp.asarray(bt), jnp.asarray(pos))
        kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
        for g in range(5):
            p = int(pos[0, g])
            pid = int(bt[0, p // page])
            np.testing.assert_array_equal(kp2[:, pid, p % page],
                                          k_new[0, g])
            np.testing.assert_array_equal(vp2[:, pid, p % page],
                                          v_new[0, g])
        # untouched slots stay zero
        assert np.abs(kp2).sum() == pytest.approx(
            np.abs(k_new).sum(), rel=1e-6)

    def test_negative_positions_are_dropped(self):
        from paddle_tpu.incubate.nn.pallas.paged_attention import \
            paged_kv_write_chunk
        rng = np.random.RandomState(5)
        n_kv, pages, page, d = 1, 3, 4, 8
        kp = np.zeros((n_kv, pages, page, d), np.float32)
        vp = np.zeros((n_kv, pages, page, d), np.float32)
        k_new = rng.randn(2, 1, n_kv, d).astype(np.float32)
        v_new = rng.randn(2, 1, n_kv, d).astype(np.float32)
        bt = np.array([[1, 2], [2, 0]], np.int32)
        pos = np.array([[-1], [3]], np.int32)     # row 0 inactive
        kp2, vp2 = paged_kv_write_chunk(
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(k_new),
            jnp.asarray(v_new), jnp.asarray(bt), jnp.asarray(pos))
        kp2 = np.asarray(kp2)
        np.testing.assert_array_equal(kp2[:, 1], 0.0)  # dropped write
        np.testing.assert_array_equal(kp2[0, 2, 3], k_new[1, 0, 0])


class TestInt8Pages:
    def test_quantized_pool_attention_close(self):
        q, kp, vp, bt, lens = _setup(n_heads=4, n_kv=2, d=32, page=16,
                                     pages_per_seq=2, seed=11)
        qkp = quantize_kv_pages(jnp.asarray(kp))
        qvp = quantize_kv_pages(jnp.asarray(vp))
        out = np.asarray(paged_attention(
            jnp.asarray(q), qkp, qvp, jnp.asarray(bt),
            jnp.asarray(lens)))
        ref = _np_reference(q, kp, vp, bt, lens, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, rtol=0.15, atol=0.15)

    def test_quantized_empty_slot_zeros(self):
        q, kp, vp, bt, lens = _setup(bsz=2, n_kv=2, d=32, page=16,
                                     pages_per_seq=2, seed=12)
        lens = np.array([10, 0], dtype=np.int32)
        out = np.asarray(paged_attention(
            jnp.asarray(q), quantize_kv_pages(jnp.asarray(kp)),
            quantize_kv_pages(jnp.asarray(vp)), jnp.asarray(bt),
            jnp.asarray(lens)))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


class TestQuantizeRoundTrip:
    """Direct bound on the int8 page codec: symmetric per-(row, head)
    absmax quantization reconstructs every element within half a
    quantization step (s = absmax / 127)."""

    def test_round_trip_error_bound(self):
        rng = np.random.RandomState(21)
        pages = (rng.randn(2, 5, 8, 16) * 3.0).astype(np.float32)
        qp = quantize_kv_pages(jnp.asarray(pages))
        deq = np.asarray(_dequant(qp["q8"], qp["s"]))
        s_row = np.abs(pages).max(axis=-1) / 127.0
        bound = 0.5 * s_row[..., None] + 1e-6
        assert (np.abs(deq - pages) <= bound).all()
        # scales are the advertised absmax/127 (clamped away from 0)
        np.testing.assert_allclose(np.asarray(qp["s"]),
                                   np.maximum(s_row, 1e-8), rtol=1e-6)

    def test_round_trip_tiny_rows(self):
        # all-zero rows must survive (scale clamp, not 0/0)
        pages = np.zeros((1, 2, 4, 8), np.float32)
        qp = quantize_kv_pages(jnp.asarray(pages))
        deq = np.asarray(_dequant(qp["q8"], qp["s"]))
        np.testing.assert_array_equal(deq, 0.0)


def _np_ragged_reference(q, k_pages, v_pages, block_tables, context_lens,
                         query_lens, scale, starts=None):
    """Loop-based reference: token j of row r attends causally to KV
    positions < context_lens[r] - query_lens[r] + j + 1. Padding tokens
    (owned by no row) are zeros. ``starts`` defaults to rows packed in
    row order."""
    n_tokens, n_heads, d = q.shape
    n_kv, _, page, _ = k_pages.shape
    group = n_heads // n_kv
    out = np.zeros_like(q, dtype=np.float32)
    if starts is None:
        starts = np.concatenate([[0], np.cumsum(query_lens)[:-1]])
    for r in range(len(query_lens)):
        for j in range(int(query_lens[r])):
            t = int(starts[r]) + j
            L = int(context_lens[r]) - int(query_lens[r]) + j + 1
            if L <= 0:
                continue
            n_pages_used = (L + page - 1) // page
            for h in range(n_heads):
                kv_h = h // group
                rows = [k_pages[kv_h, int(block_tables[r, pi])]
                        for pi in range(n_pages_used)]
                K = np.concatenate(rows, axis=0)[:L]
                rows = [v_pages[kv_h, int(block_tables[r, pi])]
                        for pi in range(n_pages_used)]
                V = np.concatenate(rows, axis=0)[:L]
                s = (q[t, h].astype(np.float32)
                     @ K.T.astype(np.float32)) * scale
                w = np.exp(s - s.max())
                w = w / w.sum()
                out[t, h] = w @ V.astype(np.float32)
    return out


def _ragged_setup(query_lens, context_lens, n_heads=4, n_kv=2, d=32,
                  page=16, pages_per_seq=4, n_pad=0, seed=0):
    rng = np.random.RandomState(seed)
    n_rows = len(query_lens)
    pages_per_seq = max(pages_per_seq, -(-int(max(context_lens)) // page))
    total_pages = n_rows * pages_per_seq + 1
    n_tokens = int(np.sum(query_lens)) + n_pad
    q = rng.randn(n_tokens, n_heads, d).astype(np.float32)
    kp = rng.randn(n_kv, total_pages, page, d).astype(np.float32)
    vp = rng.randn(n_kv, total_pages, page, d).astype(np.float32)
    bt = (1 + np.arange(n_rows * pages_per_seq)
          .reshape(n_rows, pages_per_seq)).astype(np.int32)
    ql = np.asarray(query_lens, np.int32)
    cl = np.asarray(context_lens, np.int32)
    return q, kp, vp, bt, cl, ql


class TestRaggedPagedAttention:
    """Tentpole kernel: mixed prefill+decode rows in one launch, across
    the query_lens mixes the serving engine produces (all-decode,
    all-prefill, mixed, empty rows with context_lens == 0)."""

    MIXES = {
        "all_decode": ([1, 1, 1], [9, 33, 17]),
        "all_prefill": ([7, 20, 5], [7, 20, 5]),
        "mixed": ([1, 12, 1, 6], [25, 12, 40, 30]),
        "empty_rows": ([1, 0, 8, 0], [14, 0, 8, 0]),
    }

    @staticmethod
    def _engine_batch(n_rows, decode, chunks):
        """Pack a batch as ``ServingEngine._launch`` does: rows are
        slots (the rest idle), every decode token first on the flat
        axis, then the prefill chunks. ``decode``: {slot: context};
        ``chunks``: [(slot, tokens, context)]. -> (ql, cl, qs)."""
        ql, cl, qs = (np.zeros(n_rows, np.int32) for _ in range(3))
        cursor = 0
        for s, ctx in decode.items():
            qs[s], ql[s], cl[s] = cursor, 1, ctx
            cursor += 1
        for s, n, ctx in chunks:
            qs[s], ql[s], cl[s] = cursor, n, ctx
            cursor += n
        return ql, cl, qs

    # name -> (n_rows, decode, chunks, _ragged_setup keywords, int8 pools)
    ENGINE = {
        # idle slots between live ones; decode first, then two chunks
        "slots_with_gaps": (8, {6: 21, 1: 40, 3: 9},
                            [(4, 7, 7), (0, 11, 30)], {}, False),
        # contexts that end exactly on a page boundary (page 16)
        "page_boundary": (4, {0: 16, 2: 48}, [(3, 16, 32)], {}, False),
        # a chunk that starts in one page and ends in the next but one
        "chunk_crosses_pages": (3, {1: 33}, [(0, 20, 38)], {}, False),
        # the serving cell's table: 16 pages a row, 1-13 of them live
        "pages_1_to_13_of_16": (6, {0: 5, 1: 16 * 13, 3: 16 * 6 + 3,
                                    5: 16 * 2},
                                [(4, 9, 16 * 9 + 1)],
                                dict(pages_per_seq=16), False),
        # a chunk longer than one big q block (128 rows) beside decodes
        "long_chunk": (4, {0: 140, 3: 17}, [(1, 150, 170)], {}, False),
        # GQA, group 4: big q blocks of 32 tokens, small ones of 16
        "gqa_group4": (5, {4: 29, 0: 70}, [(2, 45, 61), (3, 6, 6)],
                       dict(n_heads=8, n_kv=2), False),
        "int8_pools": (6, {5: 23, 2: 64}, [(0, 18, 50)], {}, True),
        "gqa_group4_int8": (4, {1: 37}, [(3, 35, 35)],
                            dict(n_heads=8, n_kv=2), True),
        # the token budget is not full: padding after the last row
        "padding_after_rows": (4, {2: 12, 0: 31}, [(1, 10, 26)],
                               dict(n_pad=7), False),
    }

    def _run(self, name, **kw):
        if name in self.ENGINE:
            n_rows, decode, chunks, setup_kw, quant = self.ENGINE[name]
            ql, cl, qs = self._engine_batch(n_rows, decode, chunks)
            kw["q_starts"] = jnp.asarray(qs)
        else:
            (ql, cl), setup_kw, quant, qs = self.MIXES[name], {}, False, None
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, seed=13, **setup_kw)
        pools = jnp.asarray(kp), jnp.asarray(vp)
        if quant:
            # the reference reads what the int8 pool decodes to
            pools = tuple(quantize_kv_pages(x) for x in pools)
            kp, vp = (np.asarray(_dequant(x["q8"], x["s"])) for x in pools)
        out = np.asarray(ragged_paged_attention(
            jnp.asarray(q), *pools, jnp.asarray(bt), jnp.asarray(cl),
            jnp.asarray(ql), **kw))
        ref = _np_ragged_reference(q, kp, vp, bt, cl, ql,
                                   q.shape[-1] ** -0.5, starts=qs)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        if qs is not None:
            owned = np.zeros(len(q), bool)
            for a, n in zip(qs, ql):
                owned[a:a + n] = True
            np.testing.assert_array_equal(out[~owned], 0.0)

    @pytest.mark.parametrize("mix", sorted(MIXES))
    def test_xla_matches_numpy(self, mix):
        self._run(mix, use_kernel=False)

    @pytest.mark.parametrize("mix", sorted(MIXES) + sorted(ENGINE))
    def test_kernel_matches_numpy(self, mix):
        self._run(mix, interpret=True, use_kernel=True)

    @pytest.mark.parametrize("mix", sorted(ENGINE))
    def test_xla_matches_numpy_engine_shaped(self, mix):
        self._run(mix, use_kernel=False)

    def test_kernel_blocks_heads_when_vmem_is_short(self, monkeypatch):
        """With no room for every KV head the grid gains head blocks
        (each resets and flushes its own scratch) and a visit batches
        fewer heads a score tile. The shape is this test's alone: the
        jitted wrapper reads the two limits when it traces."""
        import importlib
        paged = importlib.import_module(
            "paddle_tpu.incubate.nn.pallas.paged_attention")
        monkeypatch.setattr(paged, "_VMEM_BUDGET", 0)
        monkeypatch.setattr(paged, "_SCORE_TILE_BYTES", 0)
        monkeypatch.setitem(self.ENGINE, "head_blocks", (
            5, {4: 29, 0: 70}, [(2, 45, 61), (3, 6, 6)],
            dict(n_heads=8, n_kv=4, n_pad=3), False))
        self._run("head_blocks", interpret=True, use_kernel=True)

    def test_padding_tokens_are_zero(self):
        ql, cl = self.MIXES["mixed"]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, n_pad=5, seed=14)
        for kw in ({"use_kernel": False},
                   {"interpret": True, "use_kernel": True}):
            out = np.asarray(ragged_paged_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql), **kw))
            ref = _np_ragged_reference(q, kp, vp, bt, cl, ql,
                                       q.shape[-1] ** -0.5)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
            np.testing.assert_array_equal(out[int(np.sum(ql)):], 0.0)

    def test_all_decode_matches_decode_kernel(self):
        # a ragged batch of pure decode rows is exactly the existing
        # decode attention (row r == batch b, lens == context_lens)
        ql, cl = self.MIXES["all_decode"]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, seed=15)
        out_r = np.asarray(ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql),
            interpret=True, use_kernel=True))
        out_d = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), interpret=True,
            use_kernel=True))
        np.testing.assert_allclose(out_r, out_d, rtol=2e-4, atol=2e-4)

    def test_explicit_row_of_matches_derived(self):
        ql, cl = self.MIXES["mixed"]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, n_pad=3, seed=16)
        starts = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
        row_of = np.full(q.shape[0], -1, np.int32)
        for r in range(len(ql)):
            row_of[starts[r]:starts[r] + ql[r]] = r
        out_a = np.asarray(ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql),
            use_kernel=False))
        out_b = np.asarray(ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql),
            q_starts=jnp.asarray(starts), row_of=jnp.asarray(row_of),
            use_kernel=False))
        np.testing.assert_array_equal(out_a, out_b)

    def test_gqa_grouping(self):
        ql = [1, 9, 1]
        cl = [22, 9, 31]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, n_heads=8, n_kv=2,
                                              seed=17)
        out = np.asarray(ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql),
            interpret=True, use_kernel=True))
        ref = _np_ragged_reference(q, kp, vp, bt, cl, ql,
                                   q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestRaggedInt8Pages:
    def test_xla_int8_close_to_fp(self):
        ql = [1, 10, 1, 4]
        cl = [18, 10, 27, 33]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, seed=18)
        qkp = quantize_kv_pages(jnp.asarray(kp))
        qvp = quantize_kv_pages(jnp.asarray(vp))
        out = np.asarray(ragged_paged_attention(
            jnp.asarray(q), qkp, qvp, jnp.asarray(bt), jnp.asarray(cl),
            jnp.asarray(ql)))
        ref = _np_ragged_reference(q, kp, vp, bt, cl, ql,
                                   q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, rtol=0.15, atol=0.15)

    def test_kernel_int8_matches_xla_int8(self):
        # kernel and XLA paths share the _dequant rule -> tight agreement
        ql = [1, 10, 1, 4]
        cl = [18, 10, 27, 33]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, seed=19)
        qkp = quantize_kv_pages(jnp.asarray(kp))
        qvp = quantize_kv_pages(jnp.asarray(vp))
        out_k = np.asarray(ragged_paged_attention(
            jnp.asarray(q), qkp, qvp, jnp.asarray(bt), jnp.asarray(cl),
            jnp.asarray(ql), interpret=True, use_kernel=True))
        out_x = np.asarray(ragged_paged_attention(
            jnp.asarray(q), qkp, qvp, jnp.asarray(bt), jnp.asarray(cl),
            jnp.asarray(ql), use_kernel=False))
        np.testing.assert_allclose(out_k, out_x, rtol=2e-4, atol=2e-4)

    def test_int8_empty_rows_zero(self):
        ql = [1, 0, 3]
        cl = [12, 0, 3]
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, n_pad=2, seed=20)
        out = np.asarray(ragged_paged_attention(
            jnp.asarray(q), quantize_kv_pages(jnp.asarray(kp)),
            quantize_kv_pages(jnp.asarray(vp)), jnp.asarray(bt),
            jnp.asarray(cl), jnp.asarray(ql)))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[int(np.sum(ql)):], 0.0)



class TestPallasKVWrite:
    """The in-place tile-group write against the scatter it replaces on
    the TPU, bit for bit, on batches packed as the engine packs them (a
    block-table row and a position a token), then the ragged kernel over
    the written pools against the numpy reference."""

    # name -> (pool dtype, kv heads, q heads, head dim, pages, page,
    #          pages a row, decode {row: context}, chunks [(row, tokens,
    #          context)], padding tokens)
    CASES = {
        "decode_rows": ("bfloat16", 2, 4, 32, 12, 16, 3,
                        {0: 5, 2: 17, 3: 48, 5: 33}, [], 0),
        # 256 tokens from position 100: over the boundaries at 128, 256
        "chunk_256_crosses_two_pages": (
            "bfloat16", 2, 4, 32, 10, 128, 3, {1: 130, 2: 7},
            [(0, 256, 356)], 0),
        # rows 2 and 3 hold tokens past their three pages of 16: dropped
        "padding_and_past_the_window": (
            "bfloat16", 2, 4, 32, 14, 16, 3, {0: 9, 3: 50},
            [(1, 20, 30), (2, 12, 53)], 6),
        "gqa_32_8": ("bfloat16", 8, 32, 128, 6, 128, 2, {0: 140, 1: 3},
                     [(2, 19, 131)], 2),
        "head_dim_64": ("bfloat16", 4, 4, 64, 5, 128, 2, {0: 129},
                        [(1, 40, 40)], 1),
        "float32_pool": ("float32", 2, 4, 32, 12, 8, 4, {0: 5, 2: 17},
                         [(1, 13, 21)], 3),
        # Ouro's pool: 22 pages, 6 rows beside a chunk, T = 262
        "ouro_pool": ("bfloat16", 16, 16, 128, 22, 128, 8,
                      {0: 200, 1: 129, 2: 384, 3: 64, 4: 1, 5: 257},
                      [(6, 200, 200)], 56),
    }

    @staticmethod
    def _batch(case, seed=0):
        dtype, n_kv, n_heads, d, pages, page, pps, decode, chunks, n_pad = case
        rng = np.random.RandomState(seed)
        n_rows = max(list(decode) + [c[0] for c in chunks]) + 1
        ql, cl, qs = TestRaggedPagedAttention._engine_batch(
            n_rows, decode, chunks)
        # distinct pages a row, page 0 handed to none
        bt = np.zeros((n_rows, pps), np.int32)
        free = list(rng.permutation(np.arange(1, pages)))
        for r in range(n_rows):
            need = min(-(-int(cl[r]) // page), pps)
            bt[r, :need] = [free.pop() for _ in range(need)]
        T = int(ql.sum()) + n_pad
        pos = np.full(T, -1, np.int32)
        row_of = np.full(T, -1, np.int32)
        for r in range(n_rows):
            pos[qs[r]:qs[r] + ql[r]] = np.arange(cl[r] - ql[r], cl[r])
            row_of[qs[r]:qs[r] + ql[r]] = r
        dt = jnp.dtype(dtype)
        mk = lambda *s: jnp.asarray(rng.randn(*s), dt)      # noqa: E731
        return dict(kp=mk(n_kv, pages, page, d), vp=mk(n_kv, pages, page, d),
                    k=mk(T, n_kv, d), v=mk(T, n_kv, d),
                    q=rng.randn(T, n_heads, d).astype(np.float32),
                    bt=bt, pos=pos, row_of=row_of, ql=ql, cl=cl, qs=qs)

    @staticmethod
    def _write(b, **kw):
        from paddle_tpu.incubate.nn.pallas.paged_attention import \
            paged_kv_write_chunk
        bt_tok = b["bt"][np.clip(b["row_of"], 0, None)]
        return paged_kv_write_chunk(
            b["kp"], b["vp"], b["k"][:, None], b["v"][:, None],
            jnp.asarray(bt_tok), jnp.asarray(b["pos"][:, None]), **kw)

    @staticmethod
    def _same_bytes(got, want):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      np.asarray(want).view(np.uint8))

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_kernel_writes_what_the_scatter_writes(self, name):
        case = self.CASES[name]
        page, pps = case[5], case[6]
        b = self._batch(case)
        ref = self._write(b, use_kernel=False)
        out = self._write(b, use_kernel=True, interpret=True)
        live = (b["pos"] >= 0) & (b["pos"] < page * pps)
        assert 0 < live.sum()
        if "past_the_window" in name:
            assert (b["pos"] >= page * pps).any() and (b["pos"] < 0).any()
        for got, want, old, new in zip(out, ref, (b["kp"], b["vp"]),
                                       (b["k"], b["v"])):
            self._same_bytes(got, want)
            # every byte no live token owns is the pool's as it was:
            # page 0 (handed to no row: no clamped index reached it) ...
            self._same_bytes(got[:, 0], old[:, 0])
            # ... and, slot by slot, all but the live tokens' own
            got, old = np.array(got), np.array(old)
            for t in np.flatnonzero(live):
                pid = b["bt"][b["row_of"][t], b["pos"][t] // page]
                self._same_bytes(got[:, pid, b["pos"][t] % page], new[t])
                got[:, pid, b["pos"][t] % page] = \
                    old[:, pid, b["pos"][t] % page]
            self._same_bytes(got, old)

    @pytest.mark.parametrize("name", ["chunk_256_crosses_two_pages",
                                      "gqa_32_8", "ouro_pool"])
    def test_ragged_attention_over_the_written_pools(self, name):
        b = self._batch(self.CASES[name], seed=1)
        kp, vp = self._write(b, use_kernel=True, interpret=True)
        out = np.asarray(ragged_paged_attention(
            jnp.asarray(b["q"]), kp, vp, jnp.asarray(b["bt"]),
            jnp.asarray(b["cl"]), jnp.asarray(b["ql"]),
            q_starts=jnp.asarray(b["qs"]), interpret=True,
            use_kernel=True))
        # the reference reads pools written by the scatter, so a token
        # the write lost would show as a wrong output of its row
        rk, rv = (np.asarray(x.astype(jnp.float32))
                  for x in self._write(b, use_kernel=False))
        ref = _np_ragged_reference(b["q"], rk, rv, b["bt"], b["cl"],
                                   b["ql"], b["q"].shape[-1] ** -0.5,
                                   starts=b["qs"])
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)

    def test_rows_of_many_tokens_and_several_calls(self, monkeypatch):
        """The two-program path's shape (g tokens a row, one table a
        row), with so little room for new rows that the write takes
        several calls of 8 tokens. The shape is this test's alone: the
        jitted wrapper reads the limit when it traces."""
        import importlib
        paged = importlib.import_module(
            "paddle_tpu.incubate.nn.pallas.paged_attention")
        monkeypatch.setattr(paged, "_WRITE_NEW_BYTES", 0)
        rng = np.random.RandomState(2)
        n_kv, pages, page, d, b, g = 2, 9, 16, 32, 2, 21
        mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.bfloat16)  # noqa: E731
        bt = np.array([[3, 5, 1, 0], [2, 7, 4, 8]], np.int32)
        pos = np.stack([np.where(np.arange(g) < 17, 9 + np.arange(g), -1),
                        30 + np.arange(g)]).astype(np.int32)
        args = (mk(n_kv, pages, page, d), mk(n_kv, pages, page, d),
                mk(b, g, n_kv, d), mk(b, g, n_kv, d), jnp.asarray(bt),
                jnp.asarray(pos))
        ref = paged.paged_kv_write_chunk(*args, use_kernel=False)
        out = paged.paged_kv_write_chunk(*args, use_kernel=True,
                                         interpret=True)
        for got, want in zip(out, ref):
            self._same_bytes(got, want)
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda *a: paged.paged_kv_write_chunk(
                *a, use_kernel=True, interpret=True))(*args))


# ---------------------------------------------------------------------------
# Latent pages: one pool a cache layer, every head on the shared page.
# ---------------------------------------------------------------------------
from paddle_tpu.incubate.nn.pallas.paged_attention import (  # noqa: E402
    latent_pool_dim, latent_visits, paged_latent_write_chunk,
    ragged_latent_attention)


def _np_latent_reference(q, pool, bt, rows, d, dv, scale):
    """Per token, in numpy: its row's pages in order, the causal prefix,
    keys the first ``d`` lanes of a row, values its first ``dv``."""
    page = pool.shape[2]
    out = np.zeros(q.shape[:2] + (dv,), np.float32)
    start = 0
    for r, (n, ctx) in enumerate(rows):
        kv = np.concatenate([pool[0, bt[r, p]]
                             for p in range(-(-ctx // page))], 0) \
            if ctx else None
        for j in range(n):
            seen = kv[:ctx - n + j + 1].astype(np.float32)
            s = q[start + j].astype(np.float32) @ seen[:, :d].T * scale
            w = np.exp(s - s.max(-1, keepdims=True))
            out[start + j] = (w / w.sum(-1, keepdims=True)) @ seen[:, :dv]
        start += n
    return out


# (query tokens, context) a row, in packing order; then the token budget
LATENT_BATCHES = {
    # decode rows beside a chunk that crosses two page boundaries, an
    # idle slot between them, a row on its first token
    "chunk_and_decodes": ([(1, 9), (1, 17), (0, 0), (21, 37), (1, 1)], 40),
    # contexts that end exactly on a page boundary, and one past it
    "page_boundaries": ([(1, 16), (1, 17), (8, 24), (1, 32)], 16),
    # a whole prompt in one step: more than two q blocks of 16 tokens
    "prefill_only": ([(35, 35)], 36),
    # a chunk deep inside a long context, q blocks whole and ragged
    "mid_prompt_chunk": ([(1, 50), (33, 64)], 48),
}


@pytest.mark.parametrize("name", sorted(LATENT_BATCHES))
@pytest.mark.parametrize("heads", [4, 32])
def test_latent_ragged_kernel_matches_composition_and_numpy(name, heads):
    rows, budget = LATENT_BATCHES[name]
    d, dv, page, pps = 40, 32, 8, 8
    rng = np.random.RandomState(len(name) + heads)
    n_rows = len(rows)
    pool = rng.randn(1, n_rows * pps + 3, page, latent_pool_dim(d)) \
        .astype(np.float32)
    pool[..., d:] = 0.0
    bt = rng.permutation(n_rows * pps + 3)[:n_rows * pps] \
        .reshape(n_rows, pps).astype(np.int32)
    ql = np.asarray([n for n, _ in rows], np.int32)
    cl = np.asarray([c for _, c in rows], np.int32)
    qs = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    row_of = np.full(budget, -1, np.int32)
    for r in range(n_rows):
        row_of[qs[r]:qs[r] + ql[r]] = r
    q = rng.randn(budget, heads, d).astype(np.float32)
    want = _np_latent_reference(q, pool, bt, rows, d, dv, 0.2)
    for kernel in (False, True):
        got = ragged_latent_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
            jnp.asarray(cl), jnp.asarray(ql), q_starts=jnp.asarray(qs),
            row_of=jnp.asarray(row_of), value_dim=dv, scale=0.2,
            use_kernel=kernel, interpret=True)
        assert got.shape == (budget, heads, dv)
        assert np.abs(np.asarray(got) - want).max() < 2e-5
        # padding tokens and idle rows: zeros, not garbage
        assert np.abs(np.asarray(got)[int(ql.sum()):]).max() == 0


def test_latent_visits_list_each_block_s_pages_once():
    """The kernel's work list: every (q block, row, page) a block's
    tokens can see, sorted by q block, a null visit for a block that no
    live row touches."""
    rows = [(1, 9), (1, 17), (0, 0), (21, 37), (1, 1)]
    ql = np.asarray([n for n, _ in rows], np.int32)
    cl = np.asarray([c for _, c in rows], np.int32)
    qs = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    vis, n = latent_visits(48, 8, jnp.zeros((5, 8), jnp.int32),
                           jnp.asarray(cl), jnp.asarray(ql), jnp.asarray(qs))
    vis = np.asarray(vis)[:int(n[0])]
    got = [(v >> 20, (v >> 12) & 255, v & 4095) for v in vis.tolist()]
    want = []
    # block 0 holds tokens 0..15: rows 0, 1 and the chunk's first 14
    # tokens (positions 16..29: 4 pages); block 1 its last 7 and row 4
    want += [(0, 0, p) for p in range(2)] + [(0, 1, p) for p in range(3)]
    want += [(0, 3, p) for p in range(4)]
    want += [(1, 3, p) for p in range(5)] + [(1, 4, 0)]
    want += [(2, 5, 0)]                    # tokens 32..47: nobody's
    assert got == want


def test_latent_write_kernel_matches_scatter():
    """Rows land where the scatter puts them: page boundaries, two
    tokens of one tile group, a dropped token, the row's padding zero."""
    rng = np.random.RandomState(5)
    for dtype in (jnp.bfloat16, jnp.float32):
        pool = jnp.asarray(rng.randn(1, 10, 16, 128), dtype)
        rows = jnp.asarray(rng.randn(8, 40), dtype)
        bt = jnp.asarray(np.repeat(rng.permutation(10)[:3][None], 8, 0)
                         .astype(np.int32))
        pos = jnp.asarray([0, 15, 16, 17, -1, 47, 48, 31], jnp.int32)
        a = paged_latent_write_chunk(pool, rows, bt, pos, use_kernel=False)
        b = paged_latent_write_chunk(pool, rows, bt, pos, use_kernel=True,
                                     interpret=True)
        assert bool(jnp.array_equal(a, b))
        page = np.asarray(a[0, int(bt[0, 1])], np.float32)
        assert np.abs(page[0, :40] - np.asarray(rows[2], np.float32)).max() \
            == 0 and np.abs(page[0, 40:]).max() == 0
        # position 48 is past the 3-page window, -1 is padding: 6 rows
        assert int((np.asarray(a, np.float32)
                    != np.asarray(pool, np.float32)).any(-1).sum()) == 6
