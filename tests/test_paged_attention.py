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
                         query_lens, scale, starts=None, window=None):
    """Loop-based reference: token j of row r attends causally to KV
    positions < context_lens[r] - query_lens[r] + j + 1 (with a
    ``window``, the last ``window`` of them). Padding tokens (owned by no
    row) are zeros. ``starts`` defaults to rows packed in row order."""
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
                if window is not None:
                    K, V = K[max(0, L - window):], V[max(0, L - window):]
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
    # The cases above run at d = 32, which the kernel fetches by the
    # grid's block pipeline a page a visit (as a head dim of 64 on the
    # chip); at d = 128, whole lane tiles, it copies a group of N pages a
    # visit by DMA. GQA, group 16, at d = 128: a decode token is one q
    # block of 16 rows, and its row's pages of 16 are read N =
    # _RAGGED_PAGES = 8 a visit: decode rows of exactly N, N - 1, N + 1
    # and 2N + 1 pages, so a row's last group is whole, short by one, one
    # page long, and the third of its row; over 2 KV heads
    ENGINE.update({
        "gqa_group16_group_edges": (
            6, {0: 16 * 8, 1: 16 * 7, 3: 16 * 8 + 1, 5: 16 * 16 + 1}, [],
            dict(n_heads=32, n_kv=2, d=128), False),
        # a 256-token chunk at positions 44..299: its big q blocks' causal
        # limits end inside each of its three groups (8, 8 and 3 pages)
        "gqa_group16_chunk_256": (3, {0: 40, 2: 16 * 8 + 2},
                                  [(1, 256, 300)],
                                  dict(n_heads=16, n_kv=1, d=128), False),
        "gqa_group16_idle_and_padding": (
            6, {5: 33, 2: 140}, [(0, 20, 150)],
            dict(n_heads=16, n_kv=1, n_pad=5, d=128), False),
        "gqa_group16_int8": (4, {1: 137, 3: 16 * 8}, [(0, 24, 60)],
                             dict(n_heads=16, n_kv=1, d=128), True),
        # 16 KV heads, 2 pages a visit: a batch of heads scores a page at
        # a time (fewer score tiles than a run of pages a head)
        "many_kv_heads": (4, {0: 16 * 9 + 3, 2: 33}, [(1, 12, 40)],
                          dict(n_heads=16, n_kv=16, d=128), False),
    })
    NARROW = sorted(MIXES) + sorted(k for k, v in ENGINE.items()
                                    if "d" not in v[3])

    def _run(self, name, d=None, **kw):
        if name in self.ENGINE:
            n_rows, decode, chunks, setup_kw, quant = self.ENGINE[name]
            ql, cl, qs = self._engine_batch(n_rows, decode, chunks)
            kw["q_starts"] = jnp.asarray(qs)
        else:
            (ql, cl), setup_kw, quant, qs = self.MIXES[name], {}, False, None
        if d is not None:
            setup_kw = dict(setup_kw, d=d)
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
        # the TPU interpreter: DMAs land at their wait, and memory nobody
        # wrote reads NaN, so a stale page slot that reached a score or
        # p @ v would show
        from jax.experimental.pallas import tpu as pltpu
        self._run(mix, use_kernel=True,
                  interpret=pltpu.InterpretParams(uninitialized_memory="nan"))

    @pytest.mark.parametrize("mix", NARROW)
    def test_kernel_reads_page_groups_by_dma(self, mix):
        """The cases of d = 32 again at d = 128: the kernel's DMA of up
        to N pages a visit, under the NaN-filling TPU interpreter."""
        from jax.experimental.pallas import tpu as pltpu
        self._run(mix, d=128, use_kernel=True,
                  interpret=pltpu.InterpretParams(uninitialized_memory="nan"))

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


# the per-head kernel's plan at the serving cells' pools: tokens, heads,
# pool shape, pages a row, rows; then the pages a visit reads (N)
RAGGED_PLANS = {
    # 2 KV heads of 128: a page of the head block is 64 KiB of K and V
    "serve_reason_nemotron3nano_l18": (384, 32, (2, 2400, 128, 128), 30,
                                       128, 8),
    # 16 KV heads: a page of every head is 1 MiB, a visit alone
    "serve_chat_1p3b": (304, 16, (16, 240, 128, 128), 16, 48, 1),
    "serve_reason_ouro2p6b": (262, 16, (16, 22, 128, 128), 8, 6, 1),
    # a head dim of 64: the block pipeline's page a visit, at any heads
    "head_dim_64_gqa": (64, 32, (2, 64, 128, 64), 4, 4, 1),
}


@pytest.mark.parametrize("head_dim, page, want", [
    (64, 128, "pallas"), (128, 128, "pallas"), (256, 256, "pallas"),
    (96, 128, "xla"), (128, 16, "xla")])
def test_per_head_kernels_resolve_alike_on_a_tpu(monkeypatch, head_dim,
                                                 page, want):
    """On a TPU the ragged attention, the decode-only kernel and the
    in-place KV write take a pool of a lane-wide head dim (64 included)
    at pages of whole 128-row tiles, or all three leave it to XLA; int8
    pools keep the ragged kernel and take the XLA decode and scatter."""
    from paddle_tpu.incubate.nn.pallas.paged_attention import (
        decode_impl, kv_write_impl, ragged_impl)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ragged_impl(head_dim, page) == decode_impl(head_dim, page) \
        == kv_write_impl(head_dim, page) == want
    assert decode_impl(head_dim, page, True) \
        == kv_write_impl(head_dim, page, True) == "xla"


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("name", sorted(RAGGED_PLANS))
def test_ragged_visit_counts_match_the_kernel_s_work_list(name, seed):
    """Random ragged batches (decode rows, chunks, idle slots) at a
    serving cell's pools: the host's count of the per-head kernel's work
    (:func:`ragged_visit_counts`), which the serving step's span carries,
    is the work list's live length, and its page visits the pages those
    visits' groups hold; a row's groups start at its first page, N
    apart, in row order. N follows the shapes alone."""
    from paddle_tpu.incubate.nn.pallas.paged_attention import (
        _live_visits, _ragged_plan, ragged_visit_counts)
    t, nh, shape, pps, n_rows, want_n = RAGGED_PLANS[name]
    n_kv, _, page, d = shape
    _, npv = _ragged_plan(t, nh // n_kv, n_kv, d, page, 2, False, 2)
    assert npv == want_n
    rng = np.random.RandomState(seed)
    ql = np.zeros(n_rows, np.int32)
    cl = np.zeros(n_rows, np.int32)
    cursor = 0
    for r in rng.permutation(n_rows):
        n = min(int(rng.choice([0, 1, 1, 1, rng.randint(2, 257)])),
                t - cursor)
        if n:
            ql[r], cl[r] = n, rng.randint(n, pps * page + 1)
            cursor += n
    vrow, vpage, n_live = _live_visits(jnp.asarray(cl), jnp.asarray(ql),
                                       page, pps, npv)
    page_visits, visits = ragged_visit_counts(
        t, nh, shape, jnp.bfloat16, jnp.bfloat16, pps, cl, ql)
    assert vrow.shape[0] == n_rows * -(-pps // npv)
    assert visits == int(n_live[0])
    got = list(zip(np.asarray(vrow)[:visits].tolist(),
                   np.asarray(vpage)[:visits].tolist()))
    pages = -(-cl // page)
    want = [(r, p) for r in range(n_rows) if ql[r] > 0
            for p in range(0, int(pages[r]), npv)]
    assert got == want
    assert page_visits == int(pages[ql > 0].sum()) == sum(
        min(npv, int(pages[r]) - p) for r, p in want)


class TestWindowedRaggedAttention:
    """The per-head kernel and its XLA composition with a ``window``:
    token j of a row sees the last ``window`` of its causal keys; the
    kernel gets no visit for a page wholly before a row's first token's
    window, and masks keys inside the first page it reads."""

    # name -> (n_rows, decode {slot: context}, chunks [(slot, tokens,
    # context)], window, _ragged_setup keywords)
    CASES = {
        # a window of no whole pages (23 of 16-token pages), decode rows
        # far past it and a chunk that starts past it
        "window_off_the_page": (4, {0: 70, 2: 33}, [(1, 20, 61)], 23, {}),
        # every row shorter than its window: the causal prefix alone
        "rows_shorter_than_window": (3, {0: 9}, [(2, 12, 12)], 40, {}),
        # a chunk whose first token's window starts pages before its
        # last token's: the big q blocks skip different runs
        "chunk_straddles_window": (3, {2: 100}, [(0, 30, 95)], 48,
                                   dict(pages_per_seq=8)),
        "window_of_one_token": (3, {1: 40}, [(2, 9, 17)], 1, {}),
        # GQA 16 at d 128: a group of 8 pages a visit by DMA, the first
        # group of a row inside its window
        "gqa_group16_dma": (4, {0: 16 * 9 + 3, 3: 40}, [(1, 40, 16 * 12)],
                            16 * 5 + 7, dict(n_heads=32, n_kv=2, d=128,
                                             pages_per_seq=16)),
    }

    def _batch(self, name):
        n_rows, decode, chunks, window, kw = self.CASES[name]
        ql, cl, qs = TestRaggedPagedAttention._engine_batch(n_rows, decode,
                                                            chunks)
        q, kp, vp, bt, cl, ql = _ragged_setup(ql, cl, seed=17, **kw)
        return q, kp, vp, bt, cl, ql, qs, window

    def _check(self, out, q, kp, vp, bt, cl, ql, qs, window):
        want = _np_ragged_reference(q, kp, vp, bt, cl, ql,
                                    q.shape[-1] ** -0.5, starts=qs,
                                    window=window)
        assert np.isfinite(out).all()
        # float32 inputs: the kernel and numpy differ by the order of sums
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)

    def _call(self, q, kp, vp, bt, cl, ql, qs, window, **kw):
        return np.asarray(ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql),
            q_starts=jnp.asarray(qs), window=window, **kw))

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_kernel_and_composition_match_numpy(self, name):
        from jax.experimental.pallas import tpu as pltpu
        args = self._batch(name)
        kernel = self._call(*args, use_kernel=True,
                            interpret=pltpu.InterpretParams(
                                uninitialized_memory="nan"))
        xla = self._call(*args, use_kernel=False)
        self._check(kernel, *args)
        self._check(xla, *args)
        # float32 both ways: the same sums in another order
        np.testing.assert_allclose(kernel, xla, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("name", sorted(set(CASES)
                                            - {"rows_shorter_than_window"}))
    def test_kernel_reads_no_page_before_a_window(self, name):
        """A row's table names a page of NaN for every page wholly before
        its first token's window (as a ring names pages that now hold
        other tokens): the kernel reads none of them. (The XLA
        composition gathers a row's whole table: it runs over rings whose
        stale pages hold finite keys, which its mask removes.)"""
        from jax.experimental.pallas import tpu as pltpu
        q, kp, vp, bt, cl, ql, qs, window = self._batch(name)
        page = kp.shape[2]
        nan = kp.shape[1]
        kp = np.concatenate([kp, np.full_like(kp[:, :1], np.nan)], 1)
        vp = np.concatenate([vp, np.full_like(vp[:, :1], np.nan)], 1)
        bt = bt.copy()
        for r in range(len(ql)):
            if ql[r]:
                bt[r, :max(0, cl[r] - ql[r] - window + 1) // page] = nan
        assert (bt == nan).any()
        out = self._call(q, kp, vp, bt, cl, ql, qs, window, use_kernel=True,
                         interpret=pltpu.InterpretParams(
                             uninitialized_memory="nan"))
        self._check(out, q, kp, vp, bt, cl, ql, qs, window)


# the compiled program of ragged_paged_attention at one small shape, as
# an (opcode, result shapes) multiset hashed, the kernel (interpreted) and
# the XLA composition: read on the tree before the window came.
# With window=None the program must be that one
WINDOW_NONE_PROGRAMS = {"kernel": "abac56c348179b95",
                        "xla": "34deb39e3b4975de"}


@pytest.mark.parametrize("path", sorted(WINDOW_NONE_PROGRAMS))
def test_window_none_is_the_parent_s_program(path):
    import collections
    import functools
    import hashlib
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    try:
        from lib import xplane
    finally:
        sys.path.pop(0)
    T, H, KV, D, P, PAGES, R, PPS = 40, 8, 2, 128, 16, 24, 3, 8
    sd = jax.ShapeDtypeStruct
    kern = path == "kernel"
    f = functools.partial(ragged_paged_attention, use_kernel=kern,
                          interpret=True if kern else None)
    pool = sd((KV, PAGES, P, D), jnp.bfloat16)
    row = sd((R,), jnp.int32)
    text = jax.jit(lambda q, k, v, bt, cl, ql, qs, ro: f(
        q, k, v, bt, cl, ql, q_starts=qs, row_of=ro, window=None)).lower(
        sd((T, H, D), jnp.bfloat16), pool, pool, sd((R, PPS), jnp.int32),
        row, row, row, sd((T,), jnp.int32)).compile().as_text()
    ops = []
    for line in text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if line.startswith("%") and " = " in line:
            op = xplane.parse_hlo(line)
            ops.append((op["opcode"], tuple(map(tuple, op["results"]))))
    got = sorted([list(map(str, k)) + [v]
                  for k, v in collections.Counter(ops).items()])
    assert hashlib.sha256(json.dumps(got).encode()).hexdigest()[:16] \
        == WINDOW_NONE_PROGRAMS[path]


@pytest.mark.parametrize("window", [None, 16 * 5 + 7, 16 * 12])
@pytest.mark.parametrize("seed", range(2))
def test_windowed_visits_start_at_the_window(window, seed):
    """Random ragged batches: a row's visits start at the page of the
    first key its first token sees (page 0 without a window, the parent's
    lists), N apart, in row order, and the host's count is the work
    list's live length."""
    from paddle_tpu.incubate.nn.pallas.paged_attention import (
        _live_visits, ragged_visit_counts)
    t, nh, shape, pps, n_rows = 384, 32, (2, 640, 16, 128), 40, 12
    npv, page = 8, shape[2]
    rng = np.random.RandomState(seed)
    ql = np.zeros(n_rows, np.int32)
    cl = np.zeros(n_rows, np.int32)
    cursor = 0
    for r in rng.permutation(n_rows):
        n = min(int(rng.choice([0, 1, 1, rng.randint(2, 120)])), t - cursor)
        if n:
            ql[r], cl[r] = n, rng.randint(n, pps * page + 1)
            cursor += n
    vrow, vpage, n_live = _live_visits(jnp.asarray(cl), jnp.asarray(ql),
                                       page, pps, npv, window)
    page_visits, visits = ragged_visit_counts(
        t, nh, shape, jnp.bfloat16, jnp.bfloat16, pps, cl, ql, window)
    assert visits == int(n_live[0])
    got = list(zip(np.asarray(vrow)[:visits].tolist(),
                   np.asarray(vpage)[:visits].tolist()))
    ends = -(-cl // page)
    first = np.zeros_like(cl) if window is None else \
        np.maximum(cl - ql - window + 1, 0) // page
    want = [(r, p) for r in range(n_rows) if ql[r] > 0
            for p in range(int(first[r]), int(ends[r]), npv)]
    assert got == want
    assert page_visits == int((ends - first)[ql > 0].sum())


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
    _LATENT_PAGES, latent_pool_dim, latent_visit_counts, latent_visits,
    paged_latent_write_chunk, ragged_latent_attention)


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
# pages of 8 tokens, grouped _LATENT_PAGES (N) a visit: decode rows of
# exactly N, N - 1, N + 1 and 2N + 1 pages, so a row's last group is
# whole, short by one, one page long, and the third of its row
_G = 8 * _LATENT_PAGES
LATENT_BATCHES.update({
    "group_edges": ([(1, _G), (1, _G - 8), (1, _G + 1), (1, 2 * _G + 1)],
                    16),
    # a chunk whose q blocks' causal limits end inside a group, beside
    # decode rows of N + 1 pages, of one token and of 2N + 2 pages
    "chunk_ends_inside_group": (
        [(1, _G + 3), (37, _G + 8 + 5 + 36), (1, 1), (1, 2 * _G + 9)], 48),
})


@pytest.mark.parametrize("name", sorted(LATENT_BATCHES))
@pytest.mark.parametrize("heads", [4, 32])
def test_latent_ragged_kernel_matches_composition_and_numpy(name, heads):
    rows, budget = LATENT_BATCHES[name]
    d, dv, page = 40, 32, 8
    # more pages a row than a group holds: a row's pages form two groups
    # and more, and the block table's unused tail is live garbage
    pps = max(2 * _LATENT_PAGES + 1, -(-max(c for _, c in rows) // page))
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
    # the TPU interpreter: DMAs land at their wait, and memory nobody
    # wrote reads NaN, so a stale page slot that reached a score or
    # p @ v would show
    from jax.experimental.pallas import tpu as pltpu
    for kernel in (False, True):
        got = ragged_latent_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
            jnp.asarray(cl), jnp.asarray(ql), q_starts=jnp.asarray(qs),
            row_of=jnp.asarray(row_of), value_dim=dv, scale=0.2,
            use_kernel=kernel,
            interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
        assert got.shape == (budget, heads, dv)
        assert np.abs(np.asarray(got) - want).max() < 2e-5
        # padding tokens and idle rows: zeros, not garbage
        assert np.abs(np.asarray(got)[int(ql.sum()):]).max() == 0


def _visits_want(pages_seen, n_groups):
    """(q block, row, first page) of each visit, from the pages each
    (q block, row) pair sees; a null visit for a block nobody's."""
    want = []
    for b, seen in enumerate(pages_seen):
        got = [(b, r, p) for r, n in seen
               for p in range(0, n, _LATENT_PAGES)]
        want += got or [(b, n_groups, 0)]
    return want


# (query tokens, context) a row; then each q block's (row, pages seen)
LATENT_VISIT_CASES = {
    # block 0 holds tokens 0..15: rows 0, 1 and the chunk's first 14
    # tokens (positions 16..29: 4 pages); block 1 its last 7 and row 4;
    # block 2, tokens 32..47, nobody's
    "chunk_and_decodes": ([(1, 9), (1, 17), (0, 0), (21, 37), (1, 1)],
                          [[(0, 2), (1, 3), (3, 4)], [(3, 5), (4, 1)], []]),
    # decode rows of N, N - 1, N + 1 and 2N + 1 pages of 8
    "group_edges": ([(1, _G), (1, _G - 8), (1, _G + 1), (1, 2 * _G + 1)],
                    [[(0, _LATENT_PAGES), (1, _LATENT_PAGES - 1),
                      (2, _LATENT_PAGES + 1), (3, 2 * _LATENT_PAGES + 1)],
                     [], []]),
    # a chunk of 37 at positions _G + 12 .. _G + 48: its blocks' last
    # tokens see _G + 27, + 43 and + 49 keys, limits inside pages of
    # its second group
    "chunk_ends_inside_group": (
        [(1, _G + 3), (37, _G + 8 + 5 + 36), (1, 1)],
        [[(0, _LATENT_PAGES + 1), (1, _LATENT_PAGES + 4)],
         [(1, _LATENT_PAGES + 6)], [(1, _LATENT_PAGES + 7), (2, 1)]]),
}


@pytest.mark.parametrize("name", sorted(LATENT_VISIT_CASES))
def test_latent_visits_list_each_block_s_pages_once(name):
    """The kernel's work list: every (q block, row) pair's pages its
    tokens can see, in groups of _LATENT_PAGES from the row's first page
    (only the last one short), sorted by q block, a null visit for a
    block that no live row touches; and the host's count of the same
    work (:func:`latent_visit_counts`) matches it."""
    rows, seen = LATENT_VISIT_CASES[name]
    ql = np.asarray([n for n, _ in rows], np.int32)
    cl = np.asarray([c for _, c in rows], np.int32)
    qs = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    pps = 2 * _LATENT_PAGES + 2
    vis, n = latent_visits(48, 8, jnp.zeros((len(rows), pps), jnp.int32),
                           jnp.asarray(cl), jnp.asarray(ql), jnp.asarray(qs))
    vis = np.asarray(vis)[:int(n[0])]
    got = [(v >> 20, (v >> 12) & 255, v & 4095) for v in vis.tolist()]
    assert got == _visits_want(seen, len(rows))
    pages = sum(p for block in seen for _, p in block) \
        + sum(not block for block in seen)
    assert latent_visit_counts(48, 8, pps, cl, ql, qs) == (pages, len(got))


@pytest.mark.parametrize("seed", range(6))
def test_latent_visit_counts_match_the_kernel_s_grid(seed):
    """Random ragged batches (decode rows, chunks, idle slots, a budget
    with padding): the host's count of the kernel's visits, which the
    serving step's span carries, is the device's ``n_visits``, and the
    page visits are the pages the visits' groups hold."""
    rng = np.random.RandomState(seed)
    n_rows, page, pps, budget = 12, 8, 3 * _LATENT_PAGES + 1, 96
    ql = np.zeros(n_rows, np.int32)
    cl = np.zeros(n_rows, np.int32)
    qs = np.zeros(n_rows, np.int32)
    cursor = 0
    for r in rng.permutation(n_rows):
        n = int(rng.choice([0, 1, 1, 1, rng.randint(2, 40)]))
        n = min(n, budget - cursor)
        if n:
            qs[r], ql[r] = cursor, n
            cl[r] = rng.randint(n, pps * page + 1)
            cursor += n
    vis, n_vis = latent_visits(budget, page,
                               jnp.zeros((n_rows, pps), jnp.int32),
                               jnp.asarray(cl), jnp.asarray(ql),
                               jnp.asarray(qs))
    page_visits, visits = latent_visit_counts(budget, page, pps, cl, ql, qs)
    assert visits == int(n_vis[0])
    assert page_visits >= visits
    # each visit's group: up to _LATENT_PAGES of what its block sees
    vis = np.asarray(vis)[:visits]
    b, r, p0 = vis >> 20, (vis >> 12) & 255, vis & 4095
    live = r < n_rows
    assert page_visits - int((~live).sum()) == sum(
        min(_LATENT_PAGES, int(np.minimum(
            -(-(cl[rr] - ql[rr] + min(qs[rr] + ql[rr], 16 * bb + 16)
                - qs[rr]) // page), pps)) - pp)
        for bb, rr, pp in zip(b[live], r[live], p0[live]))


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
