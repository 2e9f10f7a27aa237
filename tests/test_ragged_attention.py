"""Ragged paged-attention parity: the Pallas kernel (interpret mode) and
the XLA gather reference must agree with a dense causal-attention oracle
across every ragged composition the engine's mixed program produces —
pure prefill, pure decode, mixed batches, sliding windows, int8 KV pages,
scale overrides, and padded/null-page lanes (docs/kernels.md)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kserve_tpu.engine.kvcache import (
    KVCacheConfig,
    init_kv_pages,
    init_kv_scales,
)
from kserve_tpu.ops.kv_write import quantize_rows, write_ragged_kv
from kserve_tpu.ops.attention import (
    ragged_attention_path,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    ragged_token_metadata,
)
from kserve_tpu.ops.pallas_paged_attention import (
    RAGGED_BQ,
    ragged_paged_attention_pallas,
    ragged_single_token_split_pallas,
)

PS = 8  # page size
NKV = 2
NQ = 4
D = 16


def _align(n: int, a: int = RAGGED_BQ) -> int:
    return (n + a - 1) // a * a


class RaggedCase:
    """One ragged batch: per-lane (kv_start, q_len) plus seeded K/V.

    Builds the packed query buffer, the paged cache (history + slice
    written via write_ragged_kv), the metadata arrays, and a dense oracle
    computed per lane with plain causal softmax over the full context.
    """

    def __init__(self, lanes, seed=0, quantized=False, window=0,
                 scale=None, softcap=0.0, d=D, W=8):
        # lanes: list of (kv_start, q_len); W: the page table's width
        rng = np.random.RandomState(seed)
        self.lanes = lanes
        self.window = window
        self.scale = scale
        self.softcap = softcap
        self.d = d
        B = len(lanes)
        num_pages = 1 + B * W
        self.q_start = np.zeros((B,), np.int32)
        self.q_len = np.array([q for _, q in lanes], np.int32)
        self.kv_start = np.array([h for h, _ in lanes], np.int32)
        off = 0
        for i, (_, qn) in enumerate(lanes):
            self.q_start[i] = off
            off += _align(max(qn, 1)) if qn > 0 else 0
        self.T = max(_align(off), RAGGED_BQ)
        self.q = rng.randn(self.T, NQ, d).astype(np.float32)
        # full per-lane K/V streams (history + slice)
        self.k_full = [rng.randn(h + qn, NKV, d).astype(np.float32)
                       for h, qn in lanes]
        self.v_full = [rng.randn(h + qn, NKV, d).astype(np.float32)
                       for h, qn in lanes]
        # paged cache: allocate pages per lane, write history directly,
        # then write the slice through the production ragged scatter
        cfg = KVCacheConfig(n_layers=1, n_kv_heads=NKV, head_dim=d,
                            page_size=PS, num_pages=num_pages,
                            max_pages_per_seq=W, dtype="float32")
        pages = init_kv_pages(cfg)[0]
        self.page_table = np.zeros((B, W), np.int32)
        nxt = 1
        for i, (h, qn) in enumerate(lanes):
            need = -(-(h + qn) // PS) if (h + qn) else 0
            for p in range(need):
                self.page_table[i, p] = nxt
                nxt += 1
        # history tokens land in their pages directly
        hist = np.asarray(pages).copy()
        for i, (h, qn) in enumerate(lanes):
            for t in range(h):
                pg = self.page_table[i, t // PS]
                hist[pg, 0, :, t % PS, :] = self.k_full[i][t]
                hist[pg, 1, :, t % PS, :] = self.v_full[i][t]
        pages = jnp.asarray(hist)
        # slice tokens go through write_ragged_kv (the production path)
        token_seq, token_loc, valid = (
            np.full((self.T,), -1, np.int32),
            np.zeros((self.T,), np.int32), None)
        self.token_pos = np.zeros((self.T,), np.int32)
        k_slice = np.zeros((self.T, NKV, d), np.float32)
        v_slice = np.zeros((self.T, NKV, d), np.float32)
        for i, (h, qn) in enumerate(lanes):
            for j in range(qn):
                t = self.q_start[i] + j
                token_seq[t] = i
                self.token_pos[t] = h + j
                k_slice[t] = self.k_full[i][h + j]
                v_slice[t] = self.v_full[i][h + j]
        self.token_seq = token_seq
        self.quantized = quantized
        if quantized:
            # quantize the PRE-WRITTEN history pages row-wise (the cache
            # layout: int8 [P, 2, nkv, ps, d] + scales [P, 2, nkv, ps])
            qp, sp = quantize_rows(pages)
            kv = (qp, sp)
            self.kv_pages = write_ragged_kv(
                kv, jnp.asarray(k_slice), jnp.asarray(v_slice),
                jnp.asarray(self.page_table), jnp.asarray(token_seq),
                jnp.asarray(self.token_pos), PS)
            # the oracle must see the QUANTIZED values (int8 is lossy)
            from kserve_tpu.ops.kv_write import dequantize_rows

            deq = dequantize_rows(
                self.kv_pages[0].transpose(0, 1, 3, 2, 4),
                self.kv_pages[1].transpose(0, 1, 3, 2),
                jnp.float32,
            )  # [num_pages, 2, ps, nkv, d]
            deq = np.asarray(deq).transpose(0, 1, 3, 2, 4)
            for i, (h, qn) in enumerate(lanes):
                for t in range(h + qn):
                    pg = self.page_table[i, t // PS]
                    self.k_full[i][t] = deq[pg, 0, :, t % PS, :]
                    self.v_full[i][t] = deq[pg, 1, :, t % PS, :]
        else:
            self.kv_pages = write_ragged_kv(
                pages, jnp.asarray(k_slice), jnp.asarray(v_slice),
                jnp.asarray(self.page_table), jnp.asarray(token_seq),
                jnp.asarray(self.token_pos), PS)

    def oracle(self) -> np.ndarray:
        """Dense causal attention per lane, full-precision numpy."""
        d = self.d
        scale = self.scale if self.scale is not None else 1.0 / d ** 0.5
        out = np.zeros((self.T, NQ, d), np.float32)
        group = NQ // NKV
        for i, (h, qn) in enumerate(self.lanes):
            for j in range(qn):
                t = self.q_start[i] + j
                pos = h + j
                lo = 0
                if self.window and self.window > 0:
                    lo = max(0, pos - self.window + 1)
                k = self.k_full[i][lo:pos + 1]  # [L, nkv, d]
                v = self.v_full[i][lo:pos + 1]
                for hq in range(NQ):
                    kv_head = hq // group
                    s = (k[:, kv_head, :] @ self.q[t, hq]) * scale
                    if self.softcap > 0.0:
                        s = np.tanh(s / self.softcap) * self.softcap
                    w = np.exp(s - s.max())
                    w = w / w.sum()
                    out[t, hq] = w @ v[:, kv_head, :]
        return out

    def args(self):
        return (
            jnp.asarray(self.q), self.kv_pages,
            jnp.asarray(self.page_table), jnp.asarray(self.q_start),
            jnp.asarray(self.q_len), jnp.asarray(self.kv_start),
        )


CASES = {
    "mixed": [(10, 1), (8, 5), (0, 7), (0, 0)],
    "pure_prefill": [(0, 7), (0, 12), (0, 3)],
    "pure_decode": [(10, 1), (3, 1), (17, 1), (1, 1)],
    "chunked": [(8, 8), (16, 5), (0, 1)],
    "all_inactive_tail": [(5, 1), (0, 0), (0, 0)],
}


def _xla(case, window=None):
    win = jnp.asarray(window, jnp.int32) if window is not None else None
    return np.asarray(ragged_paged_attention_xla(
        *case.args(), logit_softcap=case.softcap, scale=case.scale,
        window=win))


def _pallas(case, window=None):
    win = jnp.asarray(window if window is not None else 0, jnp.int32)
    return np.asarray(ragged_paged_attention_pallas(
        *case.args(), window=win, logit_softcap=case.softcap,
        scale=case.scale, interpret=True))


def _assert_close(got, want, case, atol=2e-4):
    # compare only valid rows; invalid rows must be EXACT zero
    valid = case.token_seq >= 0
    np.testing.assert_allclose(got[valid], want[valid], atol=atol, rtol=2e-4)
    assert np.all(got[~valid] == 0.0)


class TestRaggedXLAReference:
    @pytest.mark.parametrize("name", sorted(CASES))
    def test_matches_dense_oracle(self, name):
        case = RaggedCase(CASES[name], seed=hash(name) % 1000)
        _assert_close(_xla(case), case.oracle(), case)

    def test_sliding_window(self):
        case = RaggedCase(CASES["mixed"], seed=3, window=4)
        _assert_close(_xla(case, window=4), case.oracle(), case)

    def test_softcap_and_scale(self):
        case = RaggedCase(CASES["chunked"], seed=5, softcap=8.0, scale=0.17)
        _assert_close(_xla(case), case.oracle(), case)

    def test_int8_kv(self):
        case = RaggedCase(CASES["mixed"], seed=7, quantized=True)
        _assert_close(_xla(case), case.oracle(), case, atol=5e-2)

    def test_token_metadata_roundtrip(self):
        case = RaggedCase(CASES["mixed"], seed=1)
        token_seq, token_loc, valid = ragged_token_metadata(
            jnp.asarray(case.q_start), jnp.asarray(case.q_len), case.T)
        np.testing.assert_array_equal(np.asarray(token_seq), case.token_seq)
        got_valid = np.asarray(valid)
        np.testing.assert_array_equal(got_valid, case.token_seq >= 0)


class TestRaggedPallasKernel:
    @pytest.mark.parametrize("name", sorted(CASES))
    def test_interpret_matches_reference(self, name):
        case = RaggedCase(CASES[name], seed=hash(name) % 1000)
        _assert_close(_pallas(case), _xla(case), case)

    def test_interpret_matches_oracle_mixed(self):
        case = RaggedCase(CASES["mixed"], seed=11)
        _assert_close(_pallas(case), case.oracle(), case)

    def test_sliding_window(self):
        case = RaggedCase(CASES["mixed"], seed=13, window=4)
        _assert_close(_pallas(case, window=4), _xla(case, window=4), case)
        _assert_close(_pallas(case, window=4), case.oracle(), case)

    def test_int8_kv(self):
        # the XLA reference dequantizes int8 pages to bf16 (the bandwidth
        # the int8 cache exists to save); the kernel dequantizes in f32 —
        # compare both against the dequantized oracle, and against each
        # other at bf16 granularity
        case = RaggedCase(CASES["pure_prefill"], seed=17, quantized=True)
        _assert_close(_pallas(case), case.oracle(), case, atol=5e-2)
        _assert_close(_pallas(case), _xla(case), case, atol=2e-2)

    def test_softcap_and_scale(self):
        case = RaggedCase(CASES["pure_decode"], seed=19, softcap=6.0,
                          scale=0.21)
        _assert_close(_pallas(case), _xla(case), case)

    def test_unaligned_buffer_rejected(self):
        case = RaggedCase(CASES["mixed"], seed=23)
        q = jnp.asarray(case.q[: case.T - 1])
        with pytest.raises(ValueError, match="RAGGED_BQ"):
            ragged_paged_attention_pallas(
                q, case.kv_pages, jnp.asarray(case.page_table),
                jnp.asarray(case.q_start), jnp.asarray(case.q_len),
                jnp.asarray(case.kv_start), interpret=True)


class TestRaggedDispatch:
    def test_auto_dispatch_reference_on_cpu(self):
        """On a CPU backend auto-dispatch must take the gather reference
        (Mosaic cannot lower) — the production mixed program depends on
        this to run CPU test meshes."""
        case = RaggedCase(CASES["mixed"], seed=29)
        out = ragged_paged_attention(*case.args())
        _assert_close(np.asarray(out), _xla(case), case, atol=1e-5)

    def test_force_pallas_raises_on_bad_head_dim_off_tpu(self):
        case = RaggedCase(CASES["pure_decode"], seed=31)
        if jax.default_backend() == "tpu":
            pytest.skip("CPU-only guard")
        with pytest.raises(ValueError, match="head_dim"):
            ragged_paged_attention(*case.args(), use_pallas=True)


#: (kv_start, q_len) a lane: what a packed step holds beside its decode
#: lanes (lanes of ONE token, which the split hands to the decode kernel)
SPLIT_CASES = {
    "decode_lanes_beside_a_chunk": [(10, 1), (8, 5), (3, 1), (0, 7), (17, 1)],
    "chunk_of_2": [(10, 1), (6, 2), (31, 1)],
    "chunk_of_8": [(5, 1), (16, 8), (9, 1), (0, 8)],
    "chunk_of_9": [(12, 1), (7, 9), (40, 1)],
    "chunk_of_128": [(20, 1), (64, 128), (3, 1), (0, 128)],
    "empty_lanes": [(0, 0), (10, 1), (0, 0), (8, 5), (0, 0), (2, 1)],
    "one_token_prompt_at_0": [(0, 1), (23, 1), (0, 3)],
    "no_single_token_lane": [(8, 5), (0, 7), (0, 0), (16, 2)],
    "decode_only": [(10, 1), (3, 1), (17, 1), (1, 1), (63, 1), (8, 1)],
    "nine_lanes_two_blocks": [(h, 1) for h in (30, 2, 17, 0, 9, 44, 5, 61, 12)]
    + [(0, 0)] * 7,
}


class TestSingleTokenSplit:
    """The packed step's single-token lanes through the decode kernel
    (`ragged_single_token_split_pallas`, interpret mode): the ragged
    contract's answers, whatever the batch is made of."""

    @pytest.mark.parametrize("name", sorted(SPLIT_CASES))
    def test_split_is_the_xla_reference(self, name):
        lanes = SPLIT_CASES[name]
        case = RaggedCase(lanes, seed=len(name), W=32)
        got = np.asarray(ragged_single_token_split_pallas(
            *case.args(), interpret=True))
        _assert_close(got, _xla(case), case)
        # and the one kernel's, row for row
        _assert_close(got, _pallas(case), case, atol=1e-5)

    def test_softcap(self):
        case = RaggedCase(SPLIT_CASES["decode_lanes_beside_a_chunk"], seed=3,
                          softcap=6.0)
        got = np.asarray(ragged_single_token_split_pallas(
            *case.args(), logit_softcap=6.0, interpret=True))
        _assert_close(got, _xla(case), case)

    def test_the_ragged_call_walks_no_single_token_block(self):
        """What the ragged kernel is handed: `q_len` 0 where the slice is
        one token, so those blocks belong to nobody (-1: 0 pages)."""
        from kserve_tpu.ops.pallas_paged_attention import (
            _ragged_block_metadata)

        case = RaggedCase(SPLIT_CASES["chunk_of_9"], seed=5, W=32)
        q_len = jnp.asarray(case.q_len)
        block_seq, _ = _ragged_block_metadata(
            jnp.asarray(case.q_start), jnp.where(q_len == 1, 0, q_len),
            case.T // RAGGED_BQ, RAGGED_BQ)
        assert np.asarray(block_seq).tolist() == [-1, 1, 1, -1]


def _shapes(lanes=48, width=40, kv_heads=8, d=128, quantized=False):
    q = jax.ShapeDtypeStruct((512, 32, d), jnp.bfloat16)
    pages = jax.ShapeDtypeStruct((2300, 2, kv_heads, 16, d), jnp.bfloat16)
    if quantized:
        pages = (jax.ShapeDtypeStruct(pages.shape, jnp.int8),
                 jax.ShapeDtypeStruct(pages.shape[:-1], jnp.float32))
    return q, pages, jax.ShapeDtypeStruct((lanes, width), jnp.int32)


class TestSplitIsDerived:
    """`ragged_attention_path`: the split is traced where the same
    program's decode steps take the decode kernel, and nowhere else."""

    def test_decode_sat_s_shape_splits_on_a_tpu(self):
        assert ragged_attention_path(
            *_shapes(), backend="tpu") == "pallas_ragged+decode"

    @pytest.mark.parametrize("why,shapes,kwargs,path", [
        ("a window", {}, {"window": 4096}, "pallas_ragged"),
        ("a scale override", {}, {"scale": 0.1}, "pallas_ragged"),
        ("the dense packing", {}, {"dense_stride": 2}, "pallas_ragged"),
        ("int8 pages", {"quantized": True}, {}, "xla_gather"),
        ("pages of one K/V head: the decode steps gather",
         {"kv_heads": 1}, {}, "pallas_ragged"),
        ("pages of two K/V heads under 64 pages: the decode steps gather",
         {"kv_heads": 2, "width": 40}, {}, "pallas_ragged"),
        ("lanes no block divides: the decode steps gather",
         {"lanes": 1}, {}, "pallas_ragged"),
        ("heads the kernels cannot tile", {"d": 64}, {}, "xla_gather"),
        ("the reference asked for", {}, {"use_pallas": False}, "xla_gather"),
    ])
    def test_today_s_call_stays_for(self, why, shapes, kwargs, path):
        assert ragged_attention_path(
            *_shapes(**shapes), backend="tpu", **kwargs) == path, why

    def test_two_kv_heads_split_from_the_width_their_decode_steps_do(self):
        assert ragged_attention_path(
            *_shapes(kv_heads=2, width=64), backend="tpu"
        ) == "pallas_ragged+decode"

    @pytest.mark.parametrize("backend", ["cpu", "gpu"])
    def test_off_the_tpu_nothing_changes(self, backend):
        assert ragged_attention_path(
            *_shapes(), backend=backend) == "xla_gather"

    @pytest.mark.parametrize("lanes,width,kv_heads", [
        (48, 8, 8), (48, 40, 8), (12, 24, 16), (16, 80, 2), (8, 16, 1),
        (7, 40, 8)])
    def test_the_split_is_the_decode_steps_own_rule(
            self, lanes, width, kv_heads):
        """One predicate: wherever `paged_attention` auto-selects the
        kernel for the lanes, the packed step splits; and only there."""
        from kserve_tpu.ops.attention import _should_use_pallas

        decode = _should_use_pallas(
            128, False, width, lanes, "tpu", 16, kv_heads)
        path = ragged_attention_path(
            *_shapes(lanes, width, kv_heads), backend="tpu")
        assert path == ("pallas_ragged+decode" if decode else "pallas_ragged")


class TestDenseBlockPacking:
    """Dense-stride packing (docs/kernels.md, ISSUE 15): lanes at a
    static stride < RAGGED_BQ share kernel blocks — the speculative
    verify layout where lane i's (K+1)-token slice sits at offset
    i*stride.  The dense-block kernel variant must match the XLA gather
    reference (which is per-token and needs no invariant change) over
    active/inactive lanes, slice padding (stride > q_len), sliding
    windows and int8 pages."""

    def _dense_case(self, Kp, sp, B=8, seed=0, quantized=False):
        rng = np.random.RandomState(seed)
        T = B * sp
        assert T % RAGGED_BQ == 0
        W = 8
        cfg = KVCacheConfig(
            n_layers=1, n_kv_heads=NKV, head_dim=D, page_size=PS,
            num_pages=1 + B * W, max_pages_per_seq=W, dtype="float32")
        pages = jnp.asarray(
            rng.randn(*init_kv_pages(cfg)[0].shape).astype(np.float32))
        scales = None
        if quantized:
            # cache layout: int8 [P, 2, nkv, ps, d] + scales [P, 2, nkv, ps]
            pages, scales = quantize_rows(pages)
        page_table = np.zeros((B, W), np.int32)
        kv_start = rng.randint(0, 12, B).astype(np.int32)
        q_len = np.asarray(
            [0 if i % 3 == 2 else Kp for i in range(B)], np.int32)
        used = 1
        for i in range(B):
            for p in range(-(-(int(kv_start[i]) + Kp) // PS)):
                page_table[i, p] = used
                used += 1
        q = np.zeros((T, NQ, D), np.float32)
        tok_seq = np.full((T,), -1, np.int32)
        tok_pos = np.zeros((T,), np.int32)
        for i in range(B):
            for j in range(int(q_len[i])):
                r = i * sp + j
                q[r] = rng.randn(NQ, D)
                tok_seq[r] = i
                tok_pos[r] = kv_start[i] + j
        kv = (pages, scales) if quantized else pages
        k_new = rng.randn(T, NKV, D).astype(np.float32)
        v_new = rng.randn(T, NKV, D).astype(np.float32)
        kv = write_ragged_kv(kv, jnp.asarray(k_new), jnp.asarray(v_new),
                             jnp.asarray(page_table), jnp.asarray(tok_seq),
                             jnp.asarray(tok_pos), PS)
        q_start = (np.arange(B) * sp).astype(np.int32)
        return (jnp.asarray(q), kv, jnp.asarray(page_table),
                jnp.asarray(q_start), jnp.asarray(q_len),
                jnp.asarray(kv_start))

    @pytest.mark.parametrize("Kp,sp", [(1, 1), (2, 2), (3, 4), (4, 4)])
    def test_dense_kernel_matches_xla_reference(self, Kp, sp):
        args = self._dense_case(Kp, sp)
        ref = ragged_paged_attention_xla(*args)
        got = ragged_paged_attention_pallas(
            *args, interpret=True, dense_stride=sp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)

    def test_dense_kernel_sliding_window(self):
        args = self._dense_case(3, 4, seed=3)
        win = jnp.asarray(5, jnp.int32)
        ref = ragged_paged_attention_xla(*args, window=win)
        got = ragged_paged_attention_pallas(
            *args, window=win, interpret=True, dense_stride=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)

    def test_dense_kernel_int8_pages(self):
        args = self._dense_case(2, 2, seed=5, quantized=True)
        ref = ragged_paged_attention_xla(*args)
        got = ragged_paged_attention_pallas(
            *args, interpret=True, dense_stride=2)
        # the XLA reference dequantizes to bf16 (bandwidth), the kernel
        # dequantizes in f32 — tolerance covers the bf16 rounding delta
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-2)

    def test_dense_stride_must_divide_block(self):
        args = self._dense_case(2, 2)
        with pytest.raises(ValueError, match="divide"):
            ragged_paged_attention_pallas(
                *args, interpret=True, dense_stride=3)

    def test_dense_buffer_length_must_match(self):
        q, kv, pt, qs, ql, ks = self._dense_case(2, 2)
        with pytest.raises(ValueError, match="B\\*stride"):
            ragged_paged_attention_pallas(
                q, kv, pt, qs, ql, ks, interpret=True, dense_stride=1)
