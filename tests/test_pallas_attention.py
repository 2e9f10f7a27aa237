"""Pallas paged-attention kernel vs XLA reference (interpret mode on CPU;
the compiled path runs on hardware via chip_smoke.py / the engine).

B=8 with MAX_SB=8 exercises the sequence-block kernel shape (whole block in
one grid step); B=6 exercises sb<max and the multi-grid-step path; B=5
exercises the odd-batch divisor fallback."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kserve_tpu.ops import pallas_paged_attention as pk
from kserve_tpu.ops.attention import _latent_as_kv, paged_attention_xla
from kserve_tpu.ops.pallas_paged_attention import (
    _pick_sb,
    latent_attention_decode_pallas,
    paged_attention_pallas,
)


def make_case(B=8, nq=8, nkv=4, d=64, ps=8, num_pages=80, max_pages=4, seed=0,
              dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, nq, d), dtype)
    # page-major cache layout (kvcache.py): [num_pages, 2, nkv, ps, d]
    kv = jnp.asarray(rng.randn(num_pages, 2, nkv, ps, d), dtype)
    # distinct pages per sequence, ragged lengths
    page_table = jnp.asarray(
        rng.permutation(np.arange(1, num_pages))[: B * max_pages].reshape(B, max_pages),
        jnp.int32,
    )
    seq_lens = jnp.asarray(rng.randint(1, max_pages * ps + 1, size=B), jnp.int32)
    return q, kv, page_table, seq_lens


def assert_paths_match(q, kv, pt, lens, **kwargs):
    ref = paged_attention_xla(q, kv, pt, lens, **kwargs)
    got = paged_attention_pallas(q, kv, pt, lens, interpret=True, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # guard against a vacuous comparison (both paths reading garbage that
    # happens to agree): the reference must actually attend to real data
    assert float(jnp.max(jnp.abs(ref))) > 1e-3


class TestPallasPagedAttention:
    """The decode kernel in interpret mode, which has no lane tiles: d=64
    is its math at another width (on the chip a head narrower than 128
    lanes takes the gather)."""

    @pytest.mark.parametrize("seed", [0, 1])
    @pytest.mark.parametrize("d", [64, 128])
    def test_matches_xla_full_block(self, seed, d):
        # B == MAX_SB: one grid step owns the whole batch
        assert_paths_match(*make_case(B=8, seed=seed, d=d))

    @pytest.mark.parametrize("B", [6, 5, 16])
    @pytest.mark.parametrize("d", [64, 128])
    def test_matches_xla_other_batches(self, B, d):
        assert_paths_match(*make_case(B=B, seed=2, d=d))

    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("width", [8, 40, 64])
    def test_matches_xla_at_cell_widths(self, width, d):
        """The table widths the benchmark's cells compile (decode-sat 8 to
        40, chat 64 and up), 16-token pages, two grid blocks: ragged
        lengths, one lane of length 1, one full lane and one EMPTY lane.
        An empty lane's output is never read (the paths average different
        masked positions there); it must be finite on both."""
        B, ps = 16, 16
        q, kv, pt, lens = make_case(
            B=B, nq=8, nkv=2, d=d, ps=ps, num_pages=B * width + 1,
            max_pages=width, seed=width)
        lens = np.array(lens)
        lens[3], lens[5], lens[B - 1] = 1, 0, width * ps
        live = lens > 0
        lens = jnp.asarray(lens)
        ref = np.asarray(paged_attention_xla(q, kv, pt, lens))
        got = np.asarray(
            paged_attention_pallas(q, kv, pt, lens, interpret=True))
        np.testing.assert_allclose(got[live], ref[live], rtol=2e-5, atol=2e-5)
        assert np.isfinite(got).all() and np.isfinite(ref).all()
        assert np.abs(ref[live]).max() > 1e-3

    @pytest.mark.parametrize("d", [64, 128])
    def test_gqa_groups(self, d):
        assert_paths_match(*make_case(nq=16, nkv=2, d=d))

    @pytest.mark.parametrize("d", [64, 128])
    def test_single_token_sequence(self, d):
        q, kv, pt, _ = make_case(d=d)
        lens = jnp.ones((q.shape[0],), jnp.int32)
        assert_paths_match(q, kv, pt, lens)

    @pytest.mark.parametrize("d", [64, 128])
    def test_softcap(self, d):
        assert_paths_match(*make_case(d=d), logit_softcap=30.0)

    def test_bf16_cache(self):
        # production dtype: bf16 pages, f32 accumulate, bf16 out
        q, kv, pt, lens = make_case(d=64, dtype=jnp.bfloat16)
        ref = paged_attention_xla(q, kv, pt, lens)
        got = paged_attention_pallas(q, kv, pt, lens, interpret=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_a_head_narrower_than_128_lanes_is_refused_on_the_chip(self):
        q, kv, pt, lens = make_case(d=64)
        with pytest.raises(ValueError, match="head_dim % 128 == 0"):
            paged_attention_pallas(q, kv, pt, lens)

    @pytest.mark.parametrize("width", [8, 16, 32, 40, 64, 128])
    def test_auto_dispatch_predicate(self, width):
        """The production predicate (attention._should_use_pallas) at the
        page-table widths the benchmark's cells compile: Qwen3-4B's pages
        (8 KV heads x 128, 64 KB) take the kernel at EVERY one of them —
        the gate measured on the chip, docs/kernels.md "Kernel against
        gather" — and fall back on every disqualifier."""
        from kserve_tpu.ops.attention import _should_use_pallas

        ok = dict(d=128, quantized=False, table_width=width, batch=48,
                  backend="tpu", page_size=16, kv_heads=8)
        assert _should_use_pallas(**ok)
        assert _should_use_pallas(**{**ok, "d": 256})
        assert _should_use_pallas(**{**ok, "batch": 8})
        assert _should_use_pallas(**{**ok, "kv_heads": 4})  # 32 KB pages
        # disqualifiers, one at a time
        assert not _should_use_pallas(**{**ok, "d": 96})
        assert not _should_use_pallas(**{**ok, "d": 64})
        assert not _should_use_pallas(**{**ok, "quantized": True})
        assert not _should_use_pallas(**{**ok, "batch": 13})  # prime > MAX_SB
        assert not _should_use_pallas(**{**ok, "backend": "cpu"})
        # small pages: the measured crossover, not a carried constant
        assert _should_use_pallas(**{**ok, "kv_heads": 2}) == (width >= 64)
        assert _should_use_pallas(**{**ok, "page_size": 7}) == (width >= 64)
        assert not _should_use_pallas(**{**ok, "kv_heads": 1})

    def test_scale_override_auto_falls_back(self):
        """A non-default scale (query_pre_attn_scalar without a sliding
        window) must auto-dispatch to the gather, not raise at trace time;
        an explicit use_pallas=True stays loud."""
        from kserve_tpu.ops.attention import paged_attention

        q, kv, pt, lens = make_case(B=8, d=64, max_pages=64,
                                    num_pages=64 * 8 + 1)
        ref = paged_attention_xla(q, kv, pt, lens, scale=0.5)
        got = paged_attention(q, kv, pt, lens, scale=0.5)  # auto
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        with pytest.raises(ValueError, match="scale override"):
            paged_attention(q, kv, pt, lens, scale=0.5, use_pallas=True)

    def test_pick_sb_covers_odd_batches(self):
        assert _pick_sb(48) == 8
        assert _pick_sb(49) == 7
        assert _pick_sb(6) == 6
        assert _pick_sb(5) == 5
        assert _pick_sb(13) == 1  # prime > MAX_SB: no divisor <= 8 except 1


@pytest.fixture
def lane_order(monkeypatch):
    """Inside `with lane_order():` the entry points hand the kernel its
    sequences as they come, as they did before PR 42.  The entry points are
    jitted (PR 45) and remember what they traced, so what they remember goes
    on the way in, and on the way out."""
    import contextlib

    def forget():
        for entry in vars(pk).values():
            if hasattr(entry, "clear_cache"):
                entry.clear_cache()

    @contextlib.contextmanager
    def unsorted():
        with monkeypatch.context() as m:
            m.setattr(pk, "_by_length", lambda call, *args: call(*args))
            forget()
            yield
        forget()

    return unsorted


#: form -> (head size or row, kernel call, XLA reference)
_LATENT = dict(scale=0.125, value_dim=64)
FORMS = {
    # K and V planes, head size 128: _decode_kernel
    "planes": (128, lambda q, kv, pt, lens: paged_attention_pallas(
        q, kv, pt, lens, interpret=True), paged_attention_xla),
    # one latent row a token, its first 64 columns the value: _decode_kernel
    # with value_dim
    "latent": (128, lambda q, kv, pt, lens: latent_attention_decode_pallas(
        q, kv, pt, lens, interpret=True, **_LATENT),
        lambda q, kv, pt, lens: paged_attention_xla(
            q, _latent_as_kv(kv), pt, lens, scale=_LATENT["scale"]
        )[..., :_LATENT["value_dim"]]),
}
PS, WIDTH = 8, 12


def _case(form, lens):
    """Lanes of the given lengths over 8-token pages and a 12-page table no
    lane fills; the table's entries past a lane's pages are the null page,
    as the engine pads them.  -> q, cache, the cache with a NaN null page,
    table, lengths, which lanes are live."""
    d = FORMS[form][0]
    planes = nkv = 1 if form == "latent" else 2
    lanes = len(lens)
    rng = np.random.RandomState(lanes)
    own = -(-lens // PS)
    assert own.max() < WIDTH
    table = rng.permutation(np.arange(1, lanes * WIDTH + 1)).reshape(
        lanes, WIDTH)
    table[np.arange(WIDTH)[None, :] >= own[:, None]] = 0
    q = jnp.asarray(rng.randn(lanes, 8, d), jnp.float32)
    kv = jnp.asarray(
        rng.randn(lanes * WIDTH + 1, planes, nkv, PS, d),
        jnp.float32).at[0].set(0.0)
    return (q, kv, kv.at[0].set(jnp.nan), jnp.asarray(table, jnp.int32),
            jnp.asarray(lens, jnp.int32), lens > 0)


class TestBlocksOfLikeLength:
    """PR 42: the entry points hand the kernel its sequences sorted by
    length, so that a block (which walks out to its longest sequence) holds
    sequences of like length."""

    @pytest.mark.parametrize("lanes", [12, 16], ids=["sb6", "sb8"])
    @pytest.mark.parametrize("form", sorted(FORMS))
    def test_every_lane_is_the_lane_order_kernel_s_bit_for_bit(
            self, form, lanes, lane_order):
        """Two blocks: lanes of 70 and 3 tokens side by side, empty seats,
        a lane that ends exactly on a page, the longest lane in the second
        block's seats."""
        kernel, reference = FORMS[form][1:]
        sb = _pick_sb(lanes)
        assert lanes // sb == 2 and sb == {12: 6, 16: 8}[lanes]
        lens = np.random.RandomState(lanes).randint(1, 5 * PS, size=lanes)
        lens[:5] = [70, 3, 2 * PS, 0, 1]
        lens[sb + 1], lens[sb + 2], lens[-1] = 9 * PS, 0, 5
        q, kv, _, pt, seq, live = _case(form, lens)
        got = np.asarray(kernel(q, kv, pt, seq))
        want = np.asarray(reference(q, kv, pt, seq))
        assert np.isfinite(got).all()  # an empty seat writes finite numbers
        np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
        assert np.abs(want[live]).max() > 1e-3
        with lane_order():
            unsorted = np.asarray(kernel(q, kv, pt, seq))
        # each row is computed from its own sequence alone: the same pages
        # in the same order through the same accumulator
        np.testing.assert_array_equal(got[live], unsorted[live])

    @pytest.mark.parametrize("lanes", [12, 16], ids=["sb6", "sb8"])
    @pytest.mark.parametrize("form", sorted(FORMS))
    def test_a_block_of_equal_lanes_never_reads_the_null_page(
            self, form, lanes, lane_order):
        """Half the lanes hold two pages (9-16 tokens) and half hold nine,
        seated alternately.  In lane order every block walks nine pages and
        the short lanes fetch the null page seven times each: filled with
        NaN it reaches their outputs (0 x NaN).  Sorted, one block walks
        two pages and the other nine, and nobody fetches it."""
        kernel, reference = FORMS[form][1:]
        rng = np.random.RandomState(lanes + 1)
        lens = np.where(np.arange(lanes) % 2 == 0,
                        rng.randint(PS + 1, 2 * PS + 1, size=lanes),
                        rng.randint(8 * PS + 1, 9 * PS + 1, size=lanes))
        lens[2], lens[3] = 2 * PS, 9 * PS  # ending exactly on a page
        q, kv, poisoned, pt, seq, _ = _case(form, lens)
        got = np.asarray(kernel(q, poisoned, pt, seq))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, np.asarray(reference(q, kv, pt, seq)), rtol=2e-5, atol=2e-5)
        with lane_order():
            unsorted = np.asarray(kernel(q, poisoned, pt, seq))
        assert np.isnan(unsorted[0::2]).all()
        assert np.isfinite(unsorted[1::2]).all()

    @pytest.mark.parametrize("lanes", [16, 12, 48])
    def test_by_length_sorts_for_the_call_and_puts_the_rows_back(self, lanes):
        rng = np.random.RandomState(lanes)
        lens = jnp.asarray(rng.randint(0, 640, size=lanes), jnp.int32)
        table = jnp.asarray(rng.randint(1, 99, size=(lanes, 5)), jnp.int32)
        q = jnp.asarray(rng.randn(lanes, 4, 8), jnp.float32)
        seen = {}

        def call(table_, lens_, q_, kv_):
            seen.update(table=table_, lens=lens_, q=q_, kv=kv_)
            return q_ * 2.0 + lens_[:, None, None]

        out = pk._by_length(call, table, lens, q, "the cache")
        assert seen["kv"] == "the cache"
        dealt = np.asarray(seen["lens"])
        assert (np.diff(dealt) >= 0).all() and sorted(dealt) == sorted(
            np.asarray(lens))
        # a lane's table row and query travel with its length
        order = np.argsort(np.asarray(lens), kind="stable")
        np.testing.assert_array_equal(np.asarray(seen["table"]),
                                      np.asarray(table)[order])
        np.testing.assert_array_equal(np.asarray(seen["q"]),
                                      np.asarray(q)[order])
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(q * 2.0 + lens[:, None, None]))

    @pytest.mark.parametrize("lanes", [8, 13],
                             ids=["one-block", "blocks-of-one"])
    def test_nothing_is_gathered_where_nothing_is_to_sort(self, lanes):
        """One block holds every sequence, or every block holds one (13
        lanes: no divisor up to MAX_SB but 1) and walks its own pages."""
        lens = jnp.asarray(np.arange(lanes)[::-1].copy(), jnp.int32)

        def call(table, lens_, q, kv):
            assert lens_ is lens
            return q

        q = jnp.ones((lanes, 2, 4))
        assert pk.length_order(lens) is None
        assert pk._by_length(call, jnp.zeros((lanes, 3), jnp.int32),
                             lens, q, None) is q

    def test_length_order_is_a_stable_sort_and_its_inverse(self):
        lens = jnp.asarray([7, 0, 7, 3, 0, 9, 3, 3, 1, 0, 640, 2], jnp.int32)
        order, rank = pk.length_order(lens)
        np.testing.assert_array_equal(
            np.asarray(order), np.argsort(np.asarray(lens), kind="stable"))
        np.testing.assert_array_equal(np.asarray(order)[np.asarray(rank)],
                                      np.arange(12))
        rows = jnp.arange(24.0).reshape(12, 2)
        np.testing.assert_array_equal(
            np.asarray(pk.rows_at(pk.rows_at(rows, order), rank)),
            np.asarray(rows))


class TestShardedPagedAttention:
    """The kernel under TP (shard_map over the model axis) — VERDICT #6.
    Each device runs the kernel on its local heads; numerics must match
    the unsharded XLA reference exactly (no collectives involved)."""

    def _mesh(self, tp):
        from kserve_tpu.parallel.sharding import create_mesh

        return create_mesh(tp=tp)

    @pytest.mark.parametrize("tp", [2, 4])
    def test_interpret_kernel_under_tp(self, tp):
        from kserve_tpu.ops.attention import make_sharded_paged_attention

        q, kv, pt, lens = make_case(B=8, nq=8, nkv=4, d=64)
        mesh = self._mesh(tp)
        fn = make_sharded_paged_attention(mesh, interpret=True)
        ref = paged_attention_xla(q, kv, pt, lens)
        got = jax.jit(fn)(q, kv, pt, lens, jnp.asarray(0, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert float(jnp.max(jnp.abs(ref))) > 1e-3

    def test_gather_path_under_tp(self):
        """use_pallas=False through the same wrapper (the auto-dispatch
        short-context case still runs sharded)."""
        from kserve_tpu.ops.attention import make_sharded_paged_attention

        q, kv, pt, lens = make_case(B=8, nq=16, nkv=2, d=64)
        mesh = self._mesh(2)
        fn = make_sharded_paged_attention(mesh, use_pallas=False)
        ref = paged_attention_xla(q, kv, pt, lens)
        got = jax.jit(fn)(q, kv, pt, lens, jnp.asarray(0, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_windowed_under_tp(self):
        """windowed=True (Gemma-2-class configs): the traced per-layer
        scalar rides through to the gather path; numerics must match the
        unsharded windowed reference."""
        from kserve_tpu.ops.attention import make_sharded_paged_attention

        q, kv, pt, lens = make_case(B=8, nq=8, nkv=4, d=64)
        mesh = self._mesh(2)
        fn = make_sharded_paged_attention(mesh, windowed=True)
        w = jnp.asarray(4, jnp.int32)
        ref = paged_attention_xla(q, kv, pt, lens, window=w)
        got = jax.jit(fn)(q, kv, pt, lens, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # the windowed result must actually differ from full attention
        full = paged_attention_xla(q, kv, pt, lens)
        assert float(jnp.max(jnp.abs(ref - full))) > 1e-3

    def test_interpret_rejects_window_and_scale(self):
        from kserve_tpu.ops.attention import make_sharded_paged_attention

        mesh = self._mesh(2)
        with pytest.raises(ValueError, match="neither"):
            make_sharded_paged_attention(mesh, interpret=True, windowed=True)
        with pytest.raises(ValueError, match="neither"):
            make_sharded_paged_attention(mesh, interpret=True, scale=0.5)

    def test_engine_tp2_builds_sharded_decode(self):
        """The engine no longer forces use_pallas off under tp>1: the
        decode path is built with the shard_map wrapper instead."""
        from kserve_tpu.engine.engine import EngineConfig, LLMEngine
        from kserve_tpu.engine.tokenizer import ByteTokenizer
        from kserve_tpu.models.llama import LlamaConfig

        mc = LlamaConfig.tiny(dtype="float32")
        cfg = EngineConfig(max_batch_size=4, page_size=8, num_pages=64,
                           max_pages_per_seq=8, max_prefill_len=32,
                           prefill_buckets=(32,), dtype="float32", tp=2)
        engine = LLMEngine(mc, cfg, ByteTokenizer(mc.vocab_size), rng_seed=0)
        # auto stays auto (not forced False) — the sharded wrapper decides
        assert engine.config.use_pallas is None
