"""LLM engine tests: streaming generation, continuous batching, stop
conditions, greedy determinism — tiny model, 8-device CPU mesh (tp=2)."""

import asyncio

import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, GenerationOutput, LLMEngine
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer, IncrementalDetokenizer
from kserve_tpu.models.llama import LlamaConfig

from conftest import async_test


def make_engine(tp=1, **cfg_overrides):
    model_config = LlamaConfig.tiny(dtype="float32")
    cfg = dict(
        max_batch_size=4,
        page_size=8,
        num_pages=64,
        max_pages_per_seq=8,
        max_prefill_len=32,
        prefill_buckets=(16, 32),
        tp=tp,
        dtype="float32",
        use_pallas=False,
    )
    cfg.update(cfg_overrides)
    tokenizer = ByteTokenizer(model_config.vocab_size)
    return LLMEngine(model_config, EngineConfig(**cfg), tokenizer)


async def collect(engine, prompt, params):
    outs = []
    async for out in engine.generate(prompt, params):
        outs.append(out)
    return outs


class TestEngine:
    def test_tokenizer_vocab_overflow_rejected(self):
        """A tokenizer whose ids can exceed the embedding table must be
        rejected at init — under jit the lookups silently clamp, and the
        host-side penalty prompt mask IndexErrors (found by a live drive
        with ByteTokenizer(259) against a vocab-256 model)."""
        import pytest

        mc = LlamaConfig.tiny(dtype="float32", vocab_size=256)
        with pytest.raises(ValueError, match="tokenizer vocab"):
            LLMEngine(mc, EngineConfig(max_batch_size=2, page_size=8,
                                       num_pages=16, max_pages_per_seq=4,
                                       max_prefill_len=16,
                                       prefill_buckets=(16,),
                                       dtype="float32"),
                      ByteTokenizer(256))  # clamps itself to >= 259

    @async_test
    async def test_generate_streams_tokens(self):
        engine = make_engine()
        await engine.start()
        try:
            outs = await collect(
                engine, [1, 2, 3, 4], SamplingParams(max_tokens=8, temperature=0.0)
            )
            assert len(outs) == 8
            assert outs[-1].finished
            assert outs[-1].finish_reason in ("stop", "length")
            assert all(isinstance(o.token_id, int) for o in outs)
        finally:
            await engine.stop()

    @async_test
    async def test_greedy_is_deterministic(self):
        engine = make_engine()
        await engine.start()
        try:
            a = await collect(engine, [5, 6, 7], SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True))
            b = await collect(engine, [5, 6, 7], SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True))
            assert [o.token_id for o in a] == [o.token_id for o in b]
        finally:
            await engine.stop()

    @async_test
    async def test_concurrent_requests_batched(self):
        engine = make_engine()
        await engine.start()
        try:
            results = await asyncio.gather(
                collect(engine, [1, 2], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)),
                collect(engine, [3, 4], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)),
                collect(engine, [5, 6], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)),
            )
            for outs in results:
                assert len(outs) == 5
                assert outs[-1].finished
        finally:
            await engine.stop()

    @async_test
    async def test_batching_matches_solo_greedy(self):
        """Tokens from a batched run must equal a solo run (slot isolation)."""
        engine = make_engine()
        await engine.start()
        try:
            solo = await collect(engine, [9, 8, 7], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True))
            batched = await asyncio.gather(
                collect(engine, [9, 8, 7], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)),
                collect(engine, [1, 1, 1, 1, 1], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)),
            )
            assert [o.token_id for o in solo] == [o.token_id for o in batched[0]]
        finally:
            await engine.stop()

    @async_test
    async def test_tp2_matches_tp1_greedy(self):
        e1 = make_engine(tp=1)
        e2 = make_engine(tp=2)
        # same weights: both engines seed params identically (PRNGKey(1))
        await e1.start()
        await e2.start()
        try:
            a = await collect(e1, [4, 4, 4], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True))
            b = await collect(e2, [4, 4, 4], SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True))
            assert [o.token_id for o in a] == [o.token_id for o in b]
        finally:
            await e1.stop()
            await e2.stop()

    @async_test
    async def test_max_tokens_respected(self):
        engine = make_engine()
        await engine.start()
        try:
            outs = await collect(engine, [1], SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True))
            assert len(outs) == 3
            assert outs[-1].finish_reason == "length"
        finally:
            await engine.stop()

    @async_test
    async def test_prompt_too_long_rejected(self):
        engine = make_engine()
        await engine.start()
        try:
            with pytest.raises(ValueError):
                async for _ in engine.generate(list(range(100)), SamplingParams()):
                    pass
        finally:
            await engine.stop()

    @async_test
    async def test_more_requests_than_slots(self):
        engine = make_engine(max_batch_size=2)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[
                    collect(engine, [i + 1], SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True))
                    for i in range(5)
                ]
            )
            assert all(len(r) == 4 for r in results)
        finally:
            await engine.stop()


class TestDetokenizer:
    def test_incremental_utf8(self):
        tok = ByteTokenizer()
        detok = IncrementalDetokenizer(tok)
        text = "héllo ✓"
        deltas = [detok.push(t) for t in text.encode("utf-8")]
        assert "".join(deltas) == text
        # multibyte chars must not emit partial replacement chars
        assert "�" not in "".join(deltas)


class TestSeededSampling:
    @async_test
    async def test_seed_reproducible_across_batching(self):
        """Same seed + temperature>0 must reproduce tokens even when the
        batch composition differs (per-lane PRNG streams)."""
        engine = make_engine()
        await engine.start()
        try:
            p = SamplingParams(max_tokens=6, temperature=1.0, seed=42, ignore_eos=True)
            solo = await collect(engine, [7, 8, 9], p)
            batched = await asyncio.gather(
                collect(engine, [7, 8, 9], p),
                collect(engine, [1, 2], SamplingParams(max_tokens=6, temperature=1.0, ignore_eos=True)),
            )
            assert [o.token_id for o in solo] == [o.token_id for o in batched[0]]
        finally:
            await engine.stop()

    @async_test
    async def test_different_seeds_differ(self):
        engine = make_engine()
        await engine.start()
        try:
            a = await collect(engine, [7, 8, 9], SamplingParams(max_tokens=8, temperature=1.0, seed=1, ignore_eos=True))
            b = await collect(engine, [7, 8, 9], SamplingParams(max_tokens=8, temperature=1.0, seed=2, ignore_eos=True))
            assert [o.token_id for o in a] != [o.token_id for o in b]
        finally:
            await engine.stop()


class TestPenalties:
    @async_test
    async def test_frequency_penalty_blocks_repeats(self):
        """A huge frequency penalty makes every generated token distinct
        (greedy decoding would otherwise happily loop)."""
        engine = make_engine()
        await engine.start()
        try:
            outs = await collect(
                engine,
                [1, 2, 3, 4],
                SamplingParams(
                    max_tokens=12, temperature=0.0, frequency_penalty=1000.0,
                    ignore_eos=True,
                ),
            )
            tokens = [o.token_id for o in outs]
            assert len(tokens) == len(set(tokens)), tokens
        finally:
            await engine.stop()

    @async_test
    async def test_penalized_and_plain_coexist_in_batch(self):
        """One penalized + one plain request decode together; the plain
        request is bit-identical to running alone (penalties must not leak
        across lanes)."""
        engine = make_engine()
        await engine.start()
        try:
            alone = await collect(
                engine, [5, 6, 7], SamplingParams(max_tokens=8, temperature=0.0)
            )
            plain, penalized = await asyncio.gather(
                collect(engine, [5, 6, 7], SamplingParams(max_tokens=8, temperature=0.0)),
                collect(
                    engine,
                    [9, 10, 11],
                    SamplingParams(
                        max_tokens=8, temperature=0.0, repetition_penalty=1.5
                    ),
                ),
            )
            assert [o.token_id for o in plain] == [o.token_id for o in alone]
            assert penalized[-1].finished
        finally:
            await engine.stop()


class TestPreemption:
    """VERDICT #6: page exhaustion must preempt, not truncate."""

    def _squeezed_engine(self, **overrides):
        # 8 pages (7 usable) x page_size 8 = 56 token positions; two
        # 4+44-token requests need 12 pages total -> guaranteed exhaustion
        cfg = dict(num_pages=8, max_pages_per_seq=8, max_batch_size=4)
        cfg.update(overrides)
        return make_engine(**cfg)

    async def _roomy_reference(self, prompts, params):
        engine = make_engine(num_pages=64, max_pages_per_seq=8, max_batch_size=4)
        await engine.start()
        try:
            return [
                [o.token_id for o in await collect(engine, p, params)]
                for p in prompts
            ]
        finally:
            await engine.stop()

    @async_test
    async def test_both_long_requests_complete_full_length(self):
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        want = await self._roomy_reference(prompts, params)
        engine = self._squeezed_engine()
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, p, params) for p in prompts]
            )
        finally:
            await engine.stop()
        for outs, want_tokens in zip(results, want):
            # full length: not silently truncated under KV pressure
            assert outs[-1].num_generated == 44
            assert [o.token_id for o in outs] == want_tokens
        assert engine.preemption_count > 0, "cache was supposed to saturate"

    @async_test
    async def test_host_offload_spills_and_restores(self):
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        want = await self._roomy_reference(prompts, params)
        engine = self._squeezed_engine(kv_offload="host", kv_offload_gib=1.0)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, p, params) for p in prompts]
            )
        finally:
            await engine.stop()
        for outs, want_tokens in zip(results, want):
            assert outs[-1].num_generated == 44
            assert [o.token_id for o in outs] == want_tokens
        assert engine.preemption_count > 0
        # pages went host-side and came back; budget fully returned
        assert engine._offload_bytes == 0
        assert engine.allocator.free_pages == engine.config.num_pages - 1

    @async_test
    async def test_host_offload_under_pp(self):
        """pp x kv_offload: preempted slots spill the STACKED cache's
        pages to the host tier and re-inject on resume with one scatter
        across every stage; outputs match the roomy pp=1 reference."""
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        want = await self._roomy_reference(prompts, params)
        engine = self._squeezed_engine(
            pp=2, kv_offload="host", kv_offload_gib=1.0)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, p, params) for p in prompts]
            )
        finally:
            await engine.stop()
        for outs, want_tokens in zip(results, want):
            assert outs[-1].num_generated == 44
            assert [o.token_id for o in outs] == want_tokens
        assert engine.preemption_count > 0
        assert engine._offload_bytes == 0
        # same allocator-leak bar as the pp=1 variant: every page returned
        assert engine.allocator.free_pages == engine.config.num_pages - 1

    @async_test
    async def test_host_offload_under_pp_with_kv_quant(self):
        """pp x int8 KV x host tier: the quantized stacked cache spills
        (pages AND scales) and re-injects; int8 rounding means the bar is
        full-length completion, not bit parity."""
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        engine = self._squeezed_engine(
            pp=2, kv_quant="int8", kv_offload="host", kv_offload_gib=1.0)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, p, params) for p in prompts]
            )
        finally:
            await engine.stop()
        for outs in results:
            assert outs[-1].num_generated == 44
        assert engine.preemption_count > 0
        assert engine._offload_bytes == 0
        assert engine.allocator.free_pages == engine.config.num_pages - 1


class TestChunkedPrefill:
    """Prompts beyond max_prefill_len prefill in history-attending chunks."""

    @async_test
    async def test_long_prompt_matches_single_shot(self):
        prompt = [(3 + i * 7) % 500 + 3 for i in range(50)]
        params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
        # reference: an engine whose bucket swallows the prompt whole
        big = make_engine(
            max_prefill_len=64, prefill_buckets=(64,),
            num_pages=64, max_pages_per_seq=16,
        )
        await big.start()
        try:
            want = [o.token_id for o in await collect(big, prompt, params)]
        finally:
            await big.stop()
        # chunked: 16-token chunks, 50-token prompt -> 4 chunks
        small = make_engine(
            max_prefill_len=16, prefill_buckets=(16,),
            num_pages=64, max_pages_per_seq=16,
        )
        await small.start()
        try:
            got = [o.token_id for o in await collect(small, prompt, params)]
        finally:
            await small.stop()
        assert got == want

    @async_test
    async def test_chunked_and_batched_requests_coexist(self):
        engine = make_engine(
            max_prefill_len=16, prefill_buckets=(16,),
            num_pages=64, max_pages_per_seq=16,
        )
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        long_prompt = list(range(3, 43))  # 40 tokens -> chunked
        short_prompt = [5, 6, 7]  # batched path
        await engine.start()
        try:
            long_outs, short_outs = await asyncio.gather(
                collect(engine, long_prompt, params),
                collect(engine, short_prompt, params),
            )
            assert long_outs[-1].finished and short_outs[-1].finished
            assert long_outs[-1].num_prompt_tokens == 40
        finally:
            await engine.stop()

    @async_test
    async def test_preempted_long_sequence_resumes_by_chunked_recompute(self):
        """pos > max_prefill_len no longer forces truncation or host spill:
        chunked re-prefill recomputes on resume."""
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        roomy = make_engine(
            max_prefill_len=16, prefill_buckets=(16,),
            num_pages=64, max_pages_per_seq=8,
        )
        await roomy.start()
        try:
            want = [
                [o.token_id for o in await collect(roomy, p, params)]
                for p in prompts
            ]
        finally:
            await roomy.stop()
        squeezed = make_engine(
            max_prefill_len=16, prefill_buckets=(16,),
            num_pages=8, max_pages_per_seq=8,
        )
        await squeezed.start()
        try:
            results = await asyncio.gather(
                *[collect(squeezed, p, params) for p in prompts]
            )
            assert squeezed.preemption_count > 0
            for outs, want_tokens in zip(results, want):
                assert outs[-1].num_generated == 44
                assert [o.token_id for o in outs] == want_tokens
        finally:
            await squeezed.stop()


class TestPrefixCache:
    """Full prompt pages are cached, shared and LRU-evicted."""

    def _engine(self, **overrides):
        cfg = dict(
            max_prefill_len=16, prefill_buckets=(16,),
            num_pages=64, max_pages_per_seq=8, max_batch_size=4,
        )
        cfg.update(overrides)
        return make_engine(**cfg)

    @async_test
    async def test_second_request_reuses_prefix_pages(self):
        engine = self._engine()
        shared_prefix = list(range(3, 35))  # 32 tokens = 4 full pages
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        await engine.start()
        try:
            first = [o.token_id for o in await collect(
                engine, shared_prefix + [100, 101], params)]
            assert engine.prefix_cache_hits == 0
            second = [o.token_id for o in await collect(
                engine, shared_prefix + [100, 101], params)]
            # identical prompt: all 4 full pages reused
            assert engine.prefix_cache_hits == 4
            assert second == first  # reused KV is the same KV
            # divergent tail still shares the common prefix
            await collect(engine, shared_prefix + [200, 201], params)
            assert engine.prefix_cache_hits == 8
        finally:
            await engine.stop()

    @async_test
    async def test_different_prefix_no_hit(self):
        engine = self._engine()
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        await engine.start()
        try:
            await collect(engine, list(range(3, 35)), params)
            await collect(engine, list(range(103, 135)), params)
            assert engine.prefix_cache_hits == 0
        finally:
            await engine.stop()

    @async_test
    async def test_cache_reuse_matches_uncached_engine(self):
        """Output through a cache hit is bit-identical to a cold engine."""
        prompt = list(range(7, 47))  # 40 tokens
        params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
        cold = self._engine(prefix_cache=False)
        await cold.start()
        try:
            want = [o.token_id for o in await collect(cold, prompt, params)]
        finally:
            await cold.stop()
        warm = self._engine()
        await warm.start()
        try:
            await collect(warm, prompt, params)  # populate
            got = [o.token_id for o in await collect(warm, prompt, params)]
            assert warm.prefix_cache_hits > 0
            assert got == want
        finally:
            await warm.stop()

    @async_test
    async def test_eviction_under_pressure_keeps_serving(self):
        """A small allocator: cached pages are evicted rather than blocking
        new admissions; everything still completes full-length."""
        engine = self._engine(num_pages=16, max_batch_size=2)
        params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
        await engine.start()
        try:
            for base in (0, 40, 80, 120):
                outs = await collect(
                    engine, [3 + base + i for i in range(32)], params)
                assert outs[-1].num_generated == 8
            # the 16-page allocator can't hold 4 x 4 cached pages + live
            # sequences: eviction must have kicked in
            assert len(engine._prefix_cache) * 1 < 16
        finally:
            await engine.stop()

    @async_test
    async def test_cache_hits_stay_batched(self):
        """Short prompts with cached prefixes go through BATCHED admission
        (per-row chunk_start), never the serial chunked path."""
        engine = self._engine()
        prefix = list(range(3, 35))  # 4 full pages
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        await engine.start()
        try:
            await collect(engine, prefix + [100, 101], params)  # warm

            def no_serial(*a, **k):
                raise AssertionError("serial _admit_chunked used for a short cached prompt")

            engine._admit_chunked = no_serial
            results = await asyncio.gather(
                collect(engine, prefix + [110, 111], params),
                collect(engine, prefix + [120, 121], params),
            )
            assert all(r[-1].finished for r in results)
            assert engine.prefix_cache_hits == 8  # 4 pages x 2 requests
        finally:
            await engine.stop()


class TestInterleavedLongAdmission:
    @pytest.mark.parametrize("use_ragged", [None, False])
    @async_test
    async def test_decode_streams_continue_during_long_admission(
            self, use_ragged):
        """A long-prompt admission must not stall in-flight decode streams.
        Under the unified ragged program (use_ragged=None -> on) decode
        lanes advance IN the same dispatch as each prefill chunk; on the
        legacy path chunks and decode dispatches alternate.  Either way
        the short request keeps emitting while the long prompt admits."""
        engine = make_engine(
            max_prefill_len=16, prefill_buckets=(16,), num_pages=128,
            max_pages_per_seq=64, max_batch_size=4, use_ragged=use_ragged,
        )
        await engine.start()
        short_progress = []

        async def short():
            async for out in engine.generate(
                [1, 2, 3],
                SamplingParams(max_tokens=200, temperature=0.0, ignore_eos=True),
            ):
                short_progress.append(out.num_generated)

        try:
            task = asyncio.create_task(short())
            while not short_progress:  # short is live and decoding
                await asyncio.sleep(0.01)

            seen_at_chunk = []
            mixed = engine._use_mixed
            orig = engine._mixed_fn if mixed else engine._prefill_chunk_fn

            def spy(*args, **kwargs):
                if not mixed or any(
                    s.prefilling is not None for s in engine._slots
                    if s.request_id is not None
                ):
                    seen_at_chunk.append(short_progress[-1])
                return orig(*args, **kwargs)

            if mixed:
                engine._mixed_fn = spy
            else:
                engine._prefill_chunk_fn = spy
            long_prompt = [3 + (i % 500) for i in range(400)]  # 25 chunks
            outs = await collect(
                engine, long_prompt,
                SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True),
            )
            assert outs[-1].finished
            assert len(seen_at_chunk) >= 20  # chunked as expected
            # the short stream advanced while the long prompt was admitting
            assert seen_at_chunk[-1] > seen_at_chunk[0], seen_at_chunk
        finally:
            task.cancel()
            await engine.stop()


class TestMixedBatchUnifiedDispatch:
    @async_test
    async def test_mixed_batch_one_dispatch_per_step(self):
        """Acceptance (ISSUE 9): with the unified ragged program enabled,
        a mixed batch — decode lanes advancing DURING an in-flight prompt
        chunk — is served by exactly ONE program dispatch per engine step.
        Every legacy program is patched to raise, so any residual
        prefill/decode dispatch fails the test; the FakeClock keeps the
        telemetry stamps deterministic (zero real sleeps in the engine)."""
        from kserve_tpu.engine.engine import EngineConfig, LLMEngine
        from kserve_tpu.engine.tokenizer import ByteTokenizer
        from kserve_tpu.resilience import FakeClock

        model_config = LlamaConfig.tiny(dtype="float32")
        clock = FakeClock()
        engine = LLMEngine(
            model_config,
            EngineConfig(
                max_batch_size=4, page_size=8, num_pages=128,
                max_pages_per_seq=64, max_prefill_len=16,
                prefill_buckets=(16,), dtype="float32", use_pallas=False,
            ),
            ByteTokenizer(model_config.vocab_size),
            clock=clock,
            metrics_label="mixed-acceptance",
        )
        assert engine._use_mixed

        def forbidden(*a, **k):
            raise AssertionError("legacy program dispatched in mixed mode")

        for name in ("_prefill_fn", "_prefill_lp_fn", "_prefill_chunk_fn",
                     "_decode_fn", "_decode_lp_fn", "_decode_penalized_fn",
                     "_decode_penalized_lp_fn"):
            setattr(engine, name, forbidden)

        short_progress = []
        dispatches = []
        orig = engine._mixed_fn

        def spy(*args, **kwargs):
            dispatches.append({
                "chunk_lanes": sum(
                    1 for s in engine._slots
                    if s.request_id is not None and s.prefilling is not None),
                "decode_lanes": sum(
                    1 for s in engine._slots
                    if s.request_id is not None and s.prefilling is None),
                "short_at": short_progress[-1] if short_progress else 0,
            })
            return orig(*args, **kwargs)

        engine._mixed_fn = spy
        await engine.start()

        async def short():
            async for out in engine.generate(
                [1, 2, 3],
                SamplingParams(max_tokens=120, temperature=0.0,
                               ignore_eos=True),
            ):
                short_progress.append(out.num_generated)

        try:
            task = asyncio.create_task(short())
            while not short_progress:
                await asyncio.sleep(0.01)
            long_prompt = [3 + (i % 400) for i in range(240)]  # many chunks
            outs = await collect(
                engine, long_prompt,
                SamplingParams(max_tokens=4, temperature=0.0,
                               ignore_eos=True))
            assert outs[-1].finished
            await task
        finally:
            await engine.stop()

        mixed = [d for d in dispatches
                 if d["chunk_lanes"] > 0 and d["decode_lanes"] > 0]
        assert len(mixed) >= 2, dispatches
        # the decode stream ADVANCED across chunk-carrying dispatches —
        # the prefill/decode scheduler barrier is gone
        assert mixed[-1]["short_at"] > mixed[0]["short_at"], mixed
        # and every step was one dispatch: no legacy program ever ran
        # (forbidden() would have raised) and the engine's composition
        # record shows simultaneous prefill+decode tokens
        comp = engine.last_step_composition
        assert set(comp) == {"prefill_tokens", "decode_tokens"}


class TestInt8KVCache:
    """Opt-in int8 KV quantization: half the decode KV traffic, bounded
    numeric error."""

    def test_quantize_roundtrip_error_bounded(self):
        import jax.numpy as jnp
        import numpy as np

        from kserve_tpu.ops.kv_write import dequantize_rows, quantize_rows

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 2, 64) * 0.3, jnp.float32)
        q, scale = quantize_rows(x)
        back = dequantize_rows(q, scale, jnp.float32)
        err = np.max(np.abs(np.asarray(back - x)))
        assert err <= np.max(np.abs(np.asarray(x))) / 127.0 + 1e-6

    def test_paged_attention_quantized_close_to_fp(self):
        import jax.numpy as jnp
        import numpy as np

        from kserve_tpu.ops.kv_write import quantize_rows
        from kserve_tpu.ops.attention import paged_attention_xla

        B, nq, nkv, d, ps, NP, W = 3, 8, 4, 32, 8, 32, 4
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(B, nq, d), jnp.float32)
        kv = jnp.asarray(rng.randn(NP, 2, nkv, ps, d) * 0.5, jnp.float32)
        pt = jnp.asarray(
            rng.permutation(np.arange(1, NP))[: B * W].reshape(B, W), jnp.int32
        )
        lens = jnp.asarray([W * ps, 11, 1], jnp.int32)
        ref = paged_attention_xla(q, kv, pt, lens)
        # quantize the cache the way the writers do: per token row
        qkv, scales = quantize_rows(kv.transpose(0, 1, 3, 2, 4))
        qpages = qkv.transpose(0, 1, 3, 2, 4)
        qscales = scales.transpose(0, 1, 3, 2)
        got = paged_attention_xla(q, (qpages, qscales), pt, lens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=0.08, atol=0.03
        )

    @async_test
    async def test_engine_serves_with_int8_cache(self):
        engine = make_engine(kv_quant="int8")
        params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
        await engine.start()
        try:
            outs = await collect(engine, [3, 4, 5, 6], params)
            assert outs[-1].finished
            assert outs[-1].num_generated == 12
            # the cache is genuinely int8
            pages, scales = engine.kv_pages[0]
            assert pages.dtype.name == "int8"
            assert scales.dtype.name == "float32"
        finally:
            await engine.stop()

    @async_test
    async def test_int8_with_chunked_prefill_and_prefix_cache(self):
        engine = make_engine(
            kv_quant="int8", max_prefill_len=16, prefill_buckets=(16,),
            num_pages=64, max_pages_per_seq=16,
        )
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        prompt = list(range(3, 43))  # 40 tokens -> chunked
        await engine.start()
        try:
            first = [o.token_id for o in await collect(engine, prompt, params)]
            again = [o.token_id for o in await collect(engine, prompt, params)]
            assert engine.prefix_cache_hits > 0
            assert again == first  # cached int8 pages reproduce the output
        finally:
            await engine.stop()

    @async_test
    async def test_pd_paths_rejected(self):
        import pytest

        engine = make_engine(kv_quant="int8")
        with pytest.raises(NotImplementedError):
            await engine.prefill_detached([1, 2, 3], SamplingParams(max_tokens=2))
        import numpy as np

        with pytest.raises(NotImplementedError):
            engine.generate_injected(
                [1, 2], SamplingParams(max_tokens=2),
                np.zeros((2, 1, 2, 2, 8, 16), np.float32), 5,
            )

    @async_test
    async def test_int8_composes_with_host_offload(self):
        # kv_tiers payloads are dicts of arrays, so the (pages, scales)
        # int8 cache spills and restores as a unit.  A squeezed engine
        # must preempt, park quantized pages host-side, and reproduce
        # the roomy engine's greedy output exactly.
        params = SamplingParams(max_tokens=44, temperature=0.0, ignore_eos=True)
        prompts = [[1, 2, 3, 4], [9, 10, 11, 12]]
        roomy = make_engine(
            kv_quant="int8", num_pages=64, max_pages_per_seq=8, max_batch_size=4
        )
        await roomy.start()
        try:
            want = [
                [o.token_id for o in await collect(roomy, p, params)]
                for p in prompts
            ]
        finally:
            await roomy.stop()
        engine = make_engine(
            kv_quant="int8", num_pages=8, max_pages_per_seq=8,
            max_batch_size=4, kv_offload="host", kv_offload_gib=1.0,
        )
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, p, params) for p in prompts]
            )
        finally:
            await engine.stop()
        for outs, want_tokens in zip(results, want):
            assert [o.token_id for o in outs] == want_tokens
        assert engine.preemption_count > 0
        assert engine._offload_bytes == 0

    def test_pallas_combination_rejected_at_init(self):
        import pytest

        with pytest.raises(NotImplementedError, match="pallas"):
            make_engine(kv_quant="int8", use_pallas=True)

    def test_unknown_quant_rejected(self):
        import pytest

        with pytest.raises(ValueError, match="kv_quant"):
            make_engine(kv_quant="fp8")


class TestKVTierStaleSweep:
    def test_dead_process_spill_dirs_removed_live_kept(self, tmp_path):
        """PVC-tier leak guard: spill dirs from dead pids are swept at
        first spill; dirs of live processes (concurrent engines on a
        shared RWX claim) are untouched."""
        import os

        import numpy as np

        from kserve_tpu.kvstore.tiers import KVTierStore, TierConfig

        base = str(tmp_path)
        stale = os.path.join(base, "kv-999999-deadbeef")  # pid surely dead
        os.makedirs(stale)
        with open(os.path.join(stale, "x.npz"), "wb") as f:
            f.write(b"stale")
        live = os.path.join(base, f"kv-{os.getpid()}-cafecafe")
        os.makedirs(live)
        unrelated = os.path.join(base, "not-a-spill-dir")
        os.makedirs(unrelated)

        store = KVTierStore(TierConfig(
            host_bytes=1, disk_bytes=1 << 20, disk_dir=base, policy="lru"))
        # host budget of 1 byte forces the put straight to disk
        store.put("k1", {"a": np.zeros((4,), np.float32)})
        assert not os.path.exists(stale), "dead-pid dir not swept"
        assert os.path.exists(live), "live-pid dir wrongly removed"
        assert os.path.exists(unrelated), "non-spill dir wrongly removed"


# ------------------------------------------------- deferred delivery (PR 36)


class TestDeferredDelivery:
    """The `mixed` loop launches the next dispatch before it hands the
    last one's tokens to their streams (PR 36).  What every stream
    receives is what the parent commit's loop gave it, which handed every
    token over first: tests/delivery_cases.py, recorded there."""

    @pytest.mark.parametrize("case", [
        "max_tokens_mid_dispatch", "eos_mid_dispatch", "min_tokens",
        "max_model_len", "stop_string_beside_deferred", "reseated_lane",
        "cancelled_between_dispatches", "preempted_between_dispatches"])
    def test_every_stream_is_the_parents(self, case):
        import delivery_cases

        delivery_cases.NOTES.clear()
        streams = asyncio.run(
            delivery_cases.CASES[case](delivery_cases.llama_engine))
        assert delivery_cases.jsonable(streams) == delivery_cases.recorded(
            "llama", case)
        notes = delivery_cases.NOTES
        if case == "cancelled_between_dispatches":
            # the cancel did fall between an advance and its delivery
            assert notes["owed_at_each_plan"][3] > 0
        if case == "preempted_between_dispatches":
            assert notes["preemptions"] == 1

    @async_test
    async def test_the_next_launch_precedes_the_puts_but_for_a_stop_lane(self):
        """Two lanes without a stop string and one with: dispatch N's
        tokens reach the first two after dispatch N + 1 is launched and
        the third before; the last dispatch has nothing to hide behind
        and hands over in place; a batch of stop lanes alone never
        defers.  `engine_dispatch_deliveries_total` counts each token by
        when it was handed over (its row's `overlapped`, `inline`), and a
        token's stamp carries the serial of the dispatch that PRODUCED
        it."""
        import delivery_cases as dc
        from prometheus_client import REGISTRY
        from test_observability import _TickClock

        label = "engine-deferred-order"
        engine = dc.llama_engine(clock=_TickClock(), metrics_label=label)
        log = []
        mixed_fn, hand_over = engine._mixed_fn, engine._hand_over

        def launch(*args):
            log.append(("launch", engine._phases.serial))
            return mixed_fn(*args)

        def put(owed):
            log.append(("put", owed.serial, bool(owed.stops)))
            return hand_over(owed)

        engine._mixed_fn, engine._hand_over = launch, put

        def counted(when):
            return REGISTRY.get_sample_value(
                "engine_dispatch_deliveries_total",
                {"model_name": label, "when": when}) or 0.0

        await engine.start()
        try:
            # 8-token prompts: two pages from the start, so that every
            # lane takes 4 tokens from each of three dispatches
            await dc.together(
                engine, a=(dc.PROMPT_A, dc.greedy(12)),
                b=(dc.PROMPT_A[::-1], dc.greedy(12, stop=["never"])),
                c=(dc.PROMPT_A, dc.sampled(12, seed=4)))
            launches = {n: i for i, (what, n, *_) in enumerate(log)
                        if what == "launch"}
            assert sorted(launches) == [1, 2, 3]
            puts = [(i, n, stops) for i, (what, n, *stops) in enumerate(log)
                    if what == "put"]
            assert len(puts) == 36
            for i, n, (stops,) in puts:
                if n == 3:  # nothing is launched behind the last
                    assert i > launches[3]
                elif stops:
                    assert launches[n] < i < launches[n + 1]
                else:
                    assert i > launches[n + 1]
            await asyncio.sleep(0.05)  # the loop commits behind its yield
            snap = engine.telemetry_snapshot()
            assert [t["first_token_dispatch"] for t in snap["recent"]] == [1] * 3
            rows = [dict(zip(snap["dispatches"]["columns"], r))
                    for r in snap["dispatches"]["rows"]]
            # a row says when ITS iteration's tokens were handed over:
            # the stop lane's 4 in place; 8 behind the launch and 4 in
            # place; 8 behind the launch and the last dispatch's 12 in place
            assert [(r["overlapped"], r["inline"]) for r in rows] == [
                (0, 4), (8, 4), (8, 12)]
            assert (counted("overlapped"), counted("inline")) == (16, 20)
            await dc.stream(engine, dc.PROMPT_A, dc.greedy(8, stop=["never"]))
            await asyncio.sleep(0.05)
            assert (counted("overlapped"), counted("inline")) == (16, 28)
            assert not engine._undelivered
        finally:
            await engine.stop()


def _tokens_then(rows):
    """A stream as collected by `_drain_stream`: its outputs' token ids,
    and the exception that ended it (None: it finished)."""
    ids = [r.token_id for r in rows if not isinstance(r, Exception)]
    ends = [r for r in rows if isinstance(r, Exception)]
    assert len(ends) <= 1 and (not ends or rows[-1] is ends[0])
    return ids, (ends[0] if ends else None)


async def _drain_stream(engine, prompt, params, into):
    try:
        async for out in engine.generate(prompt, params):
            into.append(out)
    # the exception IS the stream's ending: kept in the list and asserted on
    except Exception as e:  # noqa: BLE001  # jaxlint: disable=swallowed-exception
        into.append(e)


class TestDeferredDeliveryExits:
    """However the loop ends, no consumer is left waiting, no token is
    lost or handed over twice, and what a request was still owed reaches
    it before any error or checkpoint does."""

    @staticmethod
    async def _two_streams(engine, n_short=8, n_long=50):
        import delivery_cases as dc

        short, long = [], []
        tasks = [
            asyncio.create_task(_drain_stream(
                engine, dc.PROMPT_A, dc.greedy(n_short), short)),
            asyncio.create_task(_drain_stream(
                engine, dc.PROMPT_B, dc.sampled(n_long, seed=6), long))]
        return short, long, tasks

    @pytest.mark.parametrize("seam", ["engine.fetch", "plan"])
    @async_test
    async def test_a_crash_between_advance_and_delivery(self, seam):
        """The third dispatch never comes back (the fetch seam's
        replica_crash), or is never launched (its planner raises): the
        request that finished in the second dispatch's advance has its
        last chunk and no error; the other has every token of two
        dispatches, once, and then the error."""
        import delivery_cases as dc

        from kserve_tpu.resilience import FaultPlan, FaultSpec, ReplicaCrashError

        engine = dc.llama_engine(max_batch_size=2)
        await engine.start()
        if seam == "engine.fetch":
            engine.fault_plan = FaultPlan(
                [FaultSpec("engine.fetch", "replica_crash", after=2, count=1)])
            error = ReplicaCrashError
        else:
            plan_ragged, calls = engine._plan_ragged, []

            def plan(meta, prefilling):
                calls.append(1)
                if len(calls) == 3:
                    assert engine._undelivered
                    raise RuntimeError("planner fault")
                return plan_ragged(meta, prefilling)

            engine._plan_ragged = plan
            error = RuntimeError
        short, long, tasks = await self._two_streams(engine)
        await asyncio.wait_for(asyncio.gather(*tasks), 60)
        ids, end = _tokens_then(short)
        assert len(ids) == 8 and end is None and short[-1].finished
        # (3 + 4: a 6-token prompt's first page ends its first dispatch)
        ids, end = _tokens_then(long)
        assert len(ids) == 7 and isinstance(end, error)
        assert [o.num_generated for o in long[:-1]] == list(range(1, 8))
        assert not engine.running and not engine._undelivered
        await engine.stop()

    @pytest.mark.parametrize("exit_", ["stop", "drain", "self_drain"])
    @async_test
    async def test_what_is_owed_goes_out_before_the_exit_speaks(self, exit_):
        """`stop()`, `drain()` past its deadline and the watchdog's
        self-drain, each called while a token is still owed (taken in by
        the state, not yet with its stream): the stream has the token
        before the error or the checkpoint, and the checkpoint counts
        exactly the tokens the stream received."""
        import delivery_cases as dc

        from kserve_tpu.engine.types import _Delivery
        from kserve_tpu.lifecycle.checkpoint import GenerationPreempted
        from kserve_tpu.resilience import Deadline

        engine = dc.llama_engine(
            max_batch_size=2, watchdog_salvage_grace_s=0.0)
        await engine.start()
        short, long, tasks = await self._two_streams(engine, n_short=40)
        while len(long) < 8 or len(short) < 8:
            await asyncio.sleep(0.005)
        # the loop is parked in its fetch: leave one more token of each
        # lane taken in and still owed, as between an advance and the
        # next launch
        assert not engine._undelivered
        for slot, token in zip(engine._slots, (501, 502)):
            slot.pos += 1
            slot.generated.append(token)
            engine._undelivered.append(
                _Delivery(slot, token, None, False, engine._phases.serial))
        if exit_ == "stop":
            await engine.stop()
        elif exit_ == "drain":
            await engine.drain(deadline=Deadline.after(0.0))
        else:
            await engine._stall_self_drain()
        await asyncio.wait_for(asyncio.gather(*tasks), 60)
        assert not engine._undelivered
        for rows, owed in ((short, 501), (long, 502)):
            ids, end = _tokens_then(rows)
            assert ids.count(owed) == 1
            outs = [r for r in rows if not isinstance(r, Exception)]
            assert [o.num_generated for o in outs] == list(
                range(1, len(outs) + 1))
            if exit_ == "stop":
                assert isinstance(end, RuntimeError)
            else:
                assert isinstance(end, GenerationPreempted)
                assert list(end.checkpoint.generated) == ids
                assert end.checkpoint.reason == (
                    "drain" if exit_ == "drain" else "stall")
        if exit_ != "stop":
            await engine.stop()

    @async_test
    async def test_a_drain_in_flight_finds_nothing_owed(self):
        """A draining engine goes on deferring behind its launches, and
        owes nothing across an await of its loop: a checkpoint taken at
        any poll counts only tokens that are with their streams."""
        import delivery_cases as dc

        from kserve_tpu.lifecycle.checkpoint import GenerationPreempted
        from kserve_tpu.resilience import Deadline

        engine = dc.llama_engine(max_batch_size=2)
        await engine.start()
        short, long, tasks = await self._two_streams(engine, n_short=40)
        while len(long) < 4:
            await asyncio.sleep(0.005)
        # two polls of the drain's loop, so that it dispatches once more
        checkpoints = await engine.drain(deadline=Deadline.after(0.015))
        await asyncio.wait_for(asyncio.gather(*tasks), 60)
        assert checkpoints
        for rows in (short, long):
            ids, end = _tokens_then(rows)
            if end is None:  # a tiny model may finish inside the budget
                assert rows[-1].finished
                continue
            assert isinstance(end, GenerationPreempted)
            assert list(end.checkpoint.generated) == ids
        await engine.stop()
