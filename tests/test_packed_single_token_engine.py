"""The packed step's single-token lanes through the decode kernel, in the
engine: greedy streams with the split traced (interpret mode, slices
aligned to the ragged kernel's blocks, as a TPU builds the program) are the
streams of the program as it is built on the CPU, the XLA reference over
every slice: what the parent served.  On the Llama path and through a
`gqa_attention` layer of models/hybrid.py.  The counters that follow the
kernel.  Tiny sizes, float32, seeded random weights.
"""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_KV_CONTEXT_TOKENS,
    ENGINE_KV_DECODE_PAGES,
    ENGINE_PACKED_LANES,
    KV_DECODE_REACHES,
    PACKED_LANE_PATHS,
)
from kserve_tpu.models import hybrid, llama
from kserve_tpu.ops import attention as att
from kserve_tpu.ops.pallas_paged_attention import (
    RAGGED_BQ,
    ragged_single_token_split_pallas,
)

PROMPTS = [np.random.RandomState(s).randint(0, 320, n).tolist()
           for s, n in ((0, 27), (1, 5), (2, 13), (3, 1))]


def engine_config(**over) -> EngineConfig:
    base = dict(max_batch_size=4, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=32,
                prefill_buckets=(32,), dtype="float32", steps_per_sync=4)
    base.update(over)
    return EngineConfig(**base)


def _llama():
    model = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=320), dtype="float32")
    return model, llama.init_params(model, jax.random.PRNGKey(1), scale=0.2)


def _nemotron():
    from test_nemotron_model import CONFIG, PARAMS

    return CONFIG, PARAMS


async def _generate(engine, prompt, n):
    params = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
    return [out.token_id async for out in engine.generate(prompt, params)]


async def _streams(engine):
    """A prompt in chunks beside decode lanes, a one-token prompt, lanes
    that come and go: dispatches of single-token lanes alone, beside a
    chunk, and beside an empty seat."""
    alone = await _generate(engine, PROMPTS[0], 12)
    together = await asyncio.gather(
        _generate(engine, PROMPTS[0], 14), _generate(engine, PROMPTS[2], 9),
        _generate(engine, PROMPTS[3], 11), _generate(engine, PROMPTS[1], 5))
    return alone, together


def _run(model, config, label):
    async def main():
        engine = LLMEngine(model[0], config, ByteTokenizer(320),
                           params=model[1], metrics_label=label)
        await engine.start()
        try:
            return await _streams(engine), engine
        finally:
            await engine.stop()

    return asyncio.run(main())


@pytest.fixture
def split_traced(monkeypatch):
    """The models' attention as `use_pallas=True` builds it on a TPU, run
    here: the packed step through the split in interpret mode (and only
    where `ragged_attention_path` says the program takes it), the decode
    steps through the gather.  Yields the paths the packed step traced."""
    traced = []

    def ragged(q, kv_pages, page_table, q_start, q_len, kv_start,
               logit_softcap=0.0, use_pallas=None, **kw):
        path = att.ragged_attention_path(
            q, kv_pages, page_table, use_pallas, **kw)
        traced.append(path)
        if path == "xla_gather":  # the program as the CPU builds it
            return att.ragged_paged_attention(
                q, kv_pages, page_table, q_start, q_len, kv_start,
                logit_softcap=logit_softcap, use_pallas=use_pallas, **kw)
        assert path == "pallas_ragged+decode", (path, kw)
        return ragged_single_token_split_pallas(
            q, kv_pages, page_table, q_start, q_len, kv_start,
            logit_softcap=logit_softcap, interpret=True)

    def decode(q, kv_pages, page_table, seq_lens, **kw):
        return att.paged_attention(
            q, kv_pages, page_table, seq_lens, **dict(kw, use_pallas=False))

    for module in (llama, hybrid):
        monkeypatch.setattr(module, "ragged_paged_attention", ragged)
        monkeypatch.setattr(module, "paged_attention", decode)
    return traced


@pytest.mark.parametrize("family", ["llama", "hybrid_gqa_attention"])
def test_greedy_streams_are_the_parent_s(family, split_traced):
    model = _llama() if family == "llama" else _nemotron()
    with_split, engine = _run(
        model, engine_config(use_pallas=True), f"split-{family}")
    assert set(split_traced) == {"pallas_ragged+decode"}
    assert engine._shapes.align == RAGGED_BQ
    split_traced.clear()
    before, _ = _run(model, engine_config(), f"parent-{family}")
    assert set(split_traced) == {"xla_gather"}
    assert with_split == before
    assert all(len(set(stream)) > 3 for stream in (before[0], *before[1][:3]))


def _value(metric, label, **labels):
    return metric.labels(model_name=label, **labels)._value.get()


def _counted(label):
    return (
        {p: _value(ENGINE_PACKED_LANES, label, attention_path=p)
         for p in PACKED_LANE_PATHS},
        {r: _value(ENGINE_KV_DECODE_PAGES, label, reach=r)
         for r in KV_DECODE_REACHES},
        _value(ENGINE_KV_CONTEXT_TOKENS, label))


#: a packed step of 16 lanes over 16-token pages: lanes 0-8 bring one token
#: (contexts 600, 100 x 6, 1 and 17 tokens: 38, 7 x 6, 1 and 2 pages), lane 9
#: a chunk of 40 at 64, lane 10 a chunk of 2, the rest are empty seats
_Q_LEN = [1] * 9 + [40, 2] + [0] * 5
_KV_START = [599] + [99] * 6 + [0, 16, 64, 7] + [0] * 5
_OWN = 38 + 6 * 7 + 1 + 2
_TOKENS = 600 + 6 * 100 + 1 + 17


@pytest.mark.parametrize("min_pages, width, lanes, pages, tokens", [
    # the program splits at every width: the decode kernel's call over the
    # nine, in two blocks of eight by length (38 | 7 ... | and 1 | 0 ...)
    (0, 40, {"decode_kernel": 9, "ragged": 2},
     {"own": _OWN, "block": 8 * 38 + 8 * 1}, _TOKENS),
    # it splits from 64 pages of table and the dispatch ran at 40: the
    # ragged kernel over every slice, nothing of decode attention's
    (64, 40, {"decode_kernel": 0, "ragged": 11}, {"own": 0, "block": 0}, 0),
    (64, 64, {"decode_kernel": 9, "ragged": 2},
     {"own": _OWN, "block": 8 * 38 + 8 * 1}, _TOKENS),
    # it never does (the CPU, a window, latent pages, rings)
    (None, 40, {"decode_kernel": 0, "ragged": 11}, {"own": 0, "block": 0}, 0),
], ids=["every-width", "under-the-gate", "at-the-gate", "never"])
def test_the_kernel_s_counters_follow_the_kernel(
        min_pages, width, lanes, pages, tokens):
    """`engine_packed_lanes_total{attention_path}`, and the packed step's call on
    `engine_kv_decode_pages_total{reach}` / `engine_kv_context_tokens_total`
    where the program makes it: `DispatchWork.packed_lanes` on a plan nobody
    launched."""
    label = f"packed-lanes-{min_pages}-{width}"

    model, params = _llama()
    engine = LLMEngine(
        model, engine_config(max_batch_size=16, page_size=16, num_pages=256,
                             max_pages_per_seq=64),
        ByteTokenizer(320), params=params, metrics_label=label)
    report = engine.dispatch_report["attention"]
    assert report["packed_single_token_min_pages"] is None  # on the CPU
    report["packed_single_token_min_pages"] = min_pages
    before = _counted(label)
    engine._work.packed_lanes(
        np.asarray(_Q_LEN), np.asarray(_KV_START), width)
    after = _counted(label)
    assert {p: after[0][p] - before[0][p] for p in after[0]} == lanes
    assert {r: after[1][r] - before[1][r] for r in after[1]} == pages
    assert after[2] - before[2] == tokens


@pytest.mark.parametrize("name, over, min_pages", [
    ("qwen3-4b's heads at decode-sat's shape", {}, 0),
    ("forced", {"use_pallas": True}, 0),
    ("the reference asked for", {"use_pallas": False}, None),
    ("int8 pages", {"kv_quant": "int8"}, None),
    ("tp > 1: a window threaded through shard_map", {"tp": 2}, None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_report_says_from_which_width_the_program_splits(
        name, over, min_pages):
    model = dataclasses.replace(
        llama.LlamaConfig.qwen3_0_6b(), n_heads=32, n_kv_heads=8)
    cfg = EngineConfig(max_batch_size=48, page_size=16, num_pages=2300,
                       max_pages_per_seq=40, **over)
    report = att.describe_attention_dispatch(model, cfg, "tpu")
    assert report["packed_single_token_min_pages"] == min_pages, name
    if min_pages is None and over.get("use_pallas") is not False:
        assert report["mixed"] in ("pallas_ragged", "xla_ragged_gather")


def test_the_report_follows_the_model_s_layers():
    """Two K/V heads a device: the decode steps take the kernel from 64
    pages of table, and so does the packed step; a model of sliding
    windows, one whose full layer is taken at the sampled rows, and one of
    latent pages never split."""
    def report(model, **over):
        cfg = EngineConfig(max_batch_size=16, page_size=16, num_pages=2300,
                           max_pages_per_seq=128, **over)
        return att.describe_attention_dispatch(model, cfg, "tpu")[
            "packed_single_token_min_pages"]

    qwen = llama.LlamaConfig.qwen3_0_6b()
    assert report(dataclasses.replace(qwen, n_heads=8, n_kv_heads=2)) == 64
    assert report(dataclasses.replace(qwen, n_heads=8, n_kv_heads=1)) is None
    assert report(dataclasses.replace(qwen, sliding_window=4096)) is None
    from test_glm_model import CONFIG as glm
    from test_hybrid_model import CFG as phi
    from test_nemotron_model import CFG as nemotron

    assert report(glm) is None
    assert report(llama.LlamaConfig.from_hf_config(phi)) is None
    wide = llama.LlamaConfig.from_hf_config(dict(
        nemotron, head_dim=128, num_key_value_heads=4))
    assert report(wide) == 0
