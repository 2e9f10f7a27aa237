"""`model_type: lfm2_moe` through the normal path: LLMEngine, the `mixed`
program, tail-only slots beside the pool's pages (K/V heads of 64 two a
cache row), the two new counters, the prefix cache resolved to off.  Tiny
sizes, float32, seeded random weights, on the CPU.
"""

import asyncio

import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.limits import model_kinds, resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_CONV_PACKED_TOKENS,
    ENGINE_CONV_UPDATE_LANE_STEPS,
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_MOE_ASSIGNMENTS,
    ENGINE_MOE_EXPERT_HITS,
    ENGINE_MOE_EXPERTS_HELD,
    ENGINE_MOE_PAIRS_ELSEWHERE,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_STATE_BYTES,
)
from kserve_tpu.parallel import sharding as shd
from test_lfm2_model import CFG, CONFIG, PARAMS, _reference
from test_work import PLAN, _read, _work

#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through eight layers
GAP = 1e-4


def engine_config(**over) -> EngineConfig:
    base = dict(max_batch_size=2, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=16,
                prefill_buckets=(16,), dtype="float32", steps_per_sync=4)
    base.update(over)
    return EngineConfig(**base)


async def _generate(engine, prompt, n):
    params = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
    return [out.token_id async for out in engine.generate(prompt, params)]


def _run(config: EngineConfig, jobs, label):
    async def main():
        engine = LLMEngine(CONFIG, config, ByteTokenizer(320), params=PARAMS,
                           metrics_label=label)
        await engine.start()
        try:
            return await jobs(engine), engine
        finally:
            await engine.stop()

    return asyncio.run(main())


PROMPTS = [np.random.RandomState(s).randint(0, 320, n).tolist()
           for s, n in ((0, 27), (1, 5), (2, 13))]


def _gaps(prompt, served):
    logits = np.asarray(_reference().forward(PARAMS, CFG, prompt + served[:-1]))
    rows = logits[len(prompt) - 1:]
    return [float(row.max() - row[t]) for row, t in zip(rows, served)]


def _value(metric, label, **labels):
    return metric.labels(model_name=label, **labels)._value.get()


def test_served_tokens_agree_with_the_reference_alone_and_together():
    """A 27-token prompt prefilled in chunks of 16 and 11 (the second starts
    from the first's stored tails and reads its pages), 20 tokens decoded
    through the tails and five more pages; two lanes of different lengths
    in one dispatch; a lane seated again starts from zero tails; the
    device's loop of four steps serves what single steps serve."""
    label = "lfm2-loop"

    async def jobs(engine):
        alone = await _generate(engine, PROMPTS[0], 20)
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9))
        return alone, both

    (alone, both), engine = _run(engine_config(), jobs, label)
    assert engine.config.prefix_cache is False  # resolved, with a log line
    assert engine.dispatch_report["regime"] == "mixed"
    assert max(_gaps(PROMPTS[0], alone)) < GAP
    assert max(_gaps(PROMPTS[2], both[1])) < GAP
    assert both[0] == alone and len(set(alone)) > 3
    (alone1, both1), _ = _run(
        engine_config(steps_per_sync=1), jobs, "lfm2-single")
    assert (alone1, both1) == (alone, both)


def test_a_preempted_lane_is_prefilled_again_and_gets_its_tails_back():
    """With too few pages for two long answers one lane is preempted and
    its request re-prefilled (prompt + what it had generated) from position
    0: its tails start from zero and are rebuilt by the prefill, and the
    tokens are those of an engine that never ran out."""
    async def jobs(engine):
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0][:20], 40),
            _generate(engine, PROMPTS[2] + PROMPTS[1], 40))
        return both, engine.preemption_count

    (roomy, none), _ = _run(engine_config(), jobs, "lfm2-roomy")
    (tight, some), _ = _run(engine_config(num_pages=24), jobs, "lfm2-tight")
    assert none == 0 and some >= 1
    assert tight == roomy
    assert max(_gaps(PROMPTS[0][:20], tight[0])) < GAP


def test_counters_state_gauges_and_scheduler_state():
    label = "lfm2-gauges"

    async def jobs(engine):
        before = engine.scheduler_state()
        seen, mid = [], None
        async for out in engine.generate(
                PROMPTS[0], SamplingParams(max_tokens=12, temperature=0.0,
                                           ignore_eos=True)):
            seen.append(out.token_id)
            if len(seen) == 6:
                mid = engine.scheduler_state()["state"]
        return before, mid

    (before, mid), engine = _run(engine_config(), jobs, label)
    layout = engine.state_layout
    # K/V of the TWO attention rows: 2 K/V heads x 64 x float32, stored as
    # one row of 128
    assert (layout.kv_heads, layout.head_dim) == (1, 128)
    assert layout.token_bytes() == 2 * 2 * 2 * 64 * 4
    assert _value(ENGINE_KV_TOKEN_BYTES, label) == layout.token_bytes()
    # six short-conv rows: a tail of 2 x 64 each, and NO scan state
    assert before["state"]["bytes_per_lane"] == {
        "window_kv": 0, "ssm": 0, "conv": 6 * 2 * 64 * 4}
    assert mid["slots_in_use"] == 1
    assert mid["bytes_in_use"]["ssm"] == 0
    assert mid["bytes_in_use"]["conv"] == 6 * 2 * 64 * 4
    assert _value(ENGINE_STATE_BYTES, label, kind="ssm") == 0
    assert _value(ENGINE_MOE_EXPERTS_HELD, label, of="8") == 8
    # the convolution's two forms, as launched: 27 prompt tokens and a
    # decode token or two through the packed steps, 3 decode steps a
    # dispatch, 6 short-conv layers
    packed = _value(ENGINE_CONV_PACKED_TOKENS, label)
    lane_steps = _value(ENGINE_CONV_UPDATE_LANE_STEPS, label)
    assert packed % 6 == 0 and 27 * 6 <= packed <= (27 + 4) * 6
    assert lane_steps % 6 == 0 and 0 < lane_steps <= 12 * 6
    # every token that passed the model was routed to 2 of 8 experts in 6
    # expert layers, all held and all in front of the last writer: the
    # host's count
    assert _value(ENGINE_MOE_ASSIGNMENTS, label) == (
        (packed + lane_steps) // 6 * 2 * 6)
    assert _value(ENGINE_MOE_PAIRS_ELSEWHERE, label) == 0
    hits, peak = (_value(m, label) for m in (ENGINE_MOE_EXPERT_HITS,
                                             ENGINE_MOE_PEAK_LOAD))
    assert 0 < hits and 0 < peak


def test_the_two_counters_of_a_hand_built_plan():
    """tests/test_work.py's plan (5 packed tokens, then 2 decode steps in
    which the three lanes have 2 + 2 + 1 steps of room) through the table's
    six short-conv layers; a Kimi-delta model counts nothing under these
    names, and this one nothing under the others'."""
    work, _ = _work(CONFIG, 4, "work-conv",
                    packed_single_token_min_pages=None)
    work.packed(PLAN, 8, steps=3)
    got = _read("work-conv")
    assert got["engine_conv_packed_tokens_total"] == 5 * 6
    assert got["engine_conv_update_lane_steps_total"] == (2 + 2 + 1) * 6
    assert not any("ssd" in name or "kda" in name for name in got)
    # the host counts the routed pairs: tokens x 2 a token x 6 expert layers
    assert got["engine_moe_assignments_total"] == (5 + 2 + 2 + 1) * 2 * 6
    from test_solar_open2_model import CONFIG as KDA

    other, _ = _work(KDA, 4, "work-not-conv",
                     packed_single_token_min_pages=None)
    other.packed(PLAN, 8, steps=3)
    assert not any("engine_conv" in name for name in _read("work-not-conv"))


@pytest.mark.parametrize("over, named", [
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(kv_quant="int8"), "kv_quant=int8"),
    (dict(weight_quant="int8"), "weight_quant=int8"),
    (dict(pp=2), "pp>1"),
    (dict(sp=2), "sp>1"),
    (dict(tp=2), "tp>1"),
    (dict(kv_offload="host"), "kv_offload"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(use_ragged=False), "use_ragged=False"),
    (dict(role="decode"), "role=decode"),
    (dict(lora=True), "lora_adapters"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_family_cannot_do_yet_is_refused_by_name(over, named):
    role, lora = over.pop("role", "both"), over.pop("lora", False)
    with pytest.raises(NotImplementedError) as info:
        resolve_serving(CONFIG, engine_config(**over), role=role, lora=lora)
    assert named in str(info.value) and "short-convolution" in str(info.value)


def test_the_family_s_rows_come_from_its_kinds():
    """No row of its own: a table of mixers whose lanes hold state beside
    their pages (a tail is state too), with expert layers."""
    assert sorted(model_kinds(CONFIG)) == ["experts", "hybrid", "lane_state"]
    config = engine_config()
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is False
    # tp = 1: every new tensor has a spec, each replicated
    specs = shd.param_pspecs(CONFIG)
    for layer, spec in zip(PARAMS["layers"], specs["layers"]):
        assert set(layer) == set(spec)
    engine = LLMEngine(CONFIG, engine_config(), ByteTokenizer(320))
    for bad, named in ((SamplingParams(max_tokens=2, logprobs=1), "logprobs"),
                       (SamplingParams(max_tokens=2, repetition_penalty=1.3),
                        "penalties")):
        with pytest.raises(ValueError, match=named):
            engine.generate([1, 2, 3], bad)


def test_the_attention_rows_take_the_kernels_on_a_tpu_and_pack_at_the_block():
    """What the engine reports and packs by on a TPU, from the predicates
    alone: the cache's rows are 128 wide (two heads of 64), so both kernels
    and the page write run and slices are packed at RAGGED_BQ; heads of 16
    (the rehearsal's) keep the gather, the row scatter and dense packing."""
    from kserve_tpu.engine.shapes import DispatchShapes
    from kserve_tpu.models import llama
    from kserve_tpu.ops import attention as att
    from kserve_tpu.ops.pallas_paged_attention import RAGGED_BQ

    config = engine_config(max_batch_size=48, page_size=64, num_pages=256,
                           max_pages_per_seq=128, dtype="bfloat16")
    whole = llama.LlamaConfig.from_hf_config(dict(
        CFG, hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        head_dim=None))
    report = att.describe_attention_dispatch(whole, config, "tpu")
    assert report["mixed"] == "pallas_ragged"
    assert report["decode"] == "pallas_decode"
    # 4 cache rows x 128 x 64 tokens: 128 KB of K and V a page, the kernel
    # at every width, and the packed step's one-token lanes on it too
    assert report["decode_pallas_min_pages"] is None
    assert report["packed_single_token_min_pages"] == 0
    assert report["kv_write"] == {"paged": "page_kernel"}
    assert DispatchShapes.of(whole, config, "tpu").align == RAGGED_BQ
    narrow = llama.LlamaConfig.from_hf_config(dict(CFG, head_dim=16))
    report = att.describe_attention_dispatch(narrow, config, "tpu")
    assert (report["mixed"], report["decode"]) == (
        "xla_ragged_gather", "xla_gather")
    assert report["kv_write"] == {"paged": "row_scatter"}
    assert DispatchShapes.of(narrow, config, "tpu").align == 1
