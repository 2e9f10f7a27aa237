"""`model_type: solar_open2` through the normal path: LLMEngine, the `mixed`
program, Kimi-delta slots beside the pool's pages, the new counters, the
expert share's counters, the prefix cache resolved to off.  Tiny sizes,
float32, seeded random weights, on the CPU.
"""

import asyncio

import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.limits import model_kinds, resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_KDA_CHUNK_TOKENS,
    ENGINE_KDA_UPDATE_LANE_STEPS,
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_MOE_ASSIGNMENTS,
    ENGINE_MOE_EXPERT_HITS,
    ENGINE_MOE_EXPERTS_HELD,
    ENGINE_MOE_PAIRS_ELSEWHERE,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_STATE_BYTES,
)
from kserve_tpu.parallel import sharding as shd
from test_solar_open2_model import CFG, CONFIG, PARAMS, _reference
from test_work import PLAN, _read, _work

#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through four layers
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
    from the first's stored state, tails and pages), 20 tokens decoded
    through the slots and five more pages; two lanes of different lengths
    in one dispatch; a lane seated again starts from zero state; the
    device's loop of four steps serves what single steps serve."""
    label = "solar-loop"

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
        engine_config(steps_per_sync=1), jobs, "solar-single")
    assert (alone1, both1) == (alone, both)


def test_a_preempted_lane_is_prefilled_again_from_zero_state():
    """With too few pages for two long answers one lane is preempted and
    its request re-prefilled (prompt + what it had generated) from position
    0: its delta-rule states and tails start from zero again, and the tokens
    are those of an engine that never ran out."""
    async def jobs(engine):
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0][:20], 40),
            _generate(engine, PROMPTS[2] + PROMPTS[1], 40))
        return both, engine.preemption_count

    (roomy, none), _ = _run(engine_config(), jobs, "solar-roomy")
    (tight, some), _ = _run(engine_config(num_pages=24), jobs, "solar-tight")
    assert none == 0 and some >= 1
    assert tight == roomy


def test_counters_state_gauges_and_scheduler_state():
    label = "solar-gauges"

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
    # K/V of the ONE attention layer: 2 x 2 heads x 16 x float32
    assert layout.token_bytes() == 2 * 2 * 16 * 4
    assert _value(ENGINE_KV_TOKEN_BYTES, label) == layout.token_bytes()
    # three KDA layers: 4 heads of [16, 16] float32, a tail of 3 x 192
    assert before["state"]["bytes_per_lane"] == {
        "window_kv": 0, "ssm": 3 * 4 * 16 * 16 * 4, "conv": 3 * 3 * 192 * 4}
    assert mid["slots_in_use"] == 1
    assert mid["bytes_in_use"]["ssm"] == 3 * 4 * 16 * 16 * 4
    assert mid["bytes_in_use"]["conv"] == 3 * 3 * 192 * 4
    assert _value(ENGINE_STATE_BYTES, label, kind="ssm") >= 0
    assert _value(ENGINE_MOE_EXPERTS_HELD, label, of="8") == 4
    # the delta rule's two forms, as launched: 27 prompt tokens and a decode
    # token or two through the packed steps, 3 decode steps a dispatch, 3
    # KDA layers
    chunked = _value(ENGINE_KDA_CHUNK_TOKENS, label)
    lane_steps = _value(ENGINE_KDA_UPDATE_LANE_STEPS, label)
    assert chunked % 3 == 0 and 27 * 3 <= chunked <= (27 + 4) * 3
    assert lane_steps % 3 == 0 and 0 < lane_steps <= 12 * 3
    # every token that passed the model was routed to 2 of 8 experts in 4
    # expert layers, each in front of or on the last writer; this chip
    # multiplied the pairs that fell on its 4
    here = _value(ENGINE_MOE_ASSIGNMENTS, label)
    away = _value(ENGINE_MOE_PAIRS_ELSEWHERE, label)
    assert (chunked + lane_steps) // 3 * 2 * 4 == here + away
    assert 0.2 < here / (here + away) < 0.8
    hits, peak = (_value(m, label) for m in (ENGINE_MOE_EXPERT_HITS,
                                             ENGINE_MOE_PEAK_LOAD))
    assert 0 < hits <= here and peak <= here


def test_the_two_counters_of_a_hand_built_plan():
    """tests/test_work.py's plan (5 packed tokens, then 2 decode steps in
    which the three lanes have 2 + 2 + 1 steps of room) through the table's
    three KDA layers; a Mamba-2 model counts nothing under these names."""
    work, _ = _work(CONFIG, 4, "work-kda",
                    packed_single_token_min_pages=None)
    work.packed(PLAN, 8, steps=3)
    got = _read("work-kda")
    assert got["engine_kda_chunk_tokens_total"] == 5 * 3
    assert got["engine_kda_update_lane_steps_total"] == (2 + 2 + 1) * 3
    assert not any("ssd" in name for name in got)
    from test_nemotron_model import CONFIG as MAMBA2

    other, _ = _work(MAMBA2, 4, "work-not-kda",
                     packed_single_token_min_pages=None)
    other.packed(PLAN, 8, steps=3)
    assert not any("kda" in name for name in _read("work-not-kda"))


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
    assert named in str(info.value) and "delta-rule" in str(info.value)


def test_the_family_s_rows_come_from_its_kinds():
    """No row of its own: a table of mixers whose lanes hold state beside
    their pages, with expert layers."""
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


def test_the_recurrent_slot_is_the_mixer_s_to_size():
    """`LlamaConfig.recurrent_slot`: the state's shape, the convolution's
    columns and taps by the kind that writes `recurrent`; `StateLayout`
    reads it and knows no family.  Two recurrent kinds in one model have no
    one slot and are refused by name."""
    import dataclasses

    from kserve_tpu.engine.kvcache import StateLayout

    heads, d = CONFIG.kda_n_heads, CONFIG.kda_head_dim
    assert CONFIG.recurrent_slot() == ((heads, d, d), 3 * heads * d, 4)
    layout = StateLayout.of(CONFIG, 4, 8, 2, "float32")
    assert (layout.ssm_shape, layout.conv_width, layout.d_conv) == (
        (heads, d, d), 3 * heads * d, 4)
    state = layout.init_state()
    assert state["ssm"][0].shape == (2, heads, d, d)
    assert state["conv"][0].shape == (2, 3, 3 * heads * d)
    kinds = list(CONFIG.mixer_kinds)
    kinds[1] = "mamba2"
    with pytest.raises(ValueError, match="one shape"):
        dataclasses.replace(CONFIG, mixer_kinds=tuple(kinds)).recurrent_slot()
    # a model without such a layer: Mamba-1's slot at its sizes of 0
    plain = dataclasses.replace(CONFIG, mixer_kinds=("gqa_attention",) * 4)
    assert plain.recurrent_slot() == ((0, 0), 0, 0)
