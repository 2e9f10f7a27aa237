"""A hybrid model (models/hybrid.py) through the normal path: LLMEngine,
the `mixed` program, the cache manager's per-kind state.  Tiny sizes,
float32, seeded random weights, on the CPU.
"""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.limits import resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_STATE_BYTES,
    ENGINE_STATE_RESETS,
    ENGINE_STATE_SLOTS_IN_USE,
)
from kserve_tpu.models import llama
from test_hybrid_model import CFG, _reference

CONFIG = dataclasses.replace(
    llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
#: scale 0.1: logits of magnitude ~2, so greedy continuations do not collapse
PARAMS = llama.init_params(CONFIG, jax.random.PRNGKey(1), scale=0.1)
#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through eight layers stays under 1e-4
#: (an exact tie aside), bfloat16 state or weights would not (~1e-2)
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


def _run(config: EngineConfig, jobs, label="engine"):
    """Start an engine, run `jobs(engine)` on it, stop it."""
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


def _gaps(params, prompt, served):
    """benchmark/reference/check.py's measure: teacher-forced reference
    logits, each served token against the maximum at its position."""
    logits = np.asarray(_reference().forward(params, CFG, prompt + served[:-1]))
    rows = logits[len(prompt) - 1:]
    return [float(row.max() - row[t]) for row, t in zip(rows, served)]


def test_served_tokens_agree_with_the_reference_and_the_device_loop_with_single_steps():
    """A 27-token prompt prefilled in chunks of 16 and 11 (max_prefill_len
    16), then 20 tokens decoded, to position 46 under a window of 8: the
    served tokens are the reference's argmax (gap under GAP).  The same
    requests with `steps_per_sync` 4 (the device loop carries ring,
    recurrent state and pages) and 1 (every step a dispatch of its own),
    alone and two at a time, give the same tokens; a lane used again gives
    what it gives alone."""
    async def jobs(engine):
        alone = await _generate(engine, PROMPTS[0], 20)
        again = await _generate(engine, PROMPTS[1], 12)  # the same lane, reset
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9))
        return alone, again, both

    (alone, again, both), engine = _run(engine_config(), jobs, "hybrid-loop")
    (alone1, again1, both1), _ = _run(
        engine_config(steps_per_sync=1), jobs, "hybrid-single")
    assert max(_gaps(PARAMS, PROMPTS[0], alone)) < GAP
    assert max(_gaps(PARAMS, PROMPTS[1], again)) < GAP
    assert max(_gaps(PARAMS, PROMPTS[2], both[1])) < GAP
    assert both[0] == alone
    assert (alone1, again1, both1) == (alone, again, both)
    assert len(set(alone)) > 3  # not a degenerate repetition


def test_state_gauges_and_scheduler_state():
    label = "hybrid-gauges"

    async def jobs(engine):
        before = engine.scheduler_state()["state"]
        stream = engine.generate(
            PROMPTS[0], SamplingParams(max_tokens=30, temperature=0.0,
                                       ignore_eos=True))
        seen = []
        async for out in stream:
            seen.append(out.token_id)
            if len(seen) == 6:
                mid = engine.scheduler_state()["state"]
                slots = ENGINE_STATE_SLOTS_IN_USE.labels(model_name=label)._value.get()
        return before, mid, slots

    (before, mid, slots), engine = _run(engine_config(), jobs, label)
    layout = engine.state_layout
    assert before["slots_in_use"] == 0 and before["pages_in_use"] == 0
    assert before["bytes_per_token"] == {"shared_kv": 2 * 1 * 32 * 4}
    assert before["bytes_per_lane"] == {
        "window_kv": 2 * 8 * 2 * 32 * 4, "ssm": 3 * 128 * 4 * 4,
        "conv": 3 * 3 * 128 * 4}
    assert mid["slots_in_use"] == 1 == slots and mid["slots"] == 2
    assert mid["pages_in_use"] >= 8  # 27 + 6 tokens at 4 a page
    assert mid["bytes_in_use"]["shared_kv"] == (
        mid["pages_in_use"] * 4 * layout.token_bytes())
    assert mid["bytes_in_use"]["ssm"] == layout.lane_bytes()["ssm"]
    assert engine.telemetry_snapshot()["state"] == engine.scheduler_state()["state"]
    after = engine.scheduler_state()["state"]
    assert after["slots_in_use"] == 0 and after["pages_in_use"] == 0
    for kind in ("shared_kv", "window_kv", "ssm", "conv"):
        assert ENGINE_STATE_BYTES.labels(
            model_name=label, kind=kind)._value.get() >= 0
    assert ENGINE_STATE_RESETS.labels(model_name=label)._value.get() == 1


@pytest.mark.parametrize("over, named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(kv_quant="int8"), "kv_quant=int8"),
    (dict(weight_quant="int8"), "weight_quant=int8"),
    (dict(pp=2), "pp>1"),
    (dict(sp=2), "sp>1"),
    (dict(kv_offload="host"), "kv_offload"),
    (dict(kv_persist_dir="/tmp/nowhere"), "kv_persist_dir"),
    (dict(use_ragged=False), "use_ragged=False"),
    (dict(role="decode"), "role=decode"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_family_cannot_do_yet_is_refused_by_name(over, named):
    role = over.pop("role", "both")
    with pytest.raises(NotImplementedError) as info:
        resolve_serving(CONFIG, engine_config(**over), role=role)
    assert named in str(info.value)


def test_defaults_resolve_to_off_and_a_llama_is_left_alone():
    config = engine_config()
    assert config.prefix_cache is None
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is False
    plain = engine_config(prefix_cache=True, spec_decode_k=2, kv_quant="int8")
    resolve_serving(llama.LlamaConfig.tiny(), plain)  # not its business
    assert plain.prefix_cache is True


def test_tensor_parallelism_and_request_time_features_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="tp>1 over a hybrid model"):
        resolve_serving(CONFIG, engine_config(tp=2))
    resolve_serving(CONFIG, engine_config(tp=1))
    with pytest.raises(NotImplementedError, match="LoRA adapters over a hybrid"):
        LLMEngine(CONFIG, engine_config(), ByteTokenizer(320),
                  lora_adapters={"a": "/nowhere"})
    with pytest.raises(NotImplementedError, match="mixed program only"):
        # 2 lanes do not fit the largest prefill bucket of 1
        LLMEngine(CONFIG, engine_config(prefill_buckets=(1,), max_prefill_len=1),
                  ByteTokenizer(320))
    engine = LLMEngine(CONFIG, engine_config(), ByteTokenizer(320))
    assert engine.config.prefix_cache is False
    assert engine.dispatch_report["regime"] == "mixed"
    assert engine.dispatch_report["attention"]["mixed"].startswith("xla_ring_window")
    ok = SamplingParams(max_tokens=2)
    for bad, named in ((SamplingParams(max_tokens=2, logprobs=1), "logprobs"),
                       (SamplingParams(max_tokens=2, repetition_penalty=1.3),
                        "penalties")):
        with pytest.raises(ValueError, match=named):
            engine.generate([1, 2, 3], bad)
    with pytest.raises(ValueError, match="P/D wire"):
        asyncio.run(engine.prefill_detached([1, 2, 3], ok))
    with pytest.raises(ValueError, match="P/D wire"):
        engine.generate_injected([1, 2, 3], ok, np.zeros((1,)), 5)


def test_a_preempted_lane_is_prefilled_again_from_zero_state():
    """With too few pages for two long answers one lane is preempted and
    its request re-prefilled (prompt + what it had generated) from position
    0: ring and recurrent state start from zero again, and the tokens are
    those of an engine that never ran out."""
    async def jobs(engine):
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0][:20], 40),
            _generate(engine, PROMPTS[2] + PROMPTS[1], 40))
        return both, engine.preemption_count

    (roomy, none), _ = _run(engine_config(), jobs, "hybrid-roomy")
    (tight, some), _ = _run(engine_config(num_pages=24), jobs, "hybrid-tight")
    assert none == 0 and some >= 1
    assert tight == roomy


@pytest.mark.parametrize("case", ["stop_string_beside_deferred", "reseated_lane"])
def test_streams_do_not_depend_on_when_tokens_are_handed_over(case):
    """The same loop as every model's: a dispatch's tokens reach their
    streams after the next launch (PR 36), a lane with a stop string in
    place; every stream is what the parent commit's loop gave, which
    handed everything over first (tests/delivery_cases.py, recorded)."""
    import delivery_cases

    streams = asyncio.run(
        delivery_cases.CASES[case](delivery_cases.hybrid_engine))
    assert delivery_cases.jsonable(streams) == delivery_cases.recorded(
        "hybrid", case)
