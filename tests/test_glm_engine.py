"""`model_type: glm4_moe_lite` through the normal path: LLMEngine, the
`mixed` program, latent pages in the pool, the prefix cache ON, the expert
counters.  Tiny sizes, float32, seeded random weights, on the CPU.
"""

import asyncio

import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.limits import resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_MOE_ASSIGNMENTS,
    ENGINE_MOE_EXPERT_HITS,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_STATE_BYTES,
)
from test_glm_model import CFG, CONFIG, PARAMS, _reference

#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through three layers
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


def test_served_tokens_agree_with_the_reference_alone_together_and_after_a_prefix_hit():
    """A 27-token prompt prefilled in chunks of 16 and 11 (the second reads
    the first's latent pages), 20 tokens decoded across five more pages;
    two lanes of different lengths in one dispatch; and the same prompt
    again, which now starts from the prefix cache's latent pages: the
    tokens of the cold prefill."""
    label = "glm-loop"

    async def jobs(engine):
        alone = await _generate(engine, PROMPTS[0], 20)
        hits = engine.prefix_cache_hits
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9))
        return alone, hits, both, engine.prefix_cache_hits

    (alone, hits0, both, hits1), engine = _run(engine_config(), jobs, label)
    assert engine.config.prefix_cache is True
    assert max(_gaps(PROMPTS[0], alone)) < GAP
    assert max(_gaps(PROMPTS[2], both[1])) < GAP
    assert hits0 == 0 and hits1 >= 6  # 27 tokens = 6 whole pages of 4
    assert both[0] == alone and len(set(alone)) > 3
    (alone1, _, both1, _), _ = _run(
        engine_config(steps_per_sync=1, prefix_cache=False), jobs, "glm-single")
    assert (alone1, both1) == (alone, both)


def test_expert_counters_cache_gauges_and_scheduler_state():
    label = "glm-gauges"

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
    assert layout.token_bytes() == 3 * 128 * 4
    assert _value(ENGINE_KV_TOKEN_BYTES, label) == layout.token_bytes()
    assert before["cache"]["token_bytes"] == layout.token_bytes()
    assert before["state"]["bytes_per_token"] == {
        "shared_kv": 0, "latent_kv": layout.token_bytes()}
    assert mid["bytes_in_use"]["latent_kv"] == (
        mid["pages_in_use"] * 4 * layout.token_bytes())
    assert mid["bytes_in_use"]["shared_kv"] == 0
    assert _value(ENGINE_STATE_BYTES, label, kind="latent_kv") >= 0
    attention = engine.dispatch_report["attention"]
    assert attention["mixed"] == "xla_ragged_gather" and attention["decode"] == "xla_gather"
    # every token that passed the model went to 2 experts in 2 expert layers:
    # the 27 prompt tokens and the decode steps the device ran (at least the
    # 11 that were served after the packed step's own token)
    pairs = _value(ENGINE_MOE_ASSIGNMENTS, label)
    assert pairs % 4 == 0 and (27 + 11) * 4 <= pairs <= (27 + 16) * 4
    hits, peak = (_value(m, label) for m in (ENGINE_MOE_EXPERT_HITS,
                                             ENGINE_MOE_PEAK_LOAD))
    # summed in the program and fetched with the tokens: every expert that
    # was hit got a row, the fullest of a step at least the step's mean
    assert 0 < hits <= pairs and pairs / 8 <= peak <= pairs


@pytest.mark.parametrize("over, named", [
    (dict(tp=2), "tp>1"),
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
    assert named in str(info.value) and "latent-attention" in str(info.value)


def test_the_prefix_cache_stays_as_configured_and_requests_are_refused_by_name():
    config = engine_config()
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is None  # the engine's default: ON
    resolve_serving(CONFIG, engine_config(prefix_cache=True))
    with pytest.raises(NotImplementedError, match="tp>1 over a hybrid model"):
        resolve_serving(CONFIG, engine_config(tp=2))
    engine = LLMEngine(CONFIG, engine_config(), ByteTokenizer(320))
    assert engine.config.prefix_cache is True
    assert engine.dispatch_report["regime"] == "mixed"
    ok = SamplingParams(max_tokens=2)
    for bad, named in ((SamplingParams(max_tokens=2, logprobs=1), "logprobs"),
                       (SamplingParams(max_tokens=2, repetition_penalty=1.3),
                        "penalties")):
        with pytest.raises(ValueError, match=named):
            engine.generate([1, 2, 3], bad)
    with pytest.raises(ValueError, match="P/D wire"):
        asyncio.run(engine.prefill_detached([1, 2, 3], ok))
