"""`model_type: nemotron_h` through the normal path: LLMEngine, the `mixed`
program, Mamba-2 slots beside the pool's pages, the expert share's
counters, the prefix cache resolved to off.  Tiny sizes, float32, seeded
random weights, on the CPU.
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
    ENGINE_DISPATCHES,
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_MOE_ASSIGNMENTS,
    ENGINE_MOE_EXPERT_HITS,
    ENGINE_MOE_EXPERTS_HELD,
    ENGINE_MOE_PAIRS_ELSEWHERE,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_SSD_SCAN_TOKENS,
    ENGINE_SSD_UPDATE_CALLS,
    ENGINE_SSD_UPDATE_LANE_STEPS,
    ENGINE_STATE_BYTES,
)
from kserve_tpu.models.llama import LlamaConfig, init_params
from kserve_tpu.parallel import sharding as shd
from test_nemotron_model import CFG, CONFIG, PARAMS, _reference

#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through six layers
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


def _run(config: EngineConfig, jobs, label, model=(CONFIG, PARAMS)):
    async def main():
        engine = LLMEngine(model[0], config, ByteTokenizer(320),
                           params=model[1], metrics_label=label)
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
    from the first's stored state, tail and pages), 20 tokens decoded
    through the slots and five more pages; two lanes of different lengths
    in one dispatch; a lane seated again starts from zero state."""
    label = "nemotron-loop"

    async def jobs(engine):
        alone = await _generate(engine, PROMPTS[0], 20)
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9))
        return alone, both

    (alone, both), engine = _run(engine_config(), jobs, label)
    assert engine.config.prefix_cache is False  # resolved, with a log line
    assert max(_gaps(PROMPTS[0], alone)) < GAP
    assert max(_gaps(PROMPTS[2], both[1])) < GAP
    assert both[0] == alone and len(set(alone)) > 3
    (alone1, both1), _ = _run(
        engine_config(steps_per_sync=1), jobs, "nemotron-single")
    assert (alone1, both1) == (alone, both)


def test_share_counters_state_gauges_and_scheduler_state():
    label = "nemotron-gauges"

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
    assert before["state"]["bytes_per_lane"] == {
        "window_kv": 0, "ssm": 3 * 8 * 8 * 16 * 4, "conv": 3 * 3 * 128 * 4}
    assert mid["slots_in_use"] == 1
    assert mid["bytes_in_use"]["ssm"] == 3 * 8 * 8 * 16 * 4
    assert _value(ENGINE_STATE_BYTES, label, kind="ssm") >= 0
    attention = engine.dispatch_report["attention"]
    assert attention["mixed"] == "xla_ragged_gather" and attention["decode"] == "xla_gather"
    assert _value(ENGINE_MOE_EXPERTS_HELD, label, of="8") == 4
    # every token that passed the model was routed to 2 of 8 experts in 2
    # expert layers; this chip multiplied the pairs that fell on its 4 and
    # counted the others as routed elsewhere
    here = _value(ENGINE_MOE_ASSIGNMENTS, label)
    away = _value(ENGINE_MOE_PAIRS_ELSEWHERE, label)
    assert (here + away) % 4 == 0
    assert (27 + 11) * 4 <= here + away <= (27 + 16) * 4
    assert 0.2 < here / (here + away) < 0.8
    hits, peak = (_value(m, label) for m in (ENGINE_MOE_EXPERT_HITS,
                                             ENGINE_MOE_PEAK_LOAD))
    assert 0 < hits <= here and here / 4 <= peak <= here
    # the Mamba-2 mixers' two forms, as launched: 27 prompt tokens and a
    # decode token or two through the packed step, 3 decode steps a
    # dispatch, 3 Mamba-2 layers
    scanned = _value(ENGINE_SSD_SCAN_TOKENS, label)
    calls = _value(ENGINE_SSD_UPDATE_CALLS, label)
    lane_steps = _value(ENGINE_SSD_UPDATE_LANE_STEPS, label)
    assert scanned % 3 == 0 and 27 * 3 <= scanned <= (27 + 4) * 3
    assert calls % 9 == 0 and calls >= 4 * 9
    assert 0 < lane_steps <= calls
    # both expert layers lie in front of the last Mamba-2 layer and see
    # every token: the program's count of the pairs routed is the host's
    # count of the tokens
    assert (scanned + lane_steps) // 3 * 4 == here + away


def test_a_closing_expert_layer_counts_the_rows_it_saw():
    """The published cut ends in `...ME`: the packed step runs the closing
    expert layer on ONE row a lane with a slice, so the pairs routed are
    fewer than tokens x experts a token x expert layers by nearly a
    quarter here, and `engine_moe_pairs_elsewhere_total` follows the
    program's count, not that product."""
    label = "nemotron-closing"
    cfg = dict(CFG, num_hidden_layers=5, hybrid_override_pattern="ME*ME")
    config = dataclasses.replace(
        LlamaConfig.from_hf_config(cfg), dtype="float32")
    params = init_params(config, jax.random.PRNGKey(4), scale=0.1)

    async def jobs(engine):
        return await _generate(engine, PROMPTS[0], 12)

    served, engine = _run(engine_config(), jobs, label, (config, params))
    assert len(served) == 12
    here = _value(ENGINE_MOE_ASSIGNMENTS, label)
    away = _value(ENGINE_MOE_PAIRS_ELSEWHERE, label)
    # 2 Mamba-2 layers: tokens through the packed steps, lane-steps decoded
    packed = _value(ENGINE_SSD_SCAN_TOKENS, label) // 2
    decoded = _value(ENGINE_SSD_UPDATE_LANE_STEPS, label) // 2
    dispatches = _value(ENGINE_DISPATCHES, label, program="mixed")
    assert packed >= 27 and decoded > 0 and dispatches >= 4
    # 2 experts a token: the first expert layer saw every token; the
    # closing one every decoded lane-step and, of a packed step, the one
    # lane's one row
    assert here + away == 2 * (packed + decoded) + 2 * (dispatches + decoded)
    assert here + away < 2 * 2 * (packed + decoded)
    assert 0.2 < here / (here + away) < 0.8


def test_a_hidden_of_no_whole_tiles_is_served_from_body_and_rest():
    """hidden 640 = 512 + 128: the engine lays the held experts' tensors
    out as a body of whole tiles and the rest (models/moe.device_layout)
    and leaves the parameters it was given as they are, names, shapes and
    values; what it serves is held to the reference over THOSE tensors."""
    label = "nemotron-640"
    cfg = dict(CFG, hidden_size=640, num_hidden_layers=4,
               hybrid_override_pattern="ME*E")
    config = dataclasses.replace(
        LlamaConfig.from_hf_config(cfg), dtype="float32")
    params = init_params(config, jax.random.PRNGKey(4), scale=0.1)
    given = jax.tree.map(np.asarray, params)
    prompt = PROMPTS[0]

    async def jobs(engine):
        return await _generate(engine, prompt, 8)

    served, engine = _run(engine_config(), jobs, label, (config, params))
    for i, (mine, canonical) in enumerate(
            zip(engine.params["layers"], params["layers"])):
        assert sorted(mine) == sorted(canonical)
        if i in (1, 3):
            for name, axis in (("w_up", 1), ("w_down", 2)):
                body, rest = mine[name]
                assert (body.shape[axis], rest.shape[axis]) == (512, 128)
                np.testing.assert_array_equal(
                    np.concatenate([body, rest], axis=axis), given["layers"][i][name])
        else:
            assert not any(isinstance(v, tuple) for v in mine.values())
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, params), given)
    logits = np.asarray(_reference().forward(params, cfg, prompt + served[:-1]))
    rows = logits[len(prompt) - 1:]
    assert max(float(r.max() - r[t]) for r, t in zip(rows, served)) < GAP
    assert float(np.ptp(rows, axis=-1).min()) > 100 * GAP and len(set(served)) > 2
    assert _value(ENGINE_MOE_ASSIGNMENTS, label) > 0


@pytest.mark.parametrize("over, named", [
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(kv_quant="int8"), "kv_quant=int8"),
    (dict(weight_quant="int8"), "weight_quant=int8"),
    (dict(pp=2), "pp>1"),
    (dict(sp=2), "sp>1"),
    (dict(kv_offload="host"), "kv_offload"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(use_ragged=False), "use_ragged=False"),
    (dict(role="decode"), "role=decode"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_family_cannot_do_yet_is_refused_by_name(over, named):
    role = over.pop("role", "both")
    with pytest.raises(NotImplementedError) as info:
        resolve_serving(CONFIG, engine_config(**over), role=role)
    assert named in str(info.value) and "Mamba-2" in str(info.value)


def test_the_prefix_cache_resolves_to_off_and_across_chips_stays_refused():
    config = engine_config()
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is False
    with pytest.raises(NotImplementedError, match="share of the experts"):
        resolve_serving(CONFIG, engine_config(tp=2))
    # tp = 1: every new tensor has a spec, each replicated
    specs = shd.param_pspecs(CONFIG)
    for layer, spec in zip(PARAMS["layers"], specs["layers"]):
        assert set(layer) == set(spec)
    engine = LLMEngine(CONFIG, engine_config(), ByteTokenizer(320))
    assert engine.dispatch_report["regime"] == "mixed"
    for bad, named in ((SamplingParams(max_tokens=2, logprobs=1), "logprobs"),
                       (SamplingParams(max_tokens=2, repetition_penalty=1.3),
                        "penalties")):
        with pytest.raises(ValueError, match=named):
            engine.generate([1, 2, 3], bad)
