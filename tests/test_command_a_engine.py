"""`model_type: cohere2_moe` through the normal path: LLMEngine, the `mixed`
program, roped window rings beside the one full layer's pages, the expert
share's counters, the window's lane-steps, the prefix cache resolved to
off.  Tiny sizes, float32, seeded random weights, on the CPU.
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
    ENGINE_MOE_EXPERTS_HELD,
    ENGINE_MOE_PAIRS_ELSEWHERE,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_STATE_BYTES,
    ENGINE_WINDOW_LANE_STEPS,
    ENGINE_WINDOW_RAGGED_WORK,
)
from kserve_tpu.ops.attention import describe_attention_dispatch
from kserve_tpu.parallel import sharding as shd
from test_command_a_model import CFG, CONFIG, PARAMS, _reference

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


#: 37 tokens: three chunks of 16, 16 and 5 over a window of 16, so the
#: second chunk reads a full ring and the third a wrapped one
PROMPTS = [np.random.RandomState(s).randint(0, 320, n).tolist()
           for s, n in ((0, 37), (1, 5), (2, 13))]


def _gaps(prompt, served):
    logits = np.asarray(_reference().forward(PARAMS, CFG, prompt + served[:-1]))
    rows = logits[len(prompt) - 1:]
    return [float(row.max() - row[t]) for row, t in zip(rows, served)]


def _value(metric, label, **labels):
    return metric.labels(model_name=label, **labels)._value.get()


def test_served_tokens_agree_with_the_reference_alone_and_together():
    """A 37-token prompt prefilled in three chunks through a wrapping ring,
    20 tokens decoded with the window binding; a short and a long lane in
    one dispatch (the window binds on one and not on the other); a lane
    seated again starts from an empty ring: three requests over two lanes
    serve what each serves alone."""
    label = "command-a-loop"

    async def jobs(engine):
        alone = await _generate(engine, PROMPTS[0], 20)
        short = await _generate(engine, PROMPTS[1], 7)
        both = await asyncio.gather(
            _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9),
            _generate(engine, PROMPTS[1], 7))
        return alone, short, both

    (alone, short, both), engine = _run(engine_config(), jobs, label)
    assert engine.config.prefix_cache is False  # resolved, with a log line
    assert max(_gaps(PROMPTS[0], alone)) < GAP
    assert max(_gaps(PROMPTS[1], short)) < GAP
    assert max(_gaps(PROMPTS[2], both[1])) < GAP
    # the third request took a seat another had left: its ring holds the
    # other's keys, and none of them is seen
    assert both[0] == alone and both[2] == short and len(set(alone)) > 3
    (alone1, _, both1), _ = _run(
        engine_config(steps_per_sync=1), jobs, "command-a-single")
    assert (alone1, both1[1]) == (alone, both[1])


def test_share_counters_window_lane_steps_gauges_and_scheduler_state():
    label = "command-a-gauges"

    async def jobs(engine):
        before = engine.scheduler_state()
        seen, mid = [], None
        async for out in engine.generate(
                PROMPTS[2], SamplingParams(max_tokens=12, temperature=0.0,
                                           ignore_eos=True)):
            seen.append(out.token_id)
            if len(seen) == 6:
                mid = engine.scheduler_state()["state"]
        return before, mid

    (before, mid), engine = _run(engine_config(), jobs, label)
    layout = engine.state_layout
    # K/V of the ONE full layer: 2 x 2 heads x 16 x float32; three rings of
    # 16 tokens a lane
    assert layout.token_bytes() == 2 * 2 * 16 * 4
    assert _value(ENGINE_KV_TOKEN_BYTES, label) == layout.token_bytes()
    ring = 3 * 16 * 2 * 2 * 16 * 4
    assert before["state"]["bytes_per_lane"] == {
        "window_kv": ring, "ssm": 0, "conv": 0}
    assert mid["slots_in_use"] == 1 and mid["bytes_in_use"]["window_kv"] == ring
    assert _value(ENGINE_STATE_BYTES, label, kind="window_kv") >= 0
    attention = engine.dispatch_report["attention"]
    assert attention["mixed"] == "xla_ring_window+xla_ragged_gather"
    assert attention["decode"] == "xla_gather"
    assert attention["kv_write"] == {"paged": "row_scatter", "window": "row_scatter"}
    assert _value(ENGINE_MOE_EXPERTS_HELD, label, of="8") == 4
    # 13 prompt tokens and 11 fed back: the first three layers see every
    # token, the last (the full row, the last writer) one row a lane in a
    # packed step; 4 of 8 experts a token, about half on the 4 held
    here = _value(ENGINE_MOE_ASSIGNMENTS, label)
    away = _value(ENGINE_MOE_PAIRS_ELSEWHERE, label)
    assert (here + away) % 4 == 0
    assert (13 + 11) * 4 * 3 < here + away <= (13 + 12) * 4 * 4
    assert 0.2 < here / (here + away) < 0.8
    hits, peak = (_value(m, label) for m in (ENGINE_MOE_EXPERT_HITS,
                                             ENGINE_MOE_PEAK_LOAD))
    assert 0 < hits <= here and peak <= here
    # decode steps of the scan: contexts 14 .. 25 against a window of 16:
    # those that attend to 17 tokens or more are bound
    free = _value(ENGINE_WINDOW_LANE_STEPS, label, bound="no")
    bound = _value(ENGINE_WINDOW_LANE_STEPS, label, bound="yes")
    assert free > 0 and bound > 0 and 6 <= free + bound <= 12
    assert bound >= 24 - 16 - 3  # the last 8 contexts, less a packed step's
    # the packed steps' window attention, 3 window layers: the 13-token
    # prompt (1 + 2 + .. + 13 pairs) and a one-token slice a later dispatch,
    # each seeing min(context, 16) keys and reading them once
    queries, pairs, keys = (_value(ENGINE_WINDOW_RAGGED_WORK, label, unit=u)
                            for u in ("queries", "pairs", "keys"))
    assert queries % 3 == 0 and 13 * 3 < queries <= (13 + 3) * 3
    extra = queries // 3 - 13  # one-token slices of the later dispatches
    assert pairs >= 3 * (91 + 14 * extra) and pairs <= 3 * (91 + 16 * extra)
    assert keys == pairs - 3 * (91 - 13)  # a one-token slice reads what it sees


def test_the_dispatch_report_names_the_kernels_on_a_tpu():
    """What the engine would log on the chip for the published sizes: the
    window kernel for the packed step, the ragged kernel for the full
    layer, the decode kernel for both."""
    import json
    import os

    from kserve_tpu.models.llama import LlamaConfig

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark", "configs", "command-a-plus.json")
    with open(path) as f:
        mc = LlamaConfig.from_hf_config(json.load(f))
    cfg = EngineConfig(max_batch_size=32, page_size=64, num_pages=4352,
                       max_pages_per_seq=128, max_prefill_len=4096)
    report = describe_attention_dispatch(mc, cfg, "tpu")
    assert report["mixed"] == "pallas_window_ragged+pallas_ragged"
    assert report["decode"] == "pallas_decode"
    assert report["decode_pallas_min_pages"] is None
    assert report["kv_write"] == {"paged": "page_kernel", "window": "page_kernel"}
    assert describe_attention_dispatch(mc, cfg, "cpu")["mixed"] == (
        "xla_ring_window+xla_ragged_gather")


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
    assert named in str(info.value) and "window" in str(info.value)


def test_the_prefix_cache_resolves_to_off_and_across_chips_stays_refused():
    config = engine_config()
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is False
    with pytest.raises(NotImplementedError, match="share of the experts"):
        resolve_serving(CONFIG, engine_config(tp=2))
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
