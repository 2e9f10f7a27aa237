"""A looped model (`model_type: ouro`) through the normal path: LLMEngine,
the `mixed` program and the legacy programs, the pool's rows per pass, the
wire and the tier store, the counters.  Tiny sizes (3 passes over 2
layers), float32, seeded random weights, on the CPU; the reference is
benchmark/reference/ouro.py.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.limits import resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.metrics import (
    ENGINE_KV_CONTEXT_TOKENS,
    ENGINE_KV_DECODE_PAGES,
    ENGINE_KV_PAGES_TOTAL,
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_LAYER_PASSES,
    KV_DECODE_REACHES,
)
from test_ouro_model import CFG, CONFIG, PARAMS, _reference, config_of

#: a served token's reference logit against the reference's maximum at its
#: position: float32 against float32 through 3 x 2 layers stays under 1e-4
#: (an exact tie aside); one pass left out, or another pass's rows read,
#: moves logits by 1e-2 and more (test_ouro_model.py)
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


def _run(config: EngineConfig, jobs, label="ouro", model=CONFIG, params=PARAMS):
    async def main():
        engine = LLMEngine(model, config, ByteTokenizer(320), params=params,
                           metrics_label=label)
        await engine.start()
        try:
            return await jobs(engine), engine
        finally:
            await engine.stop()

    return asyncio.run(main())


PROMPTS = [np.random.RandomState(s).randint(0, 320, n).tolist()
           for s, n in ((0, 27), (1, 5), (2, 13))]


def _gaps(prompt, served, cfg=CFG, params=PARAMS):
    """benchmark/reference/check.py's measure."""
    logits = np.asarray(_reference().forward(params, cfg, prompt + served[:-1]))
    rows = logits[len(prompt) - 1:]
    return [float(row.max() - row[t]) for row, t in zip(rows, served)]


def _value(metric, label):
    return metric.labels(model_name=label)._value.get()


async def _three_ways(engine):
    alone = await _generate(engine, PROMPTS[0], 20)  # chunks of 16 and 11
    short = await _generate(engine, PROMPTS[1], 12)  # one whole-prompt slice
    both = await asyncio.gather(  # packed: chunks and decode lanes together
        _generate(engine, PROMPTS[0], 20), _generate(engine, PROMPTS[2], 9))
    return alone, short, both


@pytest.mark.parametrize("over, label", [
    (dict(), "ouro-mixed"),
    (dict(steps_per_sync=1), "ouro-single"),
    (dict(use_ragged=False), "ouro-legacy"),
], ids=["mixed", "single-steps", "legacy-programs"])
def test_served_tokens_agree_with_the_reference(over, label):
    """A 27-token prompt prefilled in chunks of 16 and 11, then 20 tokens
    decoded through the cache over many pages of 4; a short prompt in one
    slice; two requests of different lengths packed into the same
    dispatches: every served token is the reference's argmax (gap under
    GAP) at 3 passes, in the `mixed` program with the device loop, with
    one step a dispatch, and in the legacy prefill / chunk / decode
    programs."""
    (alone, short, both), engine = _run(engine_config(**over), _three_ways, label)
    assert max(_gaps(PROMPTS[0], alone)) < GAP
    assert max(_gaps(PROMPTS[1], short)) < GAP
    assert max(_gaps(PROMPTS[2], both[1])) < GAP
    assert both[0] == alone
    assert len(set(alone)) > 3  # not a degenerate repetition
    assert engine.dispatch_report["regime"] == (
        "legacy" if over.get("use_ragged") is False else "mixed")
    # every forward step ran all three passes
    forwards = _value(ENGINE_LAYER_PASSES, label) / 3
    assert forwards == int(forwards) and forwards >= 20


def test_one_pass_served_from_the_same_weights_is_another_model():
    """What the parent did with this config.json: one pass.  Its tokens
    leave the 3-pass reference by far more than the tolerance (so the
    comparison would catch a program that skips passes), and agree with the
    reference told to run one pass."""
    one = config_of(total_ut_steps=1)
    params = {k: v for k, v in PARAMS.items() if not k.startswith("exit_gate")}

    async def jobs(engine):
        return await _generate(engine, PROMPTS[0], 12)

    served, _ = _run(engine_config(), jobs, "ouro-one", model=one, params=params)
    assert max(_gaps(PROMPTS[0], served, {**CFG, "total_ut_steps": 1})) < GAP
    assert max(_gaps(PROMPTS[0], served)) > 100 * GAP


def test_counters_gauges_and_the_cache_block():
    label = "ouro-counters"

    async def jobs(engine):
        before = engine.scheduler_state()["cache"]
        seen, mid = [], None
        async for out in engine.generate(PROMPTS[2], SamplingParams(
                max_tokens=11, temperature=0.0, ignore_eos=True)):
            seen.append(out.token_id)
            if len(seen) == 6:
                mid = engine.scheduler_state()["cache"]
        return before, mid

    (before, mid), engine = _run(engine_config(), jobs, label)
    token_bytes = 3 * 2 * 2 * 4 * 16 * 4  # passes x layers x K,V x heads x d x f32
    assert before == {"pages_held": 0, "pages_cached": 0, "pages_total": 63,
                      "page_size": 4, "passes": 3, "cache_rows": 6,
                      "token_bytes": token_bytes}
    assert mid["pages_held"] >= 5  # 13 + 6 tokens at 4 a page
    after = engine.scheduler_state()["cache"]
    # the prefix cache keeps the prompt's three whole pages until pressure
    assert (after["pages_held"], after["pages_cached"]) == (3, 3)
    assert _value(ENGINE_KV_TOKEN_BYTES, label) == token_bytes
    assert _value(ENGINE_KV_PAGES_TOTAL, label) == 63
    # 13-token prompt + 11 tokens at 4 forward steps a dispatch: the first
    # dispatch's packed step prefills and emits token 1, its 3 decode steps
    # tokens 2-4 at contexts 14, 15, 16; two more dispatches of 4 steps
    # each (the packed step's decode token goes through the ragged kernel
    # and is not decode attention's): contexts 18-20 and 22-24
    dispatches = 3
    assert _value(ENGINE_LAYER_PASSES, label) == dispatches * 4 * 3
    assert _value(ENGINE_KV_CONTEXT_TOKENS, label) == (
        14 + 15 + 16 + 18 + 19 + 20 + 22 + 23 + 24)
    # the same steps in pages of 4 tokens: the one live lane's own, and the
    # walk of its block of two lanes (the other seat is empty)
    own = 4 + 4 + 4 + 5 + 5 + 5 + 6 + 6 + 6
    assert _decode_pages(label) == {"own": own, "block": 2 * own}
    # the device's cache: a row a pass in each layer's array
    assert len(engine.kv_pages) == 2
    assert engine.kv_pages[0].shape[0] == 3 * 64
    assert engine.cache_config.page_bytes() == 4 * token_bytes


def _decode_pages(label):
    return {reach: ENGINE_KV_DECODE_PAGES.labels(
        model_name=label, reach=reach)._value.get()
        for reach in KV_DECODE_REACHES}


#: 3 decode steps over 16-token pages.  Lane 0 at 599 cached tokens
#: (contexts 600-602: 38 pages a step), lanes 1-6 at 99 (100-102: 7 pages),
#: lane 7 an empty seat; lane 8 meets its capacity of 48 after ONE step
#: (context 48: 3 pages, then not live), lane 9 crosses a page (contexts 16,
#: 17, 18: 1, 2, 2 pages), lane 10 is already AT its capacity (never live);
#: the rest are empty seats
_OWN = 3 * 38 + 6 * 3 * 7 + 3 + (1 + 2 + 2)


@pytest.mark.parametrize("lanes, block", [
    # two blocks of EIGHT, the lanes dealt in order of length: the eight
    # longest of a step share a block (38, six of 7 and 3 | 2 | 2) and the
    # other block holds lane 9's 1 page, then nothing (in lane order lanes
    # 8-15 would walk to 3, 2, 2)
    (16, 8 * 3 * 38 + 8 * (1 + 0 + 0)),
    # two blocks of SIX: the six longest of a step are 38 and five of 7,
    # the others walk to the sixth lane's 7
    (12, 6 * 3 * 38 + 6 * 3 * 7),
    # thirteen lanes have no divisor up to eight but one: a block a lane,
    # which walks what its lane owns
    (13, _OWN),
], ids=["blocks-of-8", "blocks-of-6", "blocks-of-1"])
def test_decode_pages_by_reach_of_a_hand_built_dispatch(lanes, block):
    """`engine_kv_decode_pages_total{reach}` beside
    `engine_kv_context_tokens_total`, from the same pos / live / capacity:
    `DispatchWork.forward` on a dispatch nobody launched."""
    label = f"pages-by-reach-{lanes}"

    async def jobs(engine):
        return None

    _, engine = _run(engine_config(max_batch_size=lanes, page_size=16,
                                   num_pages=256), jobs, label)
    before = _decode_pages(label), _value(ENGINE_KV_CONTEXT_TOKENS, label)
    pos = np.zeros(lanes, np.int64)
    live = np.zeros(lanes, bool)
    capacity = np.full(lanes, 640, np.int64)
    pos[0], live[0] = 599, True
    pos[1:7], live[1:7] = 99, True
    pos[8], live[8], capacity[8] = 47, True, 48
    pos[9], live[9] = 15, True
    pos[10], live[10], capacity[10] = 48, True, 48
    engine._work.forward(4, pos, live, capacity, decode_steps=3)
    after = _decode_pages(label)
    assert {r: after[r] - before[0][r] for r in after} == {
        "own": _OWN, "block": block}
    assert _value(ENGINE_KV_CONTEXT_TOKENS, label) - before[1] == (
        600 + 601 + 602 + 6 * (100 + 101 + 102) + 48 + 16 + 17 + 18)


def test_a_one_pass_model_counts_one_pass_a_step():
    from kserve_tpu.models import llama
    import jax

    label = "plain-counters"
    model = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=320),
                                dtype="float32")
    params = llama.init_params(model, jax.random.PRNGKey(1), scale=0.1)

    async def jobs(engine):
        return await _generate(engine, PROMPTS[2], 8)

    _, engine = _run(engine_config(), jobs, label, model=model, params=params)
    assert _value(ENGINE_LAYER_PASSES, label) == 2 * 4  # dispatches x steps
    cache = engine.scheduler_state()["cache"]
    assert (cache["passes"], cache["cache_rows"]) == (1, 2)


@pytest.mark.parametrize("model_over, engine_over, named", [
    (dict(early_exit_threshold=0.5), dict(), "early_exit_threshold=0.5 < 1"),
    (dict(), dict(pp=2), "pp>1 (a stage boundary inside the loop"),
    (dict(), dict(sp=2), "sp>1"),
], ids=["early-exit", "pp", "sp"])
def test_what_a_looped_model_cannot_do_yet_is_refused_by_name(
        model_over, engine_over, named):
    model = config_of(**model_over)
    with pytest.raises(NotImplementedError) as info:
        resolve_serving(model, engine_config(**engine_over))
    assert named in str(info.value) and "looped model" in str(info.value)
    with pytest.raises(NotImplementedError, match="looped model"):
        LLMEngine(model, engine_config(**engine_over), ByteTokenizer(320))


def test_what_runs_every_pass_is_left_alone():
    config = engine_config(prefix_cache=True, spec_decode_k=2)
    resolve_serving(CONFIG, config)
    assert config.prefix_cache is True
    resolve_serving(config_of(total_ut_steps=1),
                           engine_config(pp=2))  # not looped: not its business


def test_prefix_pages_and_the_wire_carry_every_pass():
    """A second request sharing a 16-token prefix adopts its pages (one page
    id stands for the page in every row); a prompt prefilled detached ships
    [cache_rows, P, ...] and decodes on from the injected rows: both give
    the tokens of a request served alone."""
    shared = PROMPTS[0][:16]
    a, b, c = shared + PROMPTS[1], shared + PROMPTS[2][:6], PROMPTS[2] + [7]

    async def jobs(engine):
        first = await _generate(engine, a, 6)
        second = await _generate(engine, b, 10)  # adopts 4 pages of the prefix
        alone = await _generate(engine, c, 10)
        params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
        first_tok, kv = await engine.prefill_detached(c, params)
        shipped = [  # the first token is yielded again, then the other nine
            out.token_id async for out in engine.generate_injected(
                c, params, kv, first_tok)]
        return first, second, alone, kv.shape, shipped

    (first, second, alone, shape, shipped), engine = _run(
        engine_config(prefix_cache=True), jobs, "ouro-prefix")
    assert max(_gaps(a, first)) < GAP and max(_gaps(b, second)) < GAP
    assert engine._prefix_cache.hits > 0
    assert shape == (6, 4, 2, 4, 4, 16)  # rows, pages of 14 tokens, K/V, ...
    assert max(_gaps(c, alone)) < GAP and shipped == alone


def test_a_preempted_lane_spills_and_resumes_every_pass():
    """Too few pages for two long answers: one lane is preempted, its pages
    (all rows) spill to the host tier and come back; the tokens are those
    of an engine that never ran out."""
    async def jobs(engine):
        return await asyncio.gather(
            _generate(engine, PROMPTS[0][:20], 40),
            _generate(engine, PROMPTS[2], 40))

    roomy, _ = _run(engine_config(), jobs, "ouro-roomy")
    tight, engine = _run(
        engine_config(num_pages=24, kv_offload="host", kv_offload_gib=0.01,
                      prefix_cache=False), jobs, "ouro-tight")
    assert engine.preemption_count >= 1
    assert tight == roomy


def test_tensor_parallel_runs_every_pass():
    """tp = 2 on virtual devices: K/V heads shard over the model axis, the
    loop over passes and the page-table offset are replicated, and the
    tokens are the reference's."""
    async def jobs(engine):
        return await asyncio.gather(
            _generate(engine, PROMPTS[0], 12), _generate(engine, PROMPTS[2], 9))

    (a, b), engine = _run(engine_config(tp=2), jobs, "ouro-tp2")
    assert max(_gaps(PROMPTS[0], a)) < GAP and max(_gaps(PROMPTS[2], b)) < GAP
    assert "model" in str(engine.kv_pages[0].sharding.spec)


@pytest.mark.parametrize("case", ["stop_string_beside_deferred", "reseated_lane"])
def test_streams_do_not_depend_on_when_tokens_are_handed_over(case):
    """The same loop as every model's: a dispatch's tokens reach their
    streams after the next launch (PR 36), a lane with a stop string in
    place; every stream is what the parent commit's loop gave, which
    handed everything over first (tests/delivery_cases.py, recorded)."""
    import delivery_cases

    streams = asyncio.run(
        delivery_cases.CASES[case](delivery_cases.ouro_engine))
    assert delivery_cases.jsonable(streams) == delivery_cases.recorded(
        "ouro", case)
