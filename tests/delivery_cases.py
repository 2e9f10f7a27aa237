"""Streams of the `mixed` loop that must not depend on WHEN a dispatch's
tokens are handed to their streams (PR 36: deferred delivery).

Each case drives a tiny seeded engine and returns, for every stream, its
`(token_id, text_delta, finished, finish_reason, num_generated)` sequence.
`tests/data/delivery_streams.json` holds the same cases recorded from the
parent commit of PR 36, whose loop handed every token over before it
launched the next dispatch:

    cd <parent checkout>/tests && PYTHONPATH=.. JAX_PLATFORMS=cpu \
        python <this file> <out.json>

Only the engine's public surface and two private names that both commits
have (`_plan_ragged`, the seam for a cancel between two dispatches, and
`tokenizer.eos_token_id`) are used, so that the file runs on both.
"""

import asyncio
import json
import os
import sys

from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import BaseTokenizer

STEPS = 4  # tokens a lane gets from one dispatch


class MarkTokenizer(BaseTokenizer):
    """Token i is spelled `<i>`: every prefix decodes to a prefix, nothing
    is held back, and a stop string can name a token."""

    def __init__(self, vocab_size: int):
        self._vocab_size = vocab_size
        self.eos_token_id = -1
        self.bos_token_id = -1

    @property
    def vocab_size(self) -> int:
        return self._vocab_size

    def encode(self, text, add_bos=True):
        return [int(t) for t in text.replace(">", "").split("<") if t]

    def decode(self, ids):
        return "".join(f"<{i}>" for i in ids)


def llama_engine(clock=None, metrics_label="engine", **over):
    from kserve_tpu.models.llama import LlamaConfig

    model = LlamaConfig.tiny(dtype="float32")
    cfg = dict(max_batch_size=4, page_size=8, num_pages=64,
               max_pages_per_seq=8, max_prefill_len=32,
               prefill_buckets=(16, 32), dtype="float32", use_pallas=False,
               steps_per_sync=STEPS)
    cfg.update(over)
    return LLMEngine(model, EngineConfig(**cfg),
                     MarkTokenizer(model.vocab_size), clock=clock,
                     metrics_label=metrics_label)


def hybrid_engine(**over):
    """The tiny hybrid model of tests/test_hybrid_engine.py."""
    import test_hybrid_engine as t

    cfg = t.engine_config(**{"steps_per_sync": STEPS, **over})
    return LLMEngine(t.CONFIG, cfg, MarkTokenizer(t.CONFIG.vocab_size),
                     params=t.PARAMS, metrics_label="hybrid-delivery")


def ouro_engine(**over):
    """The tiny looped model of tests/test_ouro_engine.py."""
    import test_ouro_engine as t

    cfg = t.engine_config(**{"steps_per_sync": STEPS, **over})
    return LLMEngine(t.CONFIG, cfg, MarkTokenizer(t.CONFIG.vocab_size),
                     params=t.PARAMS, metrics_label="ouro-delivery")


def sampled(n, seed, **over):
    """Seeded sampling: a varied continuation that is the same whatever
    the batch around it."""
    return SamplingParams(max_tokens=n, temperature=1.0, seed=seed,
                          ignore_eos=True, **over)


def greedy(n, **over):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **over)


def row(out):
    return (out.token_id, out.text_delta, out.finished, out.finish_reason,
            out.num_generated)


async def stream(engine, prompt, params, into=None, rid=None):
    rows = [] if into is None else into
    async for out in engine.generate(prompt, params, request_id=rid):
        rows.append(row(out))
    return rows


async def together(engine, **jobs):
    names = list(jobs)
    done = await asyncio.gather(*(stream(engine, *jobs[n]) for n in names))
    return dict(zip(names, done))


async def first_new_token(engine, prompt, params, after):
    """(index, token) of the first generated token past `after` that no
    earlier one equals: what an EOS id or a stop string can be set to."""
    tokens = [r[0] for r in await stream(engine, prompt, params)]
    for k in range(after, len(tokens)):
        if tokens[k] not in tokens[:k]:
            return k, tokens[k]
    raise AssertionError(f"no new token past {after} in {tokens}")


#: what a case saw beside its streams (not part of the recording)
NOTES = {}

PROMPT_A = [3, 1, 4, 1, 5, 9, 2, 6]
PROMPT_B = [2, 7, 1, 8, 2, 8]
PROMPT_C = list(range(11, 30))


async def max_tokens_mid_dispatch(make):
    """Three lanes whose budgets end at the second, third and fourth token
    of a dispatch; the shortest leaves while the others go on."""
    engine = make()
    await engine.start()
    try:
        return await together(
            engine, a=(PROMPT_A, greedy(6)), b=(PROMPT_B, greedy(7)),
            c=(PROMPT_C, sampled(12, seed=5)))
    finally:
        await engine.stop()


async def eos_mid_dispatch(make):
    """A lane whose EOS arrives in the middle of a dispatch: the tokens
    the device produced behind it are discarded; the lane beside it
    ignores the same id."""
    engine = make()
    await engine.start()
    try:
        k, eos = await first_new_token(
            engine, PROMPT_A, sampled(16, seed=11), after=STEPS + 1)
        engine.tokenizer.eos_token_id = eos
        return await together(
            engine,
            stops=(PROMPT_A, SamplingParams(
                max_tokens=16, temperature=1.0, seed=11)),
            ignores=(PROMPT_A, sampled(16, seed=11)))
    finally:
        await engine.stop()


async def min_tokens(make):
    """The same EOS under `min_tokens`: passed over where it first comes,
    honoured at the budget's end or where it comes again."""
    engine = make()
    await engine.start()
    try:
        k, eos = await first_new_token(
            engine, PROMPT_B, sampled(16, seed=23), after=STEPS + 1)
        engine.tokenizer.eos_token_id = eos
        return await together(
            engine,
            held=(PROMPT_B, SamplingParams(
                max_tokens=16, temperature=1.0, seed=23, min_tokens=k + 1)),
            free=(PROMPT_B, SamplingParams(
                max_tokens=16, temperature=1.0, seed=23, min_tokens=k)))
    finally:
        await engine.stop()


async def max_model_len(make):
    """A lane that ends exactly at `max_model_len` (its budget's last
    token is the last position) beside a lane that ends earlier, and then
    a lane alone whose pages run out: closed with `length` and no token
    (`_finish`), behind the tokens it was still owed."""
    engine = make(max_pages_per_seq=4)  # max_model_len 32
    await engine.start()
    try:
        streams = await together(
            engine, ceiling=(PROMPT_A, greedy(24)), beside=(PROMPT_B, greedy(9)))
    finally:
        await engine.stop()
    engine = make(num_pages=3, max_batch_size=1)  # 24 tokens of cache in all
    await engine.start()
    try:
        streams["starved"] = await stream(engine, PROMPT_B, sampled(40, seed=3))
    finally:
        await engine.stop()
    return streams


async def stop_string_beside_deferred(make):
    """One lane with a stop string (its text decides, so it is handed its
    tokens in place) between two lanes without: the stop cuts the delta
    and ends the lane mid-dispatch; the others are untouched."""
    engine = make()
    await engine.start()
    try:
        tokens = [r[0] for r in await stream(
            engine, PROMPT_C, sampled(20, seed=7))]
        spell = engine.tokenizer.decode
        # the tail of a token's spelling, met nowhere before that token:
        # the delta is cut in the middle, in the middle of a dispatch
        stop = next(f"{t}>" for k, t in enumerate(tokens)
                    if k > STEPS + 1 and f"{t}>" not in spell(tokens[:k]))
        return await together(
            engine, before=(PROMPT_A, greedy(14)),
            stopped=(PROMPT_C, sampled(20, seed=7, stop=["never", stop])),
            after=(PROMPT_B, sampled(14, seed=9)))
    finally:
        await engine.stop()


async def reseated_lane(make):
    """One lane, four requests: each is seated in the lane the one before
    it left, before that one's last tokens have reached its stream."""
    engine = make(max_batch_size=1)
    await engine.start()
    try:
        return await together(
            engine, first=(PROMPT_A, greedy(6)), second=(PROMPT_B, greedy(5)),
            third=(PROMPT_C, sampled(7, seed=2)), fourth=(PROMPT_A, greedy(4)))
    finally:
        await engine.stop()


async def cancelled_between_dispatches(make):
    """A client's cancel that lands after the third dispatch was routed
    and before the fourth is launched: its stream holds every token up to
    the third dispatch, once; the lane is free for the next request; the
    streams beside it are whole."""
    engine = make(max_batch_size=2)
    plans = []
    plan_ragged = engine._plan_ragged

    def plan(meta, prefilling):
        plans.append(len(getattr(engine, "_undelivered", ())))
        if len(plans) == 4:
            engine.cancel("victim")
        return plan_ragged(meta, prefilling)

    engine._plan_ragged = plan
    await engine.start()
    try:
        victim = []
        task = asyncio.create_task(stream(
            engine, PROMPT_A, sampled(30, seed=13), into=victim, rid="victim"))
        streams = await together(
            engine, beside=(PROMPT_B, greedy(18)))
        streams["next"] = await stream(engine, PROMPT_C, greedy(5))
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        streams["victim"] = victim
        NOTES["owed_at_each_plan"] = plans
        return streams
    finally:
        await engine.stop()


async def preempted_between_dispatches(make):
    """The fault seam `engine.preempt` takes the newest lane back to the
    queue after the third dispatch was routed and before the fourth is
    planned: its stream pauses, resumes by re-prefill and is whole."""
    from kserve_tpu.resilience import FaultPlan, FaultSpec

    engine = make(max_batch_size=2)
    await engine.start()
    engine.fault_plan = FaultPlan(
        [FaultSpec("engine.preempt", "preempt", after=3, count=1)])
    try:
        streams = await together(
            engine, older=(PROMPT_A, greedy(20)),
            newer=(PROMPT_B, sampled(20, seed=17)))
        NOTES["preemptions"] = engine.preemption_count
        return streams
    finally:
        await engine.stop()


CASES = {
    "max_tokens_mid_dispatch": max_tokens_mid_dispatch,
    "eos_mid_dispatch": eos_mid_dispatch,
    "min_tokens": min_tokens,
    "max_model_len": max_model_len,
    "stop_string_beside_deferred": stop_string_beside_deferred,
    "reseated_lane": reseated_lane,
    "cancelled_between_dispatches": cancelled_between_dispatches,
    "preempted_between_dispatches": preempted_between_dispatches,
}


def jsonable(streams):
    return {name: [list(r) for r in rows] for name, rows in streams.items()}


def recorded(model, case):
    """The parent commit's streams of `case` on `model`."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "delivery_streams.json")
    with open(path) as f:
        return json.load(f)[model][case]


#: the cases the hybrid and the looped model run too (same loop)
OTHER_MODELS = ("stop_string_beside_deferred", "reseated_lane")
MODELS = {"llama": (llama_engine, tuple(CASES)),
          "hybrid": (hybrid_engine, OTHER_MODELS),
          "ouro": (ouro_engine, OTHER_MODELS)}


def record():
    """{model: {case: {stream: rows}}}"""
    return {model: {case: jsonable(asyncio.run(CASES[case](make)))
                    for case in cases}
            for model, (make, cases) in MODELS.items()}


def dump(recording, f):
    """One stream a line, so that a diff of the recording can be read."""
    models = []
    for model, cases in sorted(recording.items()):
        by_case = []
        for case, streams in sorted(cases.items()):
            lines = ",\n".join(f"   {json.dumps(name)}: {json.dumps(rows)}"
                               for name, rows in sorted(streams.items()))
            by_case.append(f"  {json.dumps(case)}: {{\n{lines}\n  }}")
        models.append(f" {json.dumps(model)}: {{\n" + ",\n".join(by_case) + "\n }")
    f.write("{\n" + ",\n".join(models) + "\n}\n")


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        dump(record(), f)
