"""engine/shapes.DispatchShapes: the one place that decides which (T, W)
program a dispatch runs in.  Held here to a frozen copy of the two
functions that decided it before (LLMEngine._bucket_for with _plan_ragged's
round-up, EngineConfig.page_bucket), to the benchmark's hand-kept copy of
the policy, and to what a running engine dispatches and warms.
"""

import asyncio
import json
import os
import re
import types

import pytest
from aiohttp.test_utils import TestClient, TestServer

from conftest import async_test

from kserve_tpu import Model, ModelRepository
from kserve_tpu.engine.compiled import (compile_fingerprints,
                                        reset_compile_fingerprints)
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.shapes import DispatchShapes
from kserve_tpu.engine.types import EngineConfig
from kserve_tpu.metrics import XLA_COMPILES
from kserve_tpu.models.llama import LlamaConfig
from kserve_tpu.observability.timeline import DISPATCH_COLUMNS
from kserve_tpu.ops.pallas_paged_attention import RAGGED_BQ
from kserve_tpu.protocol.model_repository_extension import ModelRepositoryExtension
from kserve_tpu.protocol.openai.dataplane import OpenAIDataPlane
from kserve_tpu.protocol.rest.server import RESTServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the ragged kernel can run on this model's cache rows (128 wide): with
#: backend "tpu" slices are aligned, with "cpu" packed densely
KERNEL_MODEL = types.SimpleNamespace(cache_head_dim=128)


def frozen_bucket_for(prefill_buckets, n):
    """LLMEngine._bucket_for as it stood at PR 31 (engine/engine.py:2185),
    kept here unedited as the reference."""
    for b in prefill_buckets:
        if n <= b:
            return b
    return prefill_buckets[-1]


def frozen_page_bucket(max_pages_per_seq, n_pages):
    """EngineConfig.page_bucket as it stood at PR 31 (engine/types.py:202)."""
    b = 8
    while b < n_pages:
        b *= 2
    return min(b, max_pages_per_seq)


def deployment(max_model_len, max_prefill_len):
    """A cell of the benchmark as its server resolves it (48 lanes, pages
    of 16 tokens, generative_server's flag-to-config arithmetic)."""
    return dict(max_batch_size=48, num_pages=2300, page_size=16,
                max_pages_per_seq=max_model_len // 16,
                max_prefill_len=max_prefill_len)


CONFIGS = {
    "default": {},
    "qwen3-4b.chat": deployment(2048, 1024),
    "qwen3-4b.decode-sat": deployment(640, 512),
    "phi4-mini-flash.reason-sat": deployment(1024, 512),
    # buckets that are no multiple of the alignment, fewer pages than the
    # ladder's first rung
    "odd": dict(max_prefill_len=100, prefill_buckets=(20, 50),
                max_pages_per_seq=6, page_size=16),
}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tokens_and_width_are_what_the_engine_chose_before(name, backend):
    cfg = EngineConfig(**CONFIGS[name])
    shapes = DispatchShapes.of(KERNEL_MODEL, cfg, backend)
    align = RAGGED_BQ if backend == "tpu" else 1
    assert shapes.align == align
    assert shapes.token_buckets == cfg.prefill_buckets
    assert shapes.token_budget == cfg.prefill_buckets[-1]
    assert shapes.steps == cfg.steps_per_sync
    assert shapes.fits_pure_decode == (
        cfg.max_batch_size * align <= cfg.prefill_buckets[-1])
    for n in range(1, cfg.max_prefill_len + 2 * align + 1):
        bucket = frozen_bucket_for(cfg.prefill_buckets, n)
        assert shapes.bucket(n) == bucket, n
        assert shapes.tokens(n) == -(-bucket // align) * align, n
    for p in range(0, cfg.max_pages_per_seq + 3):
        want = frozen_page_bucket(cfg.max_pages_per_seq, p)
        assert shapes.width(p) == want, p
        assert cfg.page_bucket(p) == want, p
    pairs = shapes.pairs()
    assert pairs == sorted(set(pairs))
    assert {t for t, _ in pairs} == {
        shapes.tokens(b) for b in cfg.prefill_buckets}
    assert {w for _, w in pairs} == {
        frozen_page_bucket(cfg.max_pages_per_seq, p)
        for p in range(1, cfg.max_pages_per_seq + 1)}


@pytest.mark.parametrize("use_pallas,kv_quant,head_dim,backend,align", [
    (None, None, 128, "tpu", RAGGED_BQ),
    (None, None, 64, "tpu", 1),       # rows the kernel cannot tile
    (None, "int8", 128, "tpu", 1),    # int8 pages stay on the gather
    (None, None, 128, "cpu", 1),
    (True, None, 64, "cpu", RAGGED_BQ),  # forced: interpret-mode tests
    (False, None, 128, "tpu", 1),
])
def test_alignment_follows_where_the_ragged_kernel_can_run(
        use_pallas, kv_quant, head_dim, backend, align):
    cfg = EngineConfig(use_pallas=use_pallas, kv_quant=kv_quant)
    model = types.SimpleNamespace(cache_head_dim=head_dim)
    assert DispatchShapes.of(model, cfg, backend).align == align


def test_buckets_sp_cannot_split_are_refused():
    cfg = EngineConfig(sp=4, max_prefill_len=30, prefill_buckets=(16, 30))
    with pytest.raises(ValueError, match=r"prefill buckets \[30\] not "
                       r"divisible by sp=4"):
        DispatchShapes.of(KERNEL_MODEL, cfg, "cpu")


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_published_policy_is_the_benchmarks_copy():
    """`dispatch.shapes` for qwen3-4b.chat's server flags, key for key
    against `deployment.engine_policy`: the copy the benchmark keeps by
    hand until a benchmark PR reads the endpoint instead."""
    config = _load("configs", "qwen3-4b.json")
    cell = _load("cells", "qwen3-4b.chat.json")
    dep = config["deployment"]
    flags = {**dep["server_flags"], **cell["server_flags"]}
    cfg = EngineConfig(
        max_batch_size=flags["max_batch_size"], page_size=flags["page_size"],
        num_pages=flags["kv_pages"],
        max_pages_per_seq=flags["max_model_len"] // flags["page_size"],
        max_prefill_len=flags["max_prefill_len"])
    published = DispatchShapes.of(
        LlamaConfig.from_hf_config(config), cfg, "tpu").published()
    policy = dep["engine_policy"]
    shared = set(policy) & set(published)
    assert shared == {"lane_tokens", "tokens_per_dispatch", "token_buckets",
                      "min_width"}
    assert {k: published[k] for k in shared} == policy
    assert published["width_buckets"] == [8, 16, 32, 64, 128]
    assert json.loads(json.dumps(published)) == published


class _Served(Model):
    def __init__(self, engine):
        super().__init__("tiny")
        self.engine = engine
        self.ready = True


def _compile_counts():
    return {s.labels["program"]: int(s.value)
            for metric in XLA_COMPILES.collect() for s in metric.samples
            if s.name.endswith("_total")}


def _mixed_shapes_compiled():
    """(T, W) of every `mixed` compile recorded since the last reset, read
    from the argument spellings (q_tokens int32[T] ..., page_table
    int32[B,W])."""
    out = []
    for event in compile_fingerprints("mixed"):
        t = re.search(r"int32\[(\d+)\], int32\[\1\], int32\[\1\]",
                      event["signature"])
        w = re.search(r"\), int32\[\d+,(\d+)\], bool\[", event["signature"])
        out.append((int(t.group(1)), int(w.group(1))))
    return out


@async_test
async def test_state_endpoint_publishes_the_shapes_dispatches_take():
    from test_engine import make_engine

    engine = make_engine(max_pages_per_seq=16, max_prefill_len=64,
                         prefill_buckets=(16, 32, 64), steps_per_sync=2)
    repo = ModelRepository()
    repo.update(_Served(engine))
    app = RESTServer(OpenAIDataPlane(repo),
                     ModelRepositoryExtension(repo)).create_application()
    await engine.start()
    try:
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/v1/internal/scheduler/state")
            state = await resp.json()
        shapes = state["models"]["tiny"]["dispatch"]["shapes"]
        assert shapes == engine._shapes.published() == {
            "lane_tokens": 1, "tokens_per_dispatch": 2,
            "token_buckets": [16, 32, 64], "width_buckets": [8, 16],
            "min_width": 8}

        async def run(prompt, n):
            params = SamplingParams(max_tokens=n, temperature=0.0,
                                    ignore_eos=True)
            async for _ in engine.generate(prompt, params):
                pass

        # short and long prompts together: chunks and decode lanes share
        # dispatches, page tables pass the ladder's first rung
        await asyncio.gather(
            run(list(range(3, 10)), 40), run(list(range(20, 110)), 12),
            run(list(range(5, 45)), 30), run([7, 8], 6))
        col = {name: i for i, name in enumerate(DISPATCH_COLUMNS)}
        rows = [r for r in engine.telemetry.dispatches
                if r[col["program"]] == "mixed"]
        taken = {(r[col["tokens"]], r[col["width"]]) for r in rows}
        assert len(taken) >= 3, taken
        assert taken <= set(engine._shapes.pairs()), taken
    finally:
        await engine.stop()


#: what _aot_warmup compiled at PR 31, by engine config: programs with
#: their counts, and the (T, W) of each `mixed` (the second warm-up prompt
#: of a run finds the first's pages in the prefix cache, so it packs into
#: the first's T)
WARMUP_AT_PR31 = {
    "mixed": (
        {}, {"mixed": 1}, [(16, 8)]),
    "mixed-three-buckets": (
        dict(max_pages_per_seq=16, max_prefill_len=64,
             prefill_buckets=(16, 32, 64), steps_per_sync=2),
        {"mixed": 2}, [(16, 8), (32, 16)]),
    "legacy": (
        dict(use_ragged=False),
        {"prefill": 1, "prefill_chunk": 1, "sample_first": 1, "decode": 1},
        []),
}


@pytest.mark.parametrize("name", sorted(WARMUP_AT_PR31))
@async_test
async def test_warmup_compiles_what_it_compiled_before(name):
    from test_engine import make_engine

    overrides, programs, mixed = WARMUP_AT_PR31[name]
    engine = make_engine(aot_warmup=True, **overrides)
    reset_compile_fingerprints()
    base = _compile_counts()
    await engine.start()
    try:
        now = _compile_counts()
        compiled = {k: now[k] - base.get(k, 0) for k in now
                    if now[k] != base.get(k, 0)}
        assert compiled == programs
        assert _mixed_shapes_compiled() == mixed
        assert set(mixed) <= set(engine._shapes.pairs())
    finally:
        await engine.stop()


def test_the_decision_lives_in_shapes_py_only():
    """A fence: the engine and the program table ask DispatchShapes; they
    do not read the bucket lists or derive the alignment themselves."""
    for module in ("engine.py", "compiled.py"):
        with open(os.path.join(ROOT, "kserve_tpu", "engine", module)) as f:
            source = f.read()
        for name in ("page_bucket(", "prefill_buckets",
                     "_should_use_ragged_pallas", "_bucket_for",
                     "_ragged_align"):
            assert name not in source, (module, name)
