"""engine/shapes.DispatchShapes: the one place that decides which (T, W)
program a dispatch runs in.  Held here to a frozen copy of the two
functions that decided it before (LLMEngine._bucket_for with _plan_ragged's
round-up, EngineConfig.page_bucket), to the benchmark's hand-kept copy of
the policy, and to what a running engine dispatches and warms; and
engine/shapes.LoadedPairs, which fits the pair a mixed dispatch needs to
the pairs the program is loaded in.
"""

import asyncio
import json
import os
import re
import sys
import types

import pytest
from aiohttp.test_utils import TestClient, TestServer
from prometheus_client import REGISTRY

from conftest import async_test

from kserve_tpu import Model, ModelRepository
from kserve_tpu.engine.compiled import (compile_fingerprints,
                                        reset_compile_fingerprints)
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine import shapes as shapes_module
from kserve_tpu.engine.shapes import (FITS, SETTLED_AFTER, DispatchShapes,
                                      LoadedPairs)
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


@pytest.mark.parametrize("backend,align", [("tpu", RAGGED_BQ), ("cpu", 1)])
def test_latent_pages_take_the_kernel_on_a_tpu_whatever_the_head_size(
        backend, align):
    model = types.SimpleNamespace(cache_head_dim=64, is_latent=True)
    assert DispatchShapes.of(model, EngineConfig(), backend).align == align


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
    from the argument spellings (the tokens' buffer int32[3,T], the lanes'
    buffer, the cache, the page table int32[B,W], the base key:
    shapes.MixedLayout)."""
    out = []
    for event in compile_fingerprints("mixed"):
        t = re.search(r"int32\[3,(\d+)\], int32\[\d+,(\d+)\], \(",
                      event["signature"])
        w = re.search(r"\), int32\[%s,(\d+)\], uint32\[2\]" % t.group(2),
                      event["signature"])
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
        assert shapes.pop("loaded") == []  # nothing has run yet
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
        # the pairs it ran in are the loaded ones, and each holds the pair
        # its dispatch needed
        loaded = engine.scheduler_state()["dispatch"]["shapes"]["loaded"]
        assert loaded == sorted(map(list, taken))
        for r in rows:
            assert r[col["need_tokens"]] <= r[col["tokens"]]
            assert r[col["need_width"]] <= r[col["width"]]
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
        assert engine._loaded.published() == sorted(map(list, mixed))
    finally:
        await engine.stop()


def _settle(loaded, pair):
    """Run `pair` (loaded already) until the engine counts as settled."""
    for _ in range(SETTLED_AFTER):
        loaded.ran(pair)
    assert loaded.settled


def test_a_settled_engine_runs_in_the_smallest_loaded_pair_that_holds_it():
    loaded = LoadedPairs()
    assert loaded.fit(128, 8) == ((128, 8), "compiled")  # nothing loaded
    assert loaded.published() == []  # `fit` alone loads nothing
    for pair in [(128, 32), (256, 32), (512, 16), (512, 64), (1024, 128)]:
        loaded.ran(pair)
    assert loaded.published() == [
        [128, 32], [256, 32], [512, 16], [512, 64], [1024, 128]]
    _settle(loaded, (128, 32))
    for need, ran, fit in [
        ((128, 32), (128, 32), "exact"),
        ((1024, 128), (1024, 128), "exact"),
        # least T first, then least W
        ((128, 8), (128, 32), "padded"),
        ((128, 64), (512, 64), "padded"),
        ((256, 16), (256, 32), "padded"),
        ((512, 8), (512, 16), "padded"),
        ((512, 32), (512, 64), "padded"),
        ((512, 128), (1024, 128), "padded"),
        ((1024, 8), (1024, 128), "padded"),
        # no loaded pair is as long AND as wide
        ((2048, 8), (2048, 8), "compiled"),
        ((128, 256), (128, 256), "compiled"),
    ]:
        assert loaded.fit(*need) == (ran, fit), need
        assert fit in FITS
    # an exact match is taken whatever else holds the pair
    assert LoadedPairs([(128, 8), (128, 32)]).fit(128, 8) == ((128, 8), "exact")


def test_an_engine_that_loaded_a_pair_lately_compiles_what_it_needs():
    """Until SETTLED_AFTER dispatches in a row have run in pairs already
    loaded, need alone chooses the pair, as before: an engine's first
    traffic and a client that drives shapes compile them."""
    loaded = LoadedPairs([(1024, 128)])  # as the AOT cache preloads
    assert not loaded.settled
    assert loaded.fit(128, 32) == ((128, 32), "compiled")
    for _ in range(SETTLED_AFTER - 1):
        loaded.ran((1024, 128))
        assert loaded.fit(128, 32) == ((128, 32), "compiled")
    loaded.ran((1024, 128))
    assert loaded.settled
    assert loaded.fit(128, 32) == ((1024, 128), "padded")
    assert loaded.fit(2048, 32) == ((2048, 32), "compiled")
    # a pair no loaded one holds compiles and joins, and the engine is
    # being warmed again
    loaded.ran((2048, 32))
    assert not loaded.settled and loaded.published() == [[1024, 128], [2048, 32]]
    assert loaded.fit(128, 32) == ((128, 32), "compiled")
    _settle(loaded, (2048, 32))
    assert loaded.fit(128, 32) == ((1024, 128), "padded")
    assert loaded.fit(2048, 8) == ((2048, 32), "padded")


def _grid_dispatches(cell):
    """The (T, W) pairs the dispatches of `warmup.grid_warmup` need for a
    cell, in order.  Per stage (W ascending): the anchor's prompt, whose
    chunk can be LONGER than every wave of its stage; the background
    (anchor and pacers decoding) while the first wave is awaited; per wave
    (T ascending) the dispatch it rides and one of background; then the
    anchor's last four dispatches (`grid_plan`'s `life`)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from kbench import warmup
    finally:
        sys.path.pop(0)
    cell_file = _load("cells", cell + ".json")
    config = _load("configs", cell.split(".")[0] + ".json")
    dep = config["deployment"]
    flags = {**dep["server_flags"], **cell_file["server_flags"]}
    grid = warmup.grid_of(dep["engine_policy"], flags, cell_file["warm"])
    buckets = grid["token_buckets"]
    base = grid["lane_tokens"] * (grid["pacers"] + 1)
    background = min(t for t in buckets if t >= base)
    needs = []
    for w, anchor_prompt, _, waves in warmup.grid_plan(grid):
        needs.append((min(t for t in buckets if t >= anchor_prompt), w))
        needs += [(background, w)] * 2
        for t, _, _ in waves:
            needs += [(t, w), (background, w)]
        needs += [(background, w)] * 4
    return grid, needs


#: `_aot_warmup`'s pairs under the cells' server sizes (each prompt finds
#: the one before it in the prefix cache; a hybrid model has none, and
#: reaches the same widths), loaded before the grid starts
WARMED_AT_START = {
    "qwen3-4b.chat": [(32, 8), (64, 16), (128, 32), (256, 64), (512, 128)],
    "qwen3-4b.decode-sat": [(32, 8), (64, 16), (128, 32), (256, 40)],
    "phi4-mini-flash.reason-sat": [(32, 8), (64, 16), (128, 32), (256, 64)],
}
#: the probes that follow it need this pair, eight dispatches in a row
PROBES = [(64, 8)] * 8


@pytest.mark.parametrize("cell, n_pairs", [
    ("qwen3-4b.chat", 12), ("qwen3-4b.decode-sat", 8),
    ("phi4-mini-flash.reason-sat", 8)])
def test_the_benchmarks_grid_still_compiles_every_pair_it_drives(cell, n_pairs):
    """The grid warms shapes by driving them, on an engine whose warm-up
    has loaded pairs that hold most of the grid's, and each stage's first
    dispatch (the anchor's prompt) holds the stage's others: the pairs
    still compile one by one, as when need alone chose them, because they
    come fewer than SETTLED_AFTER dispatches apart.  A warm start finds
    each loaded.  Then a dispatch that packs tighter than the grid pads
    into it and compiles nothing."""
    grid, needs = _grid_dispatches(cell)
    warmed = {(t, w) for t in grid["warm_tokens"] for w in grid["warm_widths"]}
    assert len(warmed) == n_pairs and warmed <= set(needs)

    def replay(loaded):
        """How each pair was first found, and the most dispatches in a row
        that loaded nothing new before one that did."""
        fits, longest, since = {}, 0, 0
        for need in PROBES + needs:
            ran, fit = loaded.fit(*need)
            fits.setdefault(need, fit)
            if fit == "compiled":
                longest, since = max(longest, since), 0
            else:
                since += 1
            loaded.ran(ran)
        return fits, longest

    cold = LoadedPairs()
    for pair in WARMED_AT_START[cell]:
        cold.ran(pair)
    fits, longest = replay(cold)
    first_time = set(needs) - set(WARMED_AT_START[cell])
    assert {n for n, fit in fits.items() if fit == "compiled"} == (
        first_time | {PROBES[0]})
    assert "padded" not in fits.values()
    assert longest <= SETTLED_AFTER // 2  # room on the grid's side

    warm = LoadedPairs(map(tuple, cold.published()))  # the cache's pairs
    fits, _ = replay(warm)
    assert set(fits.values()) == {"exact"}
    assert warm.published() == cold.published()

    # the window: settled since the ramp, a need below the grid pads
    _settle(cold, min(warmed))
    for t in grid["token_buckets"]:
        for w in grid["width_buckets"]:
            ran, fit = cold.fit(t, w)
            if t <= max(grid["warm_tokens"]) and w <= max(grid["warm_widths"]):
                assert fit != "compiled" and ran[0] >= t and ran[1] >= w
                if (t, w) in warmed:
                    assert (ran, fit) == ((t, w), "exact")
            else:
                assert (ran, fit) == ((t, w), "compiled")


def _tiny_engine(label, **overrides):
    from kserve_tpu.engine.engine import LLMEngine
    from kserve_tpu.engine.tokenizer import ByteTokenizer

    model_config = LlamaConfig.tiny(dtype="float32")
    cfg = dict(
        max_batch_size=4, page_size=8, num_pages=64, max_pages_per_seq=16,
        max_prefill_len=64, prefill_buckets=(16, 32, 64), dtype="float32",
        use_pallas=False, steps_per_sync=2)
    cfg.update(overrides)
    return LLMEngine(model_config, EngineConfig(**cfg),
                     ByteTokenizer(model_config.vocab_size),
                     metrics_label=label)


def _fit_counts(label):
    return {fit: REGISTRY.get_sample_value(
        "engine_dispatch_shape_total", {"model_name": label, "fit": fit})
        or 0.0 for fit in FITS}


async def _generate(engine, prompt, n):
    params = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
    return [out.token_id async for out in engine.generate(prompt, params)]


@async_test
async def test_a_tighter_third_pair_pads_into_a_loaded_one_and_compiles_nothing(
        monkeypatch):
    """Two pairs loaded, (16, 8) by a short request and (64, 16) by a long
    prompt's first chunk; the prompt's remainder needs (32, 16) and its
    decode steps (16, 16): both run in (64, 16), with no compile, counted
    `padded`, and the tokens are those of an engine that compiles every
    pair it needs."""
    short, long = list(range(3, 10)), list(range(20, 110))
    engine = _tiny_engine("shapes-padded")
    exact = _tiny_engine("shapes-exact")
    # the reference: need alone chooses the pair, as before
    exact._loaded.fit = lambda t, w: ((t, w), "compiled")
    # the engine under test counts as settled from its first dispatch
    monkeypatch.setattr(shapes_module, "SETTLED_AFTER", 0)
    await engine.start()
    await exact.start()
    try:
        want = [await _generate(exact, short, 6), await _generate(exact, long, 12)]
        assert _fit_counts("shapes-exact")["padded"] == 0

        assert await _generate(engine, short, 6) == want[0]
        assert engine._loaded.published() == [[16, 8]]
        before, fits = _compile_counts(), _fit_counts("shapes-padded")
        assert fits["compiled"] == 1 and fits["padded"] == 0
        assert await _generate(engine, long, 12) == want[1]
        after, fits = _compile_counts(), _fit_counts("shapes-padded")
        assert engine._loaded.published() == [[16, 8], [64, 16]]
        assert after["mixed"] - before["mixed"] == 1  # (64, 16) alone
        assert fits["compiled"] == 2 and fits["padded"] >= 2

        rows = [dict(zip(DISPATCH_COLUMNS, r))
                for r in engine.telemetry.dispatches]
        padded = [r for r in rows if (r["need_tokens"], r["need_width"])
                  != (r["tokens"], r["width"])]
        # (the last dispatch's row is committed after its tokens are out)
        assert fits["padded"] - 1 <= len(padded) <= fits["padded"]
        assert {(r["need_tokens"], r["need_width"]) for r in padded} == {
            (32, 16), (16, 16)}
        assert {(r["tokens"], r["width"]) for r in padded} == {(64, 16)}
        assert not any(r["compiled"] for r in padded)
        # a further request that needs the tighter pair again: still none
        assert await _generate(engine, long, 12) == want[1]
        assert _compile_counts() == after
        assert _fit_counts("shapes-padded")["compiled"] == 2
    finally:
        await engine.stop()
        await exact.stop()


@async_test
async def test_an_engine_settles_after_dispatches_that_loaded_nothing_new():
    """The real constant: a request's first dispatches compile the pairs
    they need, (64, 8) for its prompt and (16, 8) for its decode steps,
    though the first holds the second; SETTLED_AFTER decode dispatches
    later a 20-token prompt, which needs (32, 8), pads into (64, 8)."""
    engine = _tiny_engine("shapes-settling")
    await engine.start()
    try:
        await _generate(engine, list(range(3, 53)), 12)
        assert engine._loaded.published() == [[16, 8], [64, 8]]
        assert not engine._loaded.settled
        await _generate(engine, [5, 6, 7], 2 * SETTLED_AFTER + 4)
        assert engine._loaded.published() == [[16, 8], [64, 8]]
        assert engine._loaded.settled
        fits = _fit_counts("shapes-settling")
        assert (fits["padded"], fits["compiled"]) == (0, 2)
        before = _compile_counts()
        await _generate(engine, list(range(60, 80)), 8)
        assert _compile_counts() == before
        assert engine._loaded.published() == [[16, 8], [64, 8]]
        assert _fit_counts("shapes-settling")["padded"] == 1
        rows = [dict(zip(DISPATCH_COLUMNS, r))
                for r in engine.telemetry.dispatches]
        (row,) = [r for r in rows if r["need_tokens"] != r["tokens"]]
        assert (row["need_tokens"], row["need_width"], row["tokens"],
                row["width"], row["compiled"]) == (32, 8, 64, 8, 0)
    finally:
        await engine.stop()


@async_test
async def test_a_warm_start_plans_with_the_pairs_the_cache_preloaded(
        tmp_path, monkeypatch):
    """The AOT cache's `mixed` executables are loaded pairs from the start:
    the warm engine (settled from its first dispatch, here) fits its first
    dispatch to them, where the cold one had nothing loaded."""
    monkeypatch.setattr(shapes_module, "SETTLED_AFTER", 0)
    cold = _tiny_engine("shapes-cold", aot_cache_dir=str(tmp_path),
                        aot_warmup=False)
    assert cold._loaded.published() == []
    await cold.start()
    try:
        want = await _generate(cold, list(range(20, 110)), 12)
        pairs = cold._loaded.published()
        assert pairs == [[64, 16]]
    finally:
        await cold.stop()
    warm = _tiny_engine("shapes-warm", aot_cache_dir=str(tmp_path),
                        aot_warmup=False)
    assert warm._loaded.published() == pairs  # before any dispatch
    base = _compile_counts()
    await warm.start()
    try:
        assert await _generate(warm, list(range(3, 10)), 6)  # needs (16, 8)
        assert await _generate(warm, list(range(20, 110)), 12) == want
        assert _compile_counts() == base
        assert warm._loaded.published() == pairs
        fits = _fit_counts("shapes-warm")
        assert fits["compiled"] == 0 and fits["padded"] > 0 and fits["exact"] > 0
    finally:
        await warm.stop()


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
