"""The K/V page write (ops/pallas_kv_write.py) against the row scatter it
replaces (ops/kv_write._scatter_kv), bit for bit, in interpret mode on
the CPU; the predicate that chooses between them; and the invariant the
page write rests on: no two lanes of one dispatch write the same page.

Every page but the null page must be equal: the scatter sends what it does
not write (dead lanes, padding tokens) to page 0, the kernel writes nothing
for them, so page 0 must come back as it went in.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.models import hybrid
from kserve_tpu.ops import attention as att
from kserve_tpu.ops import kv_write
from kserve_tpu.ops import pallas_kv_write as pw

#: (n_kv, page_size, head_dim) of a layer's cache, as ONE device holds it
CACHES = {
    "qwen3-4b": (8, 16, 128),
    "ouro-2.6b": (16, 16, 128),
    "phi4-mini-flash": (10, 16, 128),  # rings and pool: pairs side by side
    "glm47-flash-as-kv": (1, 16, 640),  # its row's width, were it K and V
    "gemma2-2b": (4, 16, 256),
    "qwen3-4b/page8": (8, 8, 128),
}
LANES, CONTEXT, T = 6, 80, 64  # a lane's table holds CONTEXT positions


@functools.lru_cache(maxsize=None)
def _fixture(name):
    nkv, ps, d = CACHES[name]
    width = CONTEXT // ps
    pages = 1 + LANES * width + 9
    rng = np.random.default_rng(len(name))
    cache = jnp.asarray(
        rng.standard_normal((pages, 2, nkv, ps, d)), jnp.bfloat16)
    # every lane its own pages; lanes 0 and 1 on NEIGHBOURING pages
    ids = 1 + rng.permutation(pages - 1)[: LANES * width]
    table = np.sort(ids[: 2 * width]).reshape(width, 2).T.tolist()
    table += ids[2 * width:].reshape(LANES - 2, width).tolist()
    rows = {n: (jnp.asarray(rng.standard_normal((n, nkv, d)), jnp.bfloat16),
                jnp.asarray(rng.standard_normal((n, nkv, d)), jnp.bfloat16))
            for n in (LANES, T)}
    return cache, jnp.asarray(table, jnp.int32), rows


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    return jax.jit(functools.partial(fn, interpret=True))


def _interpreted(monkeypatch):
    """Calls that reach the kernel through ops/kv_write run it in
    interpret mode."""
    kernel = pw.kv_page_write
    monkeypatch.setattr(
        pw, "kv_page_write",
        lambda *args, **kw: kernel(*args, **dict(kw, interpret=True)))


def _assert_same(got, want, cache):
    np.testing.assert_array_equal(
        np.asarray(got[1:], np.float32), np.asarray(want[1:], np.float32))
    np.testing.assert_array_equal(
        np.asarray(got[0], np.float32), np.asarray(cache[0], np.float32))


@pytest.mark.parametrize("name", sorted(CACHES))
def test_decode_write_matches_scatter(name):
    """One row a live lane: the first and last slot of a page, the first
    slot of the next page, a dead lane, neighbouring pages."""
    cache, table, rows = _fixture(name)
    ps = cache.shape[3]
    k, v = rows[LANES]
    pos = jnp.asarray([0, ps - 1, ps, 2 * ps + 1, CONTEXT - 1, 5], jnp.int32)
    active = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    want = kv_write.append_token_kv(
        cache, k, v, table, pos, active, ps, page_kernel=False)
    got = _jitted(pw.append_rows)(cache, k, v, table, pos, active)
    _assert_same(got, want, cache)
    assert not np.array_equal(np.asarray(got, np.float32),
                              np.asarray(cache, np.float32))


def _tokens_of(q_start, q_len, kv_start):
    """(token_seq, token_pos) [T] of slices (q_start, q_len, kv_start):
    what is in no slice is padding (-1)."""
    seq = np.full((T,), -1, np.int32)
    pos = np.zeros((T,), np.int32)
    for b, (s, n, p) in enumerate(zip(q_start, q_len, kv_start)):
        seq[s: s + n] = b
        pos[s: s + n] = p + np.arange(n)
    return jnp.asarray(seq), jnp.asarray(pos)


#: name -> lanes' (q_start, q_len, kv_start), page_size 16 in mind (a page
#: of 8 only makes the runs span more pages)
RUNS = {
    "lengths 0 / 1 / a page's remainder / several pages": (
        [0, 8, 16, 24, 32, 40], [1, 0, 7, 0, 1, 23], [3, 9, 9, 0, 31, 40]),
    "a run that ends on a page's last slot, one that starts on its first": (
        [0, 8, 16, 24, 32, 56], [8, 8, 1, 0, 16, 4], [8, 16, 47, 5, 0, 60]),
    "decode lanes alone, padding between them": (
        [0, 8, 16, 24, 32, 40], [1, 1, 1, 1, 1, 1], [0, 15, 16, 17, 79, 33]),
    "one prompt over the whole buffer": (
        [0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 64, 0], [0, 0, 0, 0, 5, 0]),
    "nothing at all": ([0] * 6, [0] * 6, [0] * 6),
}


@pytest.mark.parametrize("case", sorted(RUNS))
@pytest.mark.parametrize("name", sorted(CACHES))
def test_packed_write_matches_scatter(name, case):
    cache, table, rows = _fixture(name)
    ps = cache.shape[3]
    k, v = rows[T]
    q_start, q_len, kv_start = (np.asarray(a, np.int32) for a in RUNS[case])
    seq, pos = _tokens_of(q_start, q_len, kv_start)
    want = kv_write.write_ragged_kv(
        cache, k, v, table, seq, pos, ps, page_kernel=False)
    got = _jitted(pw.write_runs)(
        cache, k, v, table, jnp.arange(LANES, dtype=jnp.int32),
        jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_start))
    _assert_same(got, want, cache)


#: lanes' (q_len, kv_start) on a ring of 32 positions (two pages of 16):
#: short of the end, up to it, wrapping, as long as the ring (the two runs
#: meet on a page), longer (only the newest 32 are kept), nothing
RING_SLICES = {
    "decode": ([1, 1, 1, 1, 1, 1], [0, 31, 32, 95, 40, 7]),
    "wraps": ([8, 8, 8, 0, 3, 2], [20, 28, 60, 0, 31, 30]),
    "fills": ([32, 24, 0, 0, 0, 0], [8, 20, 0, 0, 0, 0]),
    "overflows": ([40, 16, 0, 0, 0, 0], [100, 24, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(RING_SLICES))
def test_ring_write_matches_scatter(case, monkeypatch):
    """A window layer's packed write (models/hybrid.forward_ragged): the
    ring table and `pos % R`, the kept tokens as two sets of runs."""
    cache, table, rows = _fixture("phi4-mini-flash")
    ps = cache.shape[3]
    ring_table = table[:, :2]
    R = 2 * ps
    k, v = rows[T]
    q_len, kv_start = (np.asarray(a, np.int32) for a in RING_SLICES[case])
    q_start = np.concatenate([[0], np.cumsum(-(-q_len[:-1] // 8) * 8)]).astype(
        np.int32)
    seq, pos = _tokens_of(q_start, q_len, kv_start)
    lane = jnp.maximum(seq, 0)
    kept = pos >= jnp.asarray(kv_start + q_len)[lane] - R
    ring_seq = jnp.where(kept, seq, -1)
    want = kv_write.write_ragged_kv(
        cache, k, v, ring_table, ring_seq, pos % R, ps, page_kernel=False)
    _interpreted(monkeypatch)
    got = kv_write.write_ragged_kv(
        cache, k, v, ring_table, ring_seq, pos % R, ps,
        runs=hybrid._ring_runs(jnp.asarray(q_start), jnp.asarray(q_len),
                               jnp.asarray(kv_start), R),
        page_kernel=True)
    _assert_same(got, want, cache)


def test_work_items_cover_every_page_once():
    """run_work_items: the pages of the runs in order, each once, within
    max_work_items; idle items write nothing."""
    ps = 16
    table = jnp.arange(LANES * 5, dtype=jnp.int32).reshape(LANES, 5) + 1
    q_start, q_len, kv_start = (
        jnp.asarray(a, jnp.int32)
        for a in RUNS["lengths 0 / 1 / a page's remainder / several pages"])
    n_items = pw.max_work_items(T, LANES, ps)
    page, lo, hi, src = (np.asarray(a) for a in pw.run_work_items(
        table, jnp.arange(LANES, dtype=jnp.int32), q_start, q_len, kv_start,
        ps, n_items))
    live = hi > lo
    assert len(set(page[live])) == live.sum()  # no page twice
    assert (hi - lo)[live].sum() == int(q_len.sum())  # every row once
    want = [(int(table[b, p // ps]), p % ps, b_start + i)
            for b, (b_start, n, p0) in enumerate(
                zip(q_start.tolist(), q_len.tolist(), kv_start.tolist()))
            for i, p in enumerate(range(p0, p0 + n))]
    got = [(int(page[w]), s, int(src[w]) + s - int(lo[w]))
           for w in np.nonzero(live)[0] for s in range(lo[w], hi[w])]
    assert got == want


PLAIN = jax.ShapeDtypeStruct((4, 2, 8, 16, 128), jnp.bfloat16)
ROW = jax.ShapeDtypeStruct((3, 8, 128), jnp.bfloat16)


@pytest.mark.parametrize("why, pages, v, backend, path", [
    ("a plain bf16 cache on a TPU", PLAIN, ROW, "tpu", "page_kernel"),
    ("head size 256", jax.ShapeDtypeStruct((4, 2, 4, 16, 256), jnp.bfloat16),
     ROW, "tpu", "page_kernel"),
    ("the int8 (pages, scales) tuple",
     (jax.ShapeDtypeStruct((4, 2, 8, 16, 128), jnp.int8),
      jax.ShapeDtypeStruct((4, 2, 8, 16), jnp.float32)),
     ROW, "tpu", "row_scatter"),
    ("latent pages: one row a token, v is None",
     jax.ShapeDtypeStruct((4, 1, 1, 16, 640), jnp.bfloat16), None, "tpu",
     "row_scatter"),
    ("head size 64", jax.ShapeDtypeStruct((4, 2, 8, 16, 64), jnp.bfloat16),
     ROW, "tpu", "row_scatter"),
    ("not a TPU", PLAIN, ROW, "cpu", "row_scatter"),
    ("the test host's own backend", PLAIN, ROW, None, "row_scatter"),
])
def test_predicate_table(why, pages, v, backend, path):
    assert att.kv_write_path(pages, v, backend) == path, why


def test_a_cache_sharded_over_a_mesh_keeps_the_scatter():
    """tp / sp / pp > 1: the forwards say so (`page_kernel=False`) and the
    dispatch report asks the predicate the same."""
    assert att._should_use_page_write(128, False, False, "tpu")
    assert not att._should_use_page_write(128, False, False, "tpu",
                                          sharded=True)


def test_the_predicate_has_no_shape_gate_because_no_measured_row_loses():
    """docs/data/kv_write_crossover.v5e.json (scripts/kv_write_crossover.py
    on the chip): at every measured shape of the cells the kernel wrote
    the same bytes and was faster, so `_should_use_page_write` asks what
    the kernel can run and nothing about lanes, tokens or heads."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "docs", "data",
                        "kv_write_crossover.v5e.json")
    rows = json.load(open(path))["rows"]
    assert {r["family"].split("/")[0] for r in rows} == {
        "qwen3-4b", "ouro-2.6b", "phi4-mini-flash"}
    assert {r["form"] for r in rows} == {"decode", "packed"}
    for r in rows:
        assert r["equal_but_null_page"], r
        assert r["scatter_us"] > 4 * r["kernel_us"], r
        assert att._should_use_page_write(r["d"], False, False, "tpu"), r


def _report(model_config, backend="tpu", **cfg):
    from kserve_tpu.engine.types import EngineConfig

    return att.describe_attention_dispatch(
        model_config, EngineConfig(**cfg), backend)["kv_write"]


def test_dispatch_report_names_the_path_of_every_cache_kind():
    from test_glm_model import CFG as GLM_TINY
    from test_hybrid_model import CFG as PHI_TINY

    from kserve_tpu.models.llama import LlamaConfig

    qwen = LlamaConfig.qwen3_0_6b()
    assert _report(qwen) == {"paged": "page_kernel"}
    assert _report(qwen, backend="cpu") == {"paged": "row_scatter"}
    assert _report(qwen, kv_quant="int8") == {"paged": "row_scatter"}
    assert _report(LlamaConfig.llama3_8b(), tp=4) == {"paged": "row_scatter"}
    assert _report(LlamaConfig.llama3_1b()) == {"paged": "row_scatter"}  # 64
    phi = LlamaConfig.from_hf_config(dict(
        PHI_TINY, hidden_size=2560, num_attention_heads=40,
        num_key_value_heads=20, head_dim=64))
    assert phi.cache_head_dim == 128
    assert _report(phi) == {"paged": "page_kernel", "window": "page_kernel"}
    assert _report(LlamaConfig.from_hf_config(GLM_TINY)) == {
        "latent": "row_scatter"}


# ---- the invariant the page write rests on, in the engine's own tables ----


def _pages_written(plan, page_size, steps):
    """{lane: pages} a mixed dispatch writes: the packed step's slices,
    then the decode steps of the lanes that join them, under their page
    capacity (compiled._make_mixed's own rule)."""
    table = plan["page_table"]
    out = {}
    for b in range(table.shape[0]):
        written = list(range(plan["kv_start"][b],
                             plan["kv_start"][b] + plan["q_len"][b]))
        if plan["joins"][b]:
            written += [p for p in range(plan["scan_pos0"][b],
                                         plan["scan_pos0"][b] + steps - 1)
                        if p < plan["capacity"][b]]
        if written:
            out[b] = {int(table[b, p // page_size]) for p in written}
    return out


def test_no_page_is_written_by_two_lanes_or_while_it_is_shared():
    """A whole-page read-modify-write is right only while the pages one
    dispatch writes belong to one lane each.  Prompts that share full
    pages through the prefix cache, more requests than lanes and a pool
    small enough to preempt: every page a dispatch writes is held once
    (the allocator's count: not by the cache, not by a second lane), is
    not the null page, and no two lanes write the same one."""
    import asyncio

    from prometheus_client import REGISTRY

    from kserve_tpu.engine.engine import LLMEngine
    from kserve_tpu.engine.sampling import SamplingParams
    from kserve_tpu.engine.tokenizer import ByteTokenizer
    from kserve_tpu.engine.types import EngineConfig
    from kserve_tpu.models.llama import LlamaConfig
    from kserve_tpu.observability import DISPATCH_COLUMNS

    model_config = LlamaConfig.tiny(dtype="float32")
    engine = LLMEngine(
        model_config,
        EngineConfig(max_batch_size=4, page_size=8, num_pages=20,
                     max_pages_per_seq=8, max_prefill_len=64,
                     prefill_buckets=(16, 32, 64), dtype="float32",
                     use_pallas=False, steps_per_sync=4),
        ByteTokenizer(model_config.vocab_size), metrics_label="kv-write-pages")
    seen = []
    plan_ragged = engine._plan_ragged

    def recording(meta, prefilling):
        plan = plan_ragged(meta, prefilling)
        seen.append((_pages_written(plan, 8, engine._shapes.steps),
                     list(engine.allocator._refs)))
        return plan

    engine._plan_ragged = recording
    shared = list(range(40, 64))  # three full pages every prompt starts with

    async def one(i):
        params = SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True)
        prompt = shared + [70 + i] * (3 + i)
        return [o.token_id async for o in engine.generate(prompt, params)]

    async def drive():
        await engine.start()
        try:
            await one(0)  # its full pages are in the cache from here on
            return await asyncio.gather(*(one(i) for i in range(1, 7)))
        finally:
            await engine.stop()

    asyncio.run(drive())
    # which path wrote, by the program's own report and counter: on this
    # backend the scatter, a layer-step a layer of every forward step
    assert engine.scheduler_state()["dispatch"]["attention"]["kv_write"] == {
        "paged": "row_scatter"}
    rows = [dict(zip(DISPATCH_COLUMNS, r)) for r in engine.telemetry.dispatches]
    assert all(r["kv_row_scatter"] == model_config.n_layers
               * engine._shapes.steps and r["kv_page_kernel"] == 0
               for r in rows)
    wrote = {path: REGISTRY.get_sample_value(
        "engine_kv_write_calls_total",
        {"model_name": "kv-write-pages", "write_path": path})
        for path in ("page_kernel", "row_scatter")}
    assert wrote["page_kernel"] == 0
    # (the last dispatch's row is committed after its tokens are out)
    assert 0 <= wrote["row_scatter"] - sum(
        r["kv_row_scatter"] for r in rows) <= rows[0]["kv_row_scatter"]
    assert engine._prefix_cache.hits >= 3  # pages WERE shared
    assert REGISTRY.get_sample_value(
        "engine_preemptions_total", {"model_name": "kv-write-pages"})
    assert sum(len(lanes) > 1 for lanes, _ in seen) > 3
    for lanes, refs in seen:
        pages = [p for written in lanes.values() for p in written]
        assert len(pages) == len(set(pages)), lanes
        assert 0 not in pages
        assert all(refs[p] == 1 for p in pages), (lanes, refs)
