"""PR 45: what the host builds for a `mixed` dispatch reaches the device in
three packed int32 buffers (engine/shapes.MixedLayout), the dispatch's key is
folded inside the program, and every transfer of a launch's input is counted
(engine_dispatch_uploads_total).  The layout loses no bit, no two pairs of a
grid share a signature, the packed program is its body on the same inputs,
and an engine's dispatches count three uploads each."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prometheus_client import REGISTRY

from conftest import async_test
from kserve_tpu.engine.compiled import program_defs
from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.sampling import (
    COLUMNS as SAMPLER_COLUMNS,
    SamplingParams,
    SamplingState,
    unpacked,
)
from kserve_tpu.engine.shapes import (
    LANE_ROWS,
    PLAN_ROWS,
    TOKEN_ROWS,
    DispatchShapes,
    MixedLayout,
    width_ladder,
)
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.models.llama import LlamaConfig
from kserve_tpu.observability.timeline import DISPATCH_COLUMNS
from test_engine import collect, make_engine

B = 4

#: the lanes' sampling rows of the round trip: a truncating lane, a seeded
#: one at the largest seed an int32 holds, a greedy one, the defaults
LANES = [
    SamplingParams(temperature=0.7, top_p=0.95, top_k=40, min_p=0.05),
    SamplingParams(temperature=1.3, seed=2**31 - 1, repetition_penalty=1.1,
                   frequency_penalty=0.3, presence_penalty=-0.2),
    SamplingParams(temperature=0.0),
    SamplingParams(),
]


def _columns(rng, tokens_used):
    """A dispatch's columns as `_plan_ragged` builds them, made up."""
    sampler, _ = SamplingState.planned(LANES)
    columns = {name: rng.randint(0, 1 << 20, size=B).astype(np.int32)
               for name in PLAN_ROWS}
    columns.update(
        sampler,
        joins=np.array([True, False, True, False]),
        scan_tok0=np.array([-1, 7, -1, 9], np.int32),
        adapters=np.array([-1, 0, -1, 3], np.int32),
        q_tokens=list(rng.randint(0, 500, size=tokens_used)),
        token_seq=list(rng.randint(0, B, size=tokens_used)),
        token_pos=list(rng.randint(0, 90, size=tokens_used)))
    return columns


def test_the_layout_round_trips_bit_for_bit():
    """Floats included: a top_p of 0.95, a seed of 2**31 - 1 and adapters of
    -1 come out of the buffers as they went in, and the rows behind the
    packed slices belong to no lane."""
    layout = MixedLayout(tokens=16, lanes=B, width=8)
    columns = _columns(np.random.RandomState(0), tokens_used=11)
    tokens_buf, lanes_buf = layout.pack(columns, step=12345)
    assert (tokens_buf.shape, lanes_buf.shape, (B, 8)) == layout.shapes
    assert tokens_buf.dtype == lanes_buf.dtype == np.int32
    out = layout.unpack(tokens_buf, lanes_buf)
    assert set(out) == set(TOKEN_ROWS + LANE_ROWS)
    for name in TOKEN_ROWS:
        np.testing.assert_array_equal(out[name][:11], columns[name])
    assert (out["token_seq"][11:] == -1).all()
    assert not out["q_tokens"][11:].any() and not out["token_pos"][11:].any()
    for name in PLAN_ROWS + SAMPLER_COLUMNS:
        assert out[name].dtype == columns[name].dtype, name
        # tobytes: the bits, not values that compare equal
        assert out[name].tobytes() == columns[name].tobytes(), name
    assert out["top_p"][0] == np.float32(0.95)
    assert out["seed"][1] == 2**31 - 1 and out["adapters"][0] == -1
    assert out["step"] == 12345


def test_a_program_reads_the_buffers_as_the_host_wrote_them():
    """The same cut inside a jitted program: slices and bitcasts."""
    layout = MixedLayout(tokens=16, lanes=B, width=8)
    columns = _columns(np.random.RandomState(1), tokens_used=16)
    buffers = layout.pack(columns, step=7)
    on_device = jax.jit(layout.unpack)(*buffers)
    on_host = layout.unpack(*buffers)
    for name, column in on_host.items():
        got = np.asarray(on_device[name])
        assert got.dtype == np.asarray(column).dtype, name
        assert got.tobytes() == np.asarray(column).tobytes(), name
    # the legacy launches' one transfer of the sampler's columns
    state = unpacked(jnp.asarray(SamplingState.packed(
        {name: columns[name] for name in SAMPLER_COLUMNS})))
    for name in SAMPLER_COLUMNS:
        assert np.asarray(getattr(state, name)).tobytes() == (
            columns[name].tobytes()), name


def test_a_buffer_of_another_dispatch_is_refused():
    layout = MixedLayout(tokens=16, lanes=B, width=8)
    buffers = layout.pack(_columns(np.random.RandomState(2), 4), step=1)
    with pytest.raises(ValueError, match="not a mixed dispatch"):
        MixedLayout(tokens=32, lanes=B, width=8).unpack(*buffers)


@pytest.mark.parametrize("lanes", [12, 32, 48])
def test_no_two_pairs_of_a_grid_share_a_signature(lanes):
    """The AOT signature is the arguments' shapes: over every (T, W) a
    dispatch can take they differ, and T and W read off them.  One flat
    buffer would not do: 3 T + B W + k B is one number for two pairs."""
    shapes = DispatchShapes(
        align=8, token_buckets=(128, 256, 512, 1024, 2048, 4096),
        width_buckets=width_ladder(40) + (80, 128, 256), steps=8, lanes=lanes)
    pairs = shapes.pairs()
    layouts = {pair: MixedLayout(pair[0], lanes, pair[1]) for pair in pairs}
    assert len({layout.shapes for layout in layouts.values()}) == len(pairs)
    for (tokens, width), layout in layouts.items():
        tokens_buf, lanes_buf, table = layout.shapes
        assert (tokens_buf[1], lanes_buf[1], table[1]) == (tokens, lanes, width)
        assert table[0] == lanes
    flat = [sum(int(np.prod(shape)) for shape in layout.shapes)
            for layout in layouts.values()]
    if lanes == 48:  # (512, 40) and (1024, 8): 3 x 512 = 48 x 32
        assert len(set(flat)) < len(flat)


def _labelled(label, **overrides):
    """test_engine's tiny engine under a metrics label of its own."""
    model_config = LlamaConfig.tiny(dtype="float32")
    cfg = dict(max_batch_size=4, page_size=8, num_pages=64,
               max_pages_per_seq=8, max_prefill_len=32,
               prefill_buckets=(16, 32), dtype="float32", use_pallas=False)
    cfg.update(overrides)
    return LLMEngine(model_config, EngineConfig(**cfg),
                     ByteTokenizer(model_config.vocab_size),
                     metrics_label=label)


def _spied(engine, seen):
    """`engine._mixed_fn` behind a spy that keeps, for every dispatch, the
    packed arguments (the cache copied: the call donates it) and what the
    program returned."""
    mixed = engine._mixed_fn

    def spy(params, tokens_buf, lanes_buf, kv_pages, page_table, base_rng):
        before = jax.tree.map(jnp.copy, kv_pages)
        out, after = mixed(
            params, tokens_buf, lanes_buf, kv_pages, page_table, base_rng)
        seen.append({
            "args": (np.asarray(tokens_buf), np.asarray(lanes_buf), before,
                     np.asarray(page_table), base_rng),
            "out": np.asarray(out),
            "kv": jax.tree.map(np.asarray, after)})
        return out, after

    engine._mixed_fn = spy


@async_test
async def test_the_packed_program_is_its_body_on_the_same_inputs():
    """A mixed plan on the tiny model (a prompt's chunk beside decode lanes,
    one lane seeded, one truncating): the program that takes the three
    buffers and folds the key itself returns the tokens and the K/V pages
    of its body called with the fifteen arrays and a key folded on the
    host, as the program was called before PR 45."""
    engine = make_engine(steps_per_sync=2, max_prefill_len=16,
                         prefill_buckets=(16,), max_pages_per_seq=16,
                         num_pages=96)
    seen = []
    _spied(engine, seen)
    await engine.start()
    try:
        lanes = [asyncio.create_task(collect(engine, [1, 2, 3], params))
                 for params in (
                     SamplingParams(max_tokens=40, temperature=0.9, seed=11,
                                    ignore_eos=True),
                     SamplingParams(max_tokens=40, temperature=0.8,
                                    top_p=0.9, ignore_eos=True))]
        while len(seen) < 3:  # both lanes are decoding
            await asyncio.sleep(0.01)
        await collect(engine, list(range(5, 45)), SamplingParams(
            max_tokens=4, temperature=1.0, ignore_eos=True))
        await asyncio.gather(*lanes)
    finally:
        await engine.stop()
    body = jax.jit(program_defs(
        engine.model_config, engine.config, engine.mesh)["mixed"][0].body)
    mixed_plans = 0
    for call in seen:
        tokens_buf, lanes_buf, kv_pages, page_table, base_rng = call["args"]
        cols = MixedLayout(
            tokens_buf.shape[1], lanes_buf.shape[1],
            page_table.shape[1]).unpack(tokens_buf, lanes_buf)
        chunk = (cols["q_len"] > 1).any()
        decoding = ((cols["q_len"] == 1) & (cols["step0_emits"] == 1)).sum()
        if not (chunk and decoding >= 2):
            continue
        mixed_plans += 1
        assert (cols["seed"] == 11).any() and (
            cols["top_p"] == np.float32(0.9)).any()
        state = SamplingState(**{n: cols[n] for n in SAMPLER_COLUMNS})
        rng = jax.random.fold_in(base_rng, int(cols["step"]))
        out, kv = body(
            engine.params, *(cols[n] for n in TOKEN_ROWS),
            cols["q_start"], cols["q_len"], cols["kv_start"],
            cols["last_idx"], kv_pages, page_table, cols["joins"],
            cols["scan_tok0"], cols["scan_pos0"], cols["step0_emits"],
            cols["capacity"], cols["counters"], state, rng, cols["adapters"])
        np.testing.assert_array_equal(np.asarray(out), call["out"])
        jax.tree.map(np.testing.assert_array_equal,
                     jax.tree.map(np.asarray, kv), call["kv"])
    assert mixed_plans >= 2


@async_test
async def test_a_dispatch_counts_three_uploads_and_reports_its_parts():
    """Every `mixed` dispatch of an engine's run hands the device three
    arrays, the row and the counter say so, and the parts that used to hold
    the thirty-one transfers are still reported under their names."""
    label = "mixed-layout-uploads"
    engine = _labelled(label)
    uploaded = []
    upload = engine._upload
    engine._upload = lambda array: uploaded.append(array) or upload(array)
    await engine.start()
    params = SamplingParams(max_tokens=12, temperature=0.7, top_p=0.9,
                            ignore_eos=True)
    try:
        await asyncio.gather(collect(engine, list(range(1, 40)), params),
                             collect(engine, [4, 5, 6], params))
    finally:
        await engine.stop()
    snap = engine.telemetry_snapshot()["dispatches"]
    assert snap["columns"] == list(DISPATCH_COLUMNS)
    assert DISPATCH_COLUMNS[-1] == "uploads"  # appended: nothing moved
    rows = [dict(zip(DISPATCH_COLUMNS, row)) for row in snap["rows"]]
    assert len(rows) >= 4 and {r["program"] for r in rows} == {"mixed"}
    assert all(r["uploads"] == 3 for r in rows)
    assert all(r["sampling"] > 0.0 and r["upload"] > 0.0 for r in rows)
    assert len(uploaded) == 3 * len(rows)
    assert all(isinstance(a, np.ndarray) and a.dtype == np.int32
               for a in uploaded)

    def counter(name, **labels):
        return REGISTRY.get_sample_value(name, {"model_name": label, **labels})

    assert counter("engine_dispatch_uploads_total") == 3 * len(rows)
    assert counter("engine_dispatches_total", program="mixed") == len(rows)
    for part in ("sampling", "upload"):
        assert counter("engine_dispatch_part_seconds_total", part=part) == (
            pytest.approx(sum(r[part] for r in rows)))


@pytest.mark.parametrize("regime, most", [
    ({"use_ragged": False}, 8), ({"spec_decode_k": 0}, 8)],
    ids=["legacy", "dense"])
@async_test
async def test_the_other_launches_upload_the_sampler_s_columns_once(
        regime, most):
    """The legacy decode and the dense path build their sampling state where
    they launch, in one transfer; with their own arrays a launch stays
    within eight (an iteration that chains launches books them all on its
    row, as it books their parts)."""
    label = "mixed-layout-" + "-".join(regime)
    engine = _labelled(label, **regime)
    await engine.start()
    try:
        await collect(engine, [4, 5, 6], SamplingParams(
            max_tokens=20, temperature=0.7, top_p=0.9, ignore_eos=True))
    finally:
        await engine.stop()
    rows = [dict(zip(DISPATCH_COLUMNS, row)) for row in
            engine.telemetry_snapshot()["dispatches"]["rows"]]
    other = [r for r in rows if r["program"] != "mixed"]
    assert other and 0 < sum(r["uploads"] for r in other) <= most * len(other)
