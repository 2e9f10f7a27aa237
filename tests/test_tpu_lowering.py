"""Pallas kernels against the TPU toolchain, without a chip.

Two levels, both run on the CPU-only test host:

- LOWERING (`lowering_platforms=("tpu",)`): runs the Pallas -> Mosaic MLIR
  lowering and its verifier.  Catches what the lowering refuses (a
  `tpu.matmul` with two batch dims took three of the four kernels down on
  jax 0.9.0).  PASSING IT IS NOT PROOF OF COMPILATION: Mosaic proper runs
  later, inside the XLA TPU compile.
- COMPILE (libtpu's compile-only v5e topology, when the installation has
  it): runs that XLA TPU compile, Mosaic included.  Catches unsupported
  shape casts, misaligned DMA slices and layout conflicts with the
  surrounding XLA program.  Passing it is not proof of correct numerics or
  of fitting a real chip's run-time memory — `chip_smoke.py` checks the
  kernels against their XLA references on the device.

Shapes are the published head shapes of the presets the server offers.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kserve_tpu.engine import kvcache
from kserve_tpu.ops import attention as att
from kserve_tpu.ops import kv_write
from kserve_tpu.ops import pallas_kv_write as kw
from kserve_tpu.ops import pallas_paged_attention as pk

# the suite's persistent compile cache stores these TPU executables too, and
# a compile-only client cannot load them back: jax warns and recompiles
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")

#: (n_q_heads, n_kv_heads, head_dim) a kernel sees on ONE device
HEAD_SHAPES = {
    "qwen3-0.6b": (16, 8, 128),
    "llama3-8b": (32, 8, 128),
    "llama3-8b/tp4": (8, 2, 128),  # one tp=4 shard
    "gemma2-2b": (8, 4, 256),
}
PAGE_SIZES = (8, 16)
B, W, NUM_PAGES, T = 8, 64, 128, 64


@functools.lru_cache(maxsize=None)
def _tpu_sharding():
    """A one-device sharding on libtpu's compile-only v5e topology, or None
    where the installation cannot describe one."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except (RuntimeError, ImportError, ValueError):
        # no libtpu, or one that cannot describe a topology without a chip
        return None
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def _abstract(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_tpu_sharding())


def _check(fn, *args, compiles=True):
    """Lower `fn` for TPU; then, where a compile-only topology exists,
    compile it — or assert Mosaic still refuses it (`compiles=False`)."""
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    if _tpu_sharding() is None:
        return
    lowered = jax.jit(fn).lower(*args)
    if compiles:
        lowered.compile()
    else:
        with pytest.raises(Exception):  # noqa: B017 — a MosaicError
            lowered.compile()


def _cache(nkv, ps, d, dtype=jnp.bfloat16):
    return _abstract((NUM_PAGES, 2, nkv, ps, d), dtype)


def _i32(*shape):
    return _abstract(shape, jnp.int32)


@pytest.mark.parametrize("ps", PAGE_SIZES)
@pytest.mark.parametrize("model", sorted(HEAD_SHAPES))
class TestKernelsLowerAndCompileForTpu:
    def test_decode_kernel(self, model, ps):
        """_decode_kernel."""
        nq, nkv, d = HEAD_SHAPES[model]
        _check(pk.paged_attention_pallas,
               _abstract((B, nq, d), jnp.bfloat16), _cache(nkv, ps, d),
               _i32(B, W), _i32(B))

    def test_ragged_kernel(self, model, ps):
        """_ragged_kernel, full attention and windowed."""
        nq, nkv, d = HEAD_SHAPES[model]
        if d % 128:
            pytest.skip("ragged kernel needs head_dim % 128 == 0")
        args = (_abstract((T, nq, d), jnp.bfloat16), _cache(nkv, ps, d),
                _i32(B, W), _i32(B), _i32(B), _i32(B))
        _check(pk.ragged_paged_attention_pallas, *args)
        _check(
            lambda *a: pk.ragged_paged_attention_pallas(
                *a[:-1], window=a[-1], logit_softcap=50.0, scale=0.0625),
            *args, _i32())

    @pytest.mark.parametrize("stride", [1, 2, 4])
    def test_dense_ragged_kernel(self, model, ps, stride):
        """_dense_ragged_kernel at every dense stride below RAGGED_BQ."""
        nq, nkv, d = HEAD_SHAPES[model]
        if d % 128:
            pytest.skip("ragged kernel needs head_dim % 128 == 0")
        _check(
            functools.partial(
                pk.ragged_paged_attention_pallas, dense_stride=stride),
            _abstract((B * stride, nq, d), jnp.bfloat16),
            _cache(nkv, ps, d), _i32(B, W), _i32(B), _i32(B), _i32(B))

    def test_int8_pages_lower_but_do_not_compile(self, model, ps):
        """The int8 variant LOWERS — and is the example of why that proves
        nothing: Mosaic refuses the per-page scale DMA (a [2, nkv, ps] f32
        slice whose minor dim is below the 128-lane tiling), which is why
        auto-dispatch keeps int8 caches off the kernel.  When this starts
        compiling, reopen `_should_use_ragged_pallas`."""
        nq, nkv, d = HEAD_SHAPES[model]
        if d % 128:
            pytest.skip("ragged kernel needs head_dim % 128 == 0")
        _check(
            lambda q, pages, scales, *rest: pk.ragged_paged_attention_pallas(
                q, (pages, scales), *rest),
            _abstract((T, nq, d), jnp.bfloat16),
            _cache(nkv, ps, d, jnp.int8),
            _abstract((NUM_PAGES, 2, nkv, ps), jnp.float32),
            _i32(B, W), _i32(B), _i32(B), _i32(B),
            compiles=False)
        assert not att._should_use_ragged_pallas(d, "tpu", quantized=True)


class TestCacheKeepsKernelLayout:
    def test_kv_write_then_kernel_copies_no_cache(self):
        """The KV write and the kernel's page DMAs must agree on the
        cache's layout.  A scatter indexed by (page, slot) with a
        [2, nkv, d] window made XLA re-lay the WHOLE cache out around
        every layer's write (12 GiB of temporaries for `mixed` at
        4096 pages x 28 layers — it did not fit the chip); the row scatter
        in kv_write._scatter_kv leaves the layout alone."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology in this installation")
        nq, nkv, d = HEAD_SHAPES["qwen3-0.6b"]
        ps = 16
        # deployment-sized (abstract: nothing is allocated) — XLA only
        # re-laid the cache out once it was large
        cache = _abstract((4096, 2, nkv, ps, d), jnp.bfloat16)

        def write_then_attend(pages, q, k, v, table, seq, pos, start, n):
            pages = kv_write.write_ragged_kv(
                pages, k, v, table, seq, pos, ps)
            return pages, pk.ragged_paged_attention_pallas(
                q, pages, table, start, n, start)

        compiled = jax.jit(write_then_attend, donate_argnums=(0,)).lower(
            cache, _abstract((T, nq, d), jnp.bfloat16),
            _abstract((T, nkv, d), jnp.bfloat16),
            _abstract((T, nkv, d), jnp.bfloat16),
            _i32(B, W), _i32(T), _i32(T), _i32(B), _i32(B)).compile()
        cache_bytes = int(np.prod(cache.shape)) * 2
        assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 4


def _compile_mixed(mc, cfg, cache_shape, width, monkeypatch,
                   page_write=True):
    """The `mixed` program of `mc` under `cfg` COMPILED for the described
    v5e (abstract arguments placed on its first device), with the K/V page
    write or, `page_write` False, as the program was before it: the row
    scatter."""
    from kserve_tpu.engine.compiled import program_defs
    from kserve_tpu.engine.shapes import MixedLayout
    from kserve_tpu.models import llama
    from kserve_tpu.parallel import sharding as shd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not page_write:
        monkeypatch.setattr(
            att, "_should_use_page_write", lambda *a, **kw: False)
    lanes, tokens = cfg.max_batch_size, cfg.prefill_buckets[-1]
    device = _tpu_sharding().mesh.devices.ravel()[0]
    fn, donate = program_defs(
        mc, cfg, shd.create_mesh(devices=[device]))["mixed"]

    def placed(tree):
        return jax.tree.map(lambda x: _abstract(x.shape, x.dtype), tree)

    # the three packed buffers of a dispatch (shapes.MixedLayout)
    tokens_buf, lanes_buf, page_table = (
        _i32(*shape) for shape in MixedLayout(tokens, lanes, width).shapes)
    return jax.jit(fn, donate_argnums=donate).lower(
        placed(jax.eval_shape(
            lambda: llama.init_params(mc, jax.random.PRNGKey(1)))),
        tokens_buf, lanes_buf,
        [_abstract(cache_shape, jnp.bfloat16)] * mc.n_layers,
        page_table, _abstract((2,), jnp.uint32),  # the base key
    ).compile()


def _two_layer_cell(name):
    """(model, engine config, one layer's cache shape, table width) of a
    cell of the benchmark: its lanes, tokens, pages and head shapes; two
    layers and a narrow MLP, which only have to compile."""
    import dataclasses

    from kserve_tpu.engine.types import EngineConfig
    from kserve_tpu.models import llama

    if name == "qwen3-4b.decode-sat":
        lanes, tokens, pages, width, passes = 48, 512, 2300, 40, 1
        mc = dataclasses.replace(
            llama.LlamaConfig.qwen3_0_6b(), n_layers=2, n_heads=32,
            hidden_size=256, intermediate_size=512, vocab_size=1024,
            dtype="bfloat16")
    else:
        lanes, tokens, pages, width, passes = 12, 256, 300, 24, 4
        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config({
                "model_type": "ouro", "vocab_size": 1024, "hidden_size": 256,
                "intermediate_size": 512, "num_hidden_layers": 2,
                "num_attention_heads": 16, "num_key_value_heads": 16,
                "head_dim": 128, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                "total_ut_steps": passes, "early_exit_threshold": 1}),
            dtype="bfloat16")
    cfg = EngineConfig(
        max_batch_size=lanes, page_size=16, num_pages=pages,
        max_pages_per_seq=width, max_prefill_len=tokens,
        prefill_buckets=(128, tokens), dtype="bfloat16")
    return mc, cfg, (passes * pages, 2, mc.n_kv_heads, 16, mc.head_dim), width


class TestMixedWritesPagesInPlace:
    """`mixed` with the K/V page write (ops/pallas_kv_write.py), compiled
    for the described v5e at two cells' shapes: the kernel's aliasing holds
    through both layers, the packed step, the decode steps' scan and a
    looped model's passes."""

    @pytest.mark.parametrize("cell", ["qwen3-4b.decode-sat",
                                      "ouro-2.6b.eval-sat"])
    def test_no_scatter_no_copy_no_more_temporaries(self, cell, monkeypatch):
        import re

        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology in this installation")
        mc, cfg, shape, width = _two_layer_cell(cell)
        with monkeypatch.context() as before:
            scattered = _compile_mixed(
                mc, cfg, shape, width, before, page_write=False)
        compiled = _compile_mixed(mc, cfg, shape, width, monkeypatch)
        cache = "bf16\\[" + ",".join(map(str, shape)) + "\\]"

        def count(program, op):
            return len(re.findall(cache + r"\S* " + op + r"\(",
                                  program.as_text()))

        # the check sees the scatter it is there to miss: one a layer in
        # the packed step's fusion, one in the decode steps'
        assert count(scattered, "scatter") == 2
        assert count(compiled, "scatter") == 0
        # one call a layer and form (a looped model's passes are a loop
        # around them), each aliased onto its operand
        calls = re.findall(
            r"custom-call\(.*custom_call_target=\"tpu_custom_call\".*"
            r"kv_page_write", compiled.as_text())
        assert len(calls) == 4
        # no layer's cache is copied, and the program holds no more beside
        # the cache than it did (the new rows, padded: well under 1 MB)
        assert count(compiled, "copy") == count(scattered, "copy") == 0
        was = scattered.memory_analysis().temp_size_in_bytes
        now = compiled.memory_analysis().temp_size_in_bytes
        assert now < was + (1 << 20), (was, now)
        cache_bytes = int(np.prod(shape)) * 2 * mc.n_layers
        assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


class TestDispatchReport:
    def _report(self, model_config, backend="tpu", **cfg):
        from kserve_tpu.engine.types import EngineConfig

        return att.describe_attention_dispatch(
            model_config, EngineConfig(**cfg), backend)

    def test_reports_what_dispatch_selects(self):
        from kserve_tpu.models.llama import LlamaConfig

        qwen = LlamaConfig.qwen3_0_6b()
        on_tpu = self._report(qwen)
        assert on_tpu["mixed"] == "pallas_ragged"
        assert on_tpu["decode"] == "pallas_decode"
        # 8 KV heads x 128 x 16 tokens = 64 KB pages: the kernel at every
        # table width (measured on the chip, docs/kernels.md)
        assert on_tpu["decode_pallas_min_pages"] is None
        on_cpu = self._report(qwen, backend="cpu")
        assert (on_cpu["mixed"], on_cpu["decode"]) == (
            "xla_ragged_gather", "xla_gather")
        # head_dim 64: rows narrower than a 128-lane tile take the gather
        d64 = self._report(LlamaConfig.llama3_1b(), max_batch_size=48)
        assert (d64["mixed"], d64["decode"], d64["decode_pallas_min_pages"]
                ) == ("xla_ragged_gather", "xla_gather", None)
        # int8 pages, windows and scale overrides keep decode on the gather
        assert self._report(qwen, kv_quant="int8")["mixed"] == (
            "xla_ragged_gather")
        gemma = self._report(LlamaConfig.gemma2_2b())
        assert (gemma["mixed"], gemma["decode"]) == (
            "pallas_ragged", "xla_gather")
        # the gate reads ONE device's pages: 2 local KV heads (16 KB) keep
        # the measured crossover, 1 (8 KB) keeps the gather at every width
        tp4 = self._report(qwen, tp=4)
        assert tp4["shard_map"]
        assert (tp4["decode"], tp4["decode_pallas_min_pages"]) == (
            "pallas_decode", 64)
        tp8 = self._report(qwen, tp=8)
        assert (tp8["decode"], tp8["decode_pallas_min_pages"]) == (
            "xla_gather", None)
        # a forced path reports no gate
        assert self._report(qwen, use_pallas=True)[
            "decode_pallas_min_pages"] is None

    @pytest.mark.parametrize("kv_heads, page_size, expected", [
        (8, 16, 0), (16, 16, 0), (4, 16, 0), (8, 8, 0),
        (2, 16, 64), (4, 8, 64), (1, 16, None),
    ])
    def test_gate_follows_the_page_dma_size(self, kv_heads, page_size,
                                            expected):
        """`pallas_min_pages` is a function of the bytes of one K+V page
        (the rows of docs/kernels.md's table)."""
        assert att.pallas_min_pages(128, kv_heads, page_size) == expected


def _crossover_rows():
    import json
    import pathlib

    path = pathlib.Path(__file__).parent.parent / (
        "docs/data/decode_attention_crossover.v5e.json")
    rows = {}
    for row in json.loads(path.read_text())["rows"]:
        rows.setdefault(row["family"], []).append(row)
    return rows


CROSSOVER = _crossover_rows()


@pytest.mark.parametrize("family", sorted(CROSSOVER))
class TestGateEqualsTheMeasuredTable:
    """The auto-dispatch gate against the per-call times measured on the
    v5e (docs/kernels.md "Kernel against gather"): a path WINS a row when
    it is more than 3 % ahead under both length distributions."""

    @staticmethod
    def _verdict(row):
        ratios = [row[f"{k}.gather_us"] / row[f"{k}.kernel_us"]
                  for k in ("aged", "full")]
        auto = att._should_use_pallas(
            row["d"], False, row["width"], row["lanes"], "tpu", 16,
            row["nkv"])
        return auto, min(ratios) > 1.03, max(ratios) < 0.97

    def test_never_takes_the_measured_loser(self, family):
        for row in CROSSOVER[family]:
            auto, kernel_wins, gather_wins = self._verdict(row)
            assert not (auto and gather_wins), row
            # the two paths' bf16 outputs, compared on the chip
            assert max(row["aged.max_abs_diff"],
                       row["full.max_abs_diff"]) < 2e-2, row
            if row["aged.gather_spread"] < 0.05:
                # the kernel's wins are all taken
                assert auto or not kernel_wins, row


#: the widths `qwen3-4b.decode-sat` compiles (max_model_len 640 = 40 pages)
DECODE_SAT_WIDTHS = (8, 16, 32, 40)


def _lower_mixed(mc, cfg, cache, width, monkeypatch):
    """The `mixed` program of `mc` under `cfg`, lowered for TPU from
    abstract arguments (`cache`: the program's K/V argument)."""
    from kserve_tpu.engine.compiled import program_defs
    from kserve_tpu.engine.shapes import MixedLayout
    from kserve_tpu.models import llama
    from kserve_tpu.parallel import sharding as shd

    # code that asks the backend takes its TPU branch, as on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lanes, tokens = cfg.max_batch_size, cfg.prefill_buckets[-1]
    fn, donate = program_defs(mc, cfg, shd.create_mesh())["mixed"]

    # the three packed buffers of a dispatch (shapes.MixedLayout)
    tokens_buf, lanes_buf, page_table = (
        jax.ShapeDtypeStruct(shape, jnp.int32)
        for shape in MixedLayout(tokens, lanes, width).shapes)
    return jax.jit(fn, donate_argnums=donate).trace(
        jax.eval_shape(
            lambda: llama.init_params(mc, jax.random.PRNGKey(1))),
        tokens_buf, lanes_buf, cache, page_table,
        jax.ShapeDtypeStruct((2,), jnp.uint32),  # the base key
    ).lower(lowering_platforms=("tpu",))


def _decode_sat_cell():
    """(model, engine config) at the decode-sat cell's shape (48 lanes, T =
    512, Qwen3-4B's 32/8 x 128 heads, 16-token pages).  Two layers and a
    narrow MLP: the attention shapes are the cell's, the rest only has to
    lower."""
    import dataclasses

    from kserve_tpu.engine.types import EngineConfig
    from kserve_tpu.models import llama

    lanes, tokens, ps = 48, 512, 16
    mc = dataclasses.replace(
        llama.LlamaConfig.qwen3_0_6b(), n_layers=2, n_heads=32,
        hidden_size=256, intermediate_size=512, vocab_size=1024,
        dtype="bfloat16")
    cfg = EngineConfig(
        max_batch_size=lanes, page_size=ps, num_pages=2300,
        max_pages_per_seq=40, max_prefill_len=tokens,
        prefill_buckets=(128, tokens), dtype="bfloat16")
    return mc, cfg


def _decode_sat_mixed(width, monkeypatch):
    """`mixed` of `_decode_sat_cell`, lowered for TPU."""
    mc, cfg = _decode_sat_cell()
    cache = jax.ShapeDtypeStruct(
        (cfg.num_pages, 2, mc.n_kv_heads, cfg.page_size, mc.head_dim),
        jnp.bfloat16)
    return _lower_mixed(mc, cfg, [cache] * mc.n_layers, width, monkeypatch)


def _entry_calls(text: str) -> dict:
    """Call sites in a lowered program, by the kernels' entry points
    (ops/pallas_*.py: jitted functions, so a program holds each once and
    its layers call it)."""
    import collections
    import re

    entries = [name for module in (pk, kw) for name, fn in vars(module).items()
               if hasattr(fn, "clear_cache")]
    calls = collections.Counter(re.findall(
        r"call @(%s)(?:_\d+)?\(" % "|".join(entries), text))
    defined = collections.Counter(re.findall(
        r"func\.func private @(%s)(?:_\d+)?\(" % "|".join(entries), text))
    assert all(defined[name] == 1 for name in calls), defined
    return dict(calls)


#: call sites of the kernels' entry points in a two-layer `mixed` program
#: whose packed step splits (PR 46): each layer's packed step calls the
#: split, whose one lowering calls the ragged kernel once and the decode
#: kernel once; the decode steps' two layers call that same decode kernel
PACKED_SPLIT_CALLS = {
    "ragged_single_token_split_pallas": 2, "ragged_paged_attention_pallas": 1,
    "paged_attention_pallas": 3, "append_rows": 2, "write_runs": 2}


class TestMixedProgramTakesTheDecodeKernel:
    @pytest.mark.parametrize("width", DECODE_SAT_WIDTHS)
    def test_mixed_lowers_with_decode_kernel_and_no_gather(
            self, width, monkeypatch):
        """Its scan tail calls the decode kernel and nothing gathers a
        [48, W, .., 16, 128] copy of the lanes' pages (such a gather in
        every layer of every step was 40 % of the cell's device time,
        PERF.md section 6)."""
        import re

        text = _decode_sat_mixed(width, monkeypatch).as_text()
        kernels = set(re.findall(r'kernel_name = "([a-z_]+)"', text))
        assert {"paged_attention_decode", "ragged_paged_attention"} <= kernels
        gathered = re.findall(
            rf"tensor<48x{width}x[0-9x]*16x128xbf16>", text)
        assert not gathered, sorted(set(gathered))

    def test_the_kernel_s_entry_sorts_its_lanes_around_each_call(
            self, monkeypatch):
        """The decode kernel walks a block of lanes to its longest, so its
        entry point hands it the lanes in order of length and puts its rows
        back (PR 42, `_by_length`): a layer's call gathers the page table,
        the queries and the attention's rows by lane, and nothing else of
        the step moves (the hidden state stays in lane order)."""
        import re

        text = _decode_sat_mixed(40, monkeypatch).as_text()
        gathers = re.findall(r'"stablehlo.gather"\(.*?-> (tensor<[^>]+>)', text)
        # once in the program's text: the entry point is a jitted function
        # (PR 45), lowered once and called by both layers
        assert gathers.count("tensor<48x40xi32>") == 1, gathers
        # the queries in, the rows back; and the packed step's one query a
        # lane out of its buffer (PR 46)
        assert gathers.count("tensor<48x32x128xbf16>") == 3, gathers
        assert "tensor<48x1x256xbf16>" not in gathers, gathers
        assert _entry_calls(text) == PACKED_SPLIT_CALLS

    @pytest.mark.parametrize("width", DECODE_SAT_WIDTHS)
    def test_the_packed_step_hands_its_single_token_lanes_to_that_kernel(
            self, width, monkeypatch):
        """PR 46: at every width the cell compiles, each layer's packed
        step calls the split, which calls the ragged kernel and the SAME
        lowered decode kernel the scan's layers call (the program holds it
        once: no custom call more than before), and puts the lanes' rows
        back with one scatter of 48 rows: no sort, no gather of pages."""
        import re

        text = _decode_sat_mixed(width, monkeypatch).as_text()
        assert _entry_calls(text) == PACKED_SPLIT_CALLS
        kernels = re.findall(r'kernel_name = "([a-z_]+)"', text)
        assert kernels.count("paged_attention_decode") == 1
        assert kernels.count("ragged_paged_attention") == 1
        split = text[text.index(
            "func.func private @ragged_single_token_split_pallas"):]
        split = split[:split.index("\n  }\n") + 1]
        assert split.count('"stablehlo.scatter"(') == 1
        assert re.search(
            r"tensor<48x32x128xbf16>\) -> tensor<512x32x128xbf16>", split)
        assert "stablehlo.sort" not in split
        assert att.describe_attention_dispatch(
            *_decode_sat_cell(), "tpu")["packed_single_token_min_pages"] == 0


def _sorts_met_without_a_branch(hlo: str):
    """(sorts in all, computations that sort and are reached from the entry
    without passing a conditional's branch), from HLO text."""
    import re

    bodies, entry, name = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            bodies[name] = []
            entry = name if head.group(1) else entry
        elif name is not None:
            bodies[name].append(line)
    sorts = {n for n, body in bodies.items()
             if any(re.search(r"\ssort\(", ln) for ln in body)}
    reached, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in reached:
            continue
        reached.add(comp)
        for ln in bodies[comp]:
            # every callee but a conditional's branches
            ln = re.sub(r"(branch_computations=\{[^}]*\}|"
                        r"(true|false)_computation=%?[\w.\-]+)", "", ln)
            todo += re.findall(
                r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)", ln)
    n_sorts = sum(len(re.findall(r"\ssort\(", ln))
                  for body in bodies.values() for ln in body)
    return n_sorts, sorted(sorts & reached)


class TestSamplerSortsNothing:
    """engine/sampling.sample_tokens truncates in one branch of a
    conditional on the batch's own sampling state, and since PR 38 that
    branch finds its cutoffs by threshold searches (loops of
    compare-and-reduce): no program sorts for the sampler any more (the
    sorts were 47-53 % of every cell's device time before PR 31, and still
    a third of the truncating cells' before PR 38: PERF.md section 6)."""

    def _hybrid_mixed(self, monkeypatch):
        import dataclasses

        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama
        from test_hybrid_model import CFG

        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config(CFG), dtype="float32")
        cfg = EngineConfig(
            max_batch_size=4, page_size=4, num_pages=64, max_pages_per_seq=16,
            max_prefill_len=16, prefill_buckets=(16,), dtype="float32")
        layout = kvcache.StateLayout.of(mc, 4, cfg.num_pages, 4, "float32")
        return _lower_mixed(
            mc, cfg, jax.eval_shape(layout.init_state), 16, monkeypatch)

    @pytest.mark.parametrize("family", ["llama", "hybrid", "looped"])
    def test_mixed_sorts_nothing_and_branches_once_a_sampler_site(
            self, family, monkeypatch):
        lowered = {
            "llama": lambda: _decode_sat_mixed(40, monkeypatch),
            "hybrid": lambda: self._hybrid_mixed(monkeypatch),
            "looped": lambda: TestLoopedMixedProgram()._lowered(
                monkeypatch, 4),
        }[family]()
        hlo = lowered.compiler_ir(dialect="hlo").as_hlo_text()
        assert _sorts_met_without_a_branch(hlo) == (0, [])
        # step 0's sampler and the scan tail's: one conditional each
        assert hlo.count(" conditional(") == 2

    @pytest.mark.parametrize("outside", [True, False])
    def test_the_check_sees_a_sort_outside_a_branch(self, outside):
        def fn(x):
            inside = jax.lax.cond(x[0] > 0, jnp.sort, lambda y: y, x)
            return inside + jnp.sort(x) if outside else inside

        hlo = jax.jit(fn).lower(jnp.zeros((8,))).compiler_ir(
            dialect="hlo").as_hlo_text()
        n_sorts, unconditional = _sorts_met_without_a_branch(hlo)
        assert n_sorts >= 1 and bool(unconditional) == outside

    def test_the_tpu_compiler_keeps_the_conditional_and_the_loops(self):
        """The sampler at Qwen3-4B's 48 x 151936 logits inside a scan, as
        `mixed` calls it, through the XLA TPU compile: the optimised
        program still holds ONE conditional of two branches (a conditional
        flattened to both sides and a select would run the searches for
        every batch), sorts nowhere, and keeps the two searches as loops
        (32 passes unrolled twice a sampler site would be paid in set-up
        time by every (T, W) pair)."""
        import re

        from kserve_tpu.engine.sampling import (
            SamplingState, sample_tokens, sampler_top_k, sampler_truncates)

        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology in this installation")
        lanes, vocab = 48, 151936
        state = jax.tree.map(
            lambda a: _abstract(a.shape, a.dtype),
            jax.eval_shape(lambda: SamplingState.defaults(lanes)))

        def steps(logits, state, rng, counters):
            truncates, top_k = sampler_truncates(state), sampler_top_k(state)

            def body(carry, step_rng):
                logits, counters = carry
                out = sample_tokens(
                    logits, state, step_rng, counters, truncates, top_k)
                logits = logits.at[jnp.arange(lanes), out].add(-1.0)
                return (logits, counters + 1), out

            return jax.lax.scan(
                body, (logits, counters), jax.random.split(rng, 8))[1]

        hlo = jax.jit(steps).lower(
            _abstract((lanes, vocab), jnp.float32), state,
            _abstract((2,), jnp.uint32), _i32(lanes)).compile().as_text()
        assert _sorts_met_without_a_branch(hlo) == (0, [])
        branches = re.findall(r"branch_computations=\{([^}]*)\}", hlo)
        assert len(branches) == 1 and branches[0].count(",") == 1
        # the steps, top-k's search and the nucleus's
        assert len(re.findall(r"\swhile\(", hlo)) == 3


class TestHybridDecodeKernelCalls:
    """models/hybrid.py reads both of its caches with the decode kernel, at
    rows of a differential pair's two heads side by side (10 rows of 128
    for Phi-4-mini-flash's 20 K/V heads of 64), with the score scale of the
    64-wide heads stated and the call named by the layer's kind: the names
    are what a trace tells window and shared-cache attention apart by."""

    @pytest.mark.parametrize("width, pages, name", [
        (32, 1 + 48 * 32, "window_attention_decode"),  # a lane's ring
        (8, 50000, "shared_kv_attention_decode"),
        (64, 50000, "shared_kv_attention_decode"),
    ])
    def test_kernel_compiles_at_the_published_widths(self, width, pages, name):
        fn = functools.partial(
            pk.paged_attention_pallas, scale=64 ** -0.5, name=name)
        args = (_abstract((48, 40, 128), jnp.bfloat16),
                _abstract((pages, 2, 10, 16, 128), jnp.bfloat16),
                _i32(48, width), _i32(48))
        _check(fn, *args)
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert name in text and "paged_attention_decode" not in text.replace(
            name, "")

    def test_gate_takes_the_kernel_at_every_width_of_the_cell(self):
        """80 KB pages (10 rows x 16 tokens x 128, K and V): the kernel at
        every width, the ring's 32 pages included."""
        assert att.pallas_min_pages(128, 10, 16) == 0
        for width in (8, 16, 32, 64):
            assert att._should_use_pallas(128, False, width, 48, "tpu", 16, 10)

    def test_a_head_narrower_than_128_lanes_is_refused_by_the_kernel(self):
        with pytest.raises(ValueError, match="head_dim % 128 == 0"):
            pk.paged_attention_pallas(
                jnp.zeros((8, 4, 64), jnp.bfloat16),
                jnp.zeros((8, 2, 2, 16, 64), jnp.bfloat16),
                jnp.zeros((8, 4), jnp.int32), jnp.zeros((8,), jnp.int32))


class TestLoopedMixedProgram:
    """A looped model's `mixed` program at the `ouro-2.6b.eval-sat` cell's
    shape (12 lanes, 16/16 heads x 128, 300 pages of 16 tokens a pass, 24-page
    tables; two layers and a narrow MLP: the loop and the attention shapes
    are the cell's, the rest only has to lower): the passes are loops IN the
    program (the packed step's, the decode steps', and the decode steps'
    own), every layer's kernels are traced once and not once a pass, and the
    gate keeps the kernel at every width of the cell."""

    def _lowered(self, monkeypatch, passes):
        import dataclasses

        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama

        lanes, ps, pages = 12, 16, 300
        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config({
                "model_type": "ouro", "vocab_size": 1024, "hidden_size": 256,
                "intermediate_size": 512, "num_hidden_layers": 2,
                "num_attention_heads": 16, "num_key_value_heads": 16,
                "head_dim": 128, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                "total_ut_steps": passes, "early_exit_threshold": 1}),
            dtype="bfloat16")
        cfg = EngineConfig(
            max_batch_size=lanes, page_size=ps, num_pages=pages,
            max_pages_per_seq=24, max_prefill_len=256,
            prefill_buckets=(128, 256), dtype="bfloat16")
        cache = jax.ShapeDtypeStruct(
            (passes * pages, 2, mc.n_kv_heads, ps, mc.head_dim), jnp.bfloat16)
        return _lower_mixed(mc, cfg, [cache] * mc.n_layers, 24, monkeypatch)

    def test_the_passes_are_loops_in_the_program(self, monkeypatch):
        import re

        looped = self._lowered(monkeypatch, 4).as_text()
        one = self._lowered(monkeypatch, 1).as_text()
        kernels = re.findall(r'kernel_name = "([a-z_]+)"', looped)
        # 2 layers, whatever the passes: every kernel's entry point is
        # lowered ONCE a program (PR 45: the page write once in each form)
        # and called by each layer under each loop
        assert sorted(kernels) == sorted(
            re.findall(r'kernel_name = "([a-z_]+)"', one)) == [
                "kv_page_write"] * 2 + ["paged_attention_decode"] + [
                "ragged_paged_attention"]
        assert _entry_calls(looped) == _entry_calls(one) == PACKED_SPLIT_CALLS
        # beside the sampler's two searches at each of its two sites:
        searches = 4
        assert looped.count("stablehlo.while") == 3 + searches  # passes, steps, passes
        assert one.count("stablehlo.while") == 1 + searches  # the decode steps alone
        # the cache arrays enter and leave whole: a pass is an offset into
        # the page table, never a slice of the cache
        assert not re.findall(r"tensor<300x2x16x16x128xbf16>", looped)

    def test_gate_takes_the_kernel_at_every_width_of_the_cell(self):
        """128 KB pages (16 rows x 16 tokens x 128, K and V) at 12 lanes."""
        assert att.pallas_min_pages(128, 16, 16) == 0
        for width in (8, 16, 24):
            assert att._should_use_pallas(128, False, width, 12, "tpu", 16, 16)


class TestLatentPagesAndGroupedExperts:
    """`model_type: glm4_moe_lite` (PR 37): both reads of the latent pages
    are kernels that compile for the chip at the published widths (20 query
    heads over a row of 576 values stored in 640 columns, the value its
    first 512), whatever the page size; the `mixed` program of such a model
    calls them and holds nothing of [tokens, experts, width]."""

    @pytest.mark.parametrize("ps", [16, 64, 128])
    def test_kernels_compile_at_the_published_widths(self, ps):
        pages = _abstract((294400 // ps, 1, 1, ps, 640), jnp.bfloat16)
        width = 3200 // ps
        decode = functools.partial(
            pk.latent_attention_decode_pallas, scale=1 / 16, value_dim=512)
        _check(decode, _abstract((48, 20, 640), jnp.bfloat16), pages,
               _i32(48, width), _i32(48))
        ragged = functools.partial(
            pk.latent_attention_ragged_pallas, scale=1 / 16, value_dim=512)
        args = (_abstract((2048, 20, 640), jnp.bfloat16), pages,
                _i32(48, width), _i32(48), _i32(48), _i32(48))
        _check(ragged, *args)
        text = jax.jit(ragged).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "latent_attention_ragged" in text
        assert "tensor<2048x20x512xbf16>" in text  # the value's width out

    def test_mixed_calls_the_latent_kernels_and_groups_its_experts(
            self, monkeypatch):
        import dataclasses
        import re

        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama
        from test_glm_model import CFG

        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config(
                dict(CFG, hidden_size=128, kv_lora_rank=96, qk_rope_head_dim=32,
                     n_routed_experts=16, moe_intermediate_size=80)),
            dtype="bfloat16")
        cfg = EngineConfig(
            max_batch_size=8, page_size=16, num_pages=64, max_pages_per_seq=16,
            max_prefill_len=128, prefill_buckets=(128,), dtype="bfloat16")
        layout = kvcache.StateLayout.of(mc, 16, cfg.num_pages, 8, "bfloat16")
        assert layout.latent_row == 128
        text = _lower_mixed(
            mc, cfg, jax.eval_shape(layout.init_state), 16,
            monkeypatch).as_text()
        kernels = re.findall(r'kernel_name = "([a-z_]+)"', text)
        # 3 layers call the packed step's kernel and the decode steps', each
        # lowered once (PR 45)
        assert sorted(kernels) == [
            "latent_attention_decode", "latent_attention_ragged"]
        assert _entry_calls(text) == {
            "latent_attention_decode_pallas": 3,
            "latent_attention_ragged_pallas": 3}
        # 2 expert layers x (packed step + decode steps) x gate, up, down
        assert len(re.findall(r"ragged_dot", text)) >= 12
        # nothing over (tokens or pairs, experts, an expert's width)
        assert not re.findall(r"tensor<(?:128|256|8|16)x16x80x", text)
        # the dispatch's slices are packed at the kernel's block
        from kserve_tpu.engine.shapes import DispatchShapes
        assert DispatchShapes.of(mc, cfg, "tpu").align == pk.RAGGED_BQ
        report = att.describe_attention_dispatch(mc, cfg, "tpu")
        assert (report["mixed"], report["decode"]) == (
            "pallas_latent_ragged", "pallas_latent_decode")


class TestNemotronH:
    """`model_type: nemotron_h` (PR 41): the mixed program groups its held
    experts and reads its two attention layers through the kernels the
    Llama path uses; the routed experts' width is stored where the grouped
    matmul wants it."""

    def test_mixed_groups_the_held_experts_and_calls_the_paged_kernels(
            self, monkeypatch):
        import dataclasses
        import re

        from kserve_tpu.engine.shapes import DispatchShapes
        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama
        from test_nemotron_model import CFG

        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config(
                dict(CFG, hidden_size=128, head_dim=128, num_attention_heads=4,
                     n_routed_experts=8, router_n_experts=16,
                     moe_intermediate_size=520)),
            dtype="bfloat16")
        cfg = EngineConfig(
            max_batch_size=8, page_size=16, num_pages=64, max_pages_per_seq=16,
            max_prefill_len=128, prefill_buckets=(128,), dtype="bfloat16")
        layout = kvcache.StateLayout.of(mc, 16, cfg.num_pages, 8, "bfloat16")
        text = _lower_mixed(
            mc, cfg, jax.eval_shape(layout.init_state), 16,
            monkeypatch).as_text()
        kernels = re.findall(r'kernel_name = "([a-z_]+)"', text)
        # ONE attention layer: the ragged kernel in the packed step, the
        # page write in both steps (the decode gather under this width)
        assert "ragged_paged_attention" in " ".join(kernels), kernels
        # 2 expert layers x (packed step + decode steps) x up, down: no gate
        assert len(re.findall(r"ragged_dot", text)) >= 8
        # the router's 16 outputs, the 8 held experts' tensors of width 520
        # at 1024 columns (models/moe.stored_width)
        assert "tensor<8x128x1024xbf16>" in text
        assert not re.findall(r"tensor<16x128x(?:520|1024)xbf16>", text)
        # nothing over (tokens, heads, head_dim, state) and no state a block
        assert not re.findall(r"tensor<128x8x8x16x", text)
        assert not re.findall(r"tensor<16x8x8x16xf32>", text)  # 128 / 8 blocks
        assert DispatchShapes.of(mc, cfg, "tpu").align == pk.RAGGED_BQ
        report = att.describe_attention_dispatch(mc, cfg, "tpu")
        assert report["mixed"] == "pallas_ragged"
        assert report["kv_write"] == {"paged": "page_kernel"}

    @pytest.mark.parametrize("width, copies", [(2048, 0), (1920, 0), (1856, 1)])
    def test_the_stored_width_spares_the_grouped_matmul_a_copy(
            self, width, copies):
        """At the published sizes the device's default layout of [64, 2688,
        1856] puts 2688 innermost (1856 is no multiple of 128 lanes) and
        the grouped matmul copies the tensor, 609 MB a layer and call;
        stored in a multiple of 128 columns it is taken as it lies (2048, where
        the chip measured its tiles twice as fast as at 1920: docs/kernels.md)."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        compiled = jax.jit(jax.lax.ragged_dot).lower(
            _abstract((288, 2688), jnp.bfloat16),
            _abstract((64, 2688, width), jnp.bfloat16), _i32(64)).compile()
        found = [line for line in compiled.as_text().splitlines()
                 if " copy(" in line and "64,2688" in line]
        assert len(found) == copies, found
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert (temp > 600e6) == bool(copies)


#: the routed experts of the benchmark's five expert configurations on one
#: chip: (experts held, hidden, stored width, experts a token, lanes of a
#: decode step, tokens of the longest packed step)
HELD_EXPERTS = {
    "glm47-flash": (64, 2048, 1536, 4, 48, 2048),
    "nemotron3-nano": (64, 2688, 2048, 6, 48, 2048),
    "command-a-plus": (16, 4096, 4096, 8, 32, 4096),
    "solar-open2": (40, 4096, 1536, 8, 48, 4096),
    "lfm2-24b-a2b": (64, 2048, 1536, 4, 48, 4096),
}


def _grouped_tiling(rows, stacked):
    """(m, k, n) tiles the TPU's grouped matmul takes for [rows, k] x
    `stacked` [held, k, n], as the compiled program states them."""
    import re

    held, k, n = stacked.shape
    text = jax.jit(functools.partial(
        jax.lax.ragged_dot, preferred_element_type=jnp.float32)).lower(
        _abstract((rows, k), jnp.bfloat16), _abstract((held, k, n), jnp.bfloat16),
        _i32(held)).compile().as_text()
    (found,) = set(re.findall(r"ragged_dot_tiling[^0-9]*(\d+,\d+,\d+)", text))
    return tuple(int(t) for t in found.split(","))


class TestHeldExpertsMeetWholeTiles:
    """The grouped matmul takes ONE tile size for each of k and n, the
    largest of 512 / 256 / 128 that divides it, and on 128 it read its
    weights at a third of the bandwidth (docs/kernels.md, "A chip's share
    of the experts"; PR 55).  Every routed tensor of every expert
    configuration, as `models/moe.device_layout` lays it out, compiles to
    512 x 512, in a decode step and in the packed step; what is left over
    of a `hidden` of no whole tiles keeps its 128 on that side alone."""

    @pytest.mark.parametrize("step", ["decode", "packed"])
    @pytest.mark.parametrize("name", sorted(HELD_EXPERTS))
    def test_the_device_layout_compiles_to_512_tiles(self, name, step):
        from kserve_tpu.models.moe import device_layout, hidden_body

        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        held, hidden, width, k, lanes, tokens = HELD_EXPERTS[name]
        rows = k * (lanes if step == "decode" else tokens)
        layer = {"w_up": jax.ShapeDtypeStruct((held, hidden, width), jnp.bfloat16),
                 "w_down": jax.ShapeDtypeStruct((held, width, hidden), jnp.bfloat16)}
        laid = jax.eval_shape(device_layout, layer)
        split = hidden_body(hidden) != hidden
        assert split == (name == "nemotron3-nano")
        for tensor in (laid["w_up"], laid["w_down"]):
            assert isinstance(tensor, tuple) == split
            body, *rest = tensor if split else (tensor,)
            assert _grouped_tiling(rows, body)[1:] == (512, 512), body
            for part in rest:  # 128 rows of w_up, 128 columns of w_down
                tiles = _grouped_tiling(rows, part)[1:]
                assert sorted(tiles) == [128, 512], part
                assert tiles[part.shape[1:].index(128)] == 128

    @pytest.mark.parametrize("rows", [288, 12288])
    def test_a_hidden_of_21_x_128_whole_ran_one_side_on_128_tiles(self, rows):
        """What PR 41 to PR 54 ran: the pin that would have caught it."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        up = jax.ShapeDtypeStruct((64, 2688, 2048), jnp.bfloat16)
        down = jax.ShapeDtypeStruct((64, 2048, 2688), jnp.bfloat16)
        assert _grouped_tiling(rows, up)[1:] == (128, 512)
        assert _grouped_tiling(rows, down)[1:] == (512, 128)


class TestCommandAPlus:
    """`model_type: cohere2_moe` (PR 43): the packed step's window attention
    as ONE kernel over (ring pages, the buffer's own slice) at the published
    sizes, and the `mixed` program that calls it."""

    def test_the_window_kernel_compiles_at_the_published_sizes(self):
        """128 query / 8 K/V heads of 128, a ring of 64 pages of 64 tokens,
        32 lanes, a 1024-token buffer: no [T, T] or [blocks, ring] array
        outside the kernel (its temporaries are the two transposes of the
        buffer's queries), and the K/V page write of a 4096-token buffer
        states the VMEM it needs."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        from kserve_tpu.ops import pallas_kv_write

        T, lanes, nq, nkv, d, ps, ring = 1024, 32, 128, 8, 128, 64, 64
        bf16 = jnp.bfloat16
        rings = _abstract((1 + lanes * ring, 2, nkv, ps, d), bf16)
        compiled = jax.jit(
            lambda q, k, v, pages, table, start, n, kv0:
            pk.window_attention_ragged_pallas(
                q, k, v, pages, table, start, n, kv0, d ** -0.5)).lower(
            _abstract((T, nq, d), bf16), _abstract((T, nkv, d), bf16),
            _abstract((T, nkv, d), bf16), rings, _i32(lanes, ring),
            _i32(lanes), _i32(lanes), _i32(lanes)).compile()
        assert "window_attention_ragged" in compiled.as_text()
        # q by K/V head and back, the buffer's K/V as pages: a few copies
        # of the buffer, nothing of the size of a gathered ring a block
        # (128 blocks x 16.8 MB = 2.1 GB) or of the scores
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 4 * T * nq * d * 2, temp
        pool = _abstract((4352, 2, nkv, ps, d), bf16)
        jax.jit(pallas_kv_write.write_runs).lower(
            pool, _abstract((4096, nkv, d), bf16), _abstract((4096, nkv, d), bf16),
            _i32(lanes, 128), _i32(lanes), _i32(lanes), _i32(lanes),
            _i32(lanes)).compile()

    def test_mixed_calls_the_window_kernel_and_the_decode_kernel_over_rings(
            self, monkeypatch):
        import dataclasses
        import re

        from kserve_tpu.engine.shapes import DispatchShapes
        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama
        from test_command_a_model import CFG

        # rows of 128 and a window wide enough for the rule to choose the
        # kernel (36 MB of gathered ring and scores a block of 8 queries)
        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config(
                dict(CFG, hidden_size=128, head_dim=128, num_attention_heads=32,
                     num_key_value_heads=2, sliding_window=16384,
                     intermediate_size=128)),
            dtype="bfloat16")
        cfg = EngineConfig(
            max_batch_size=8, page_size=64, num_pages=64, max_pages_per_seq=8,
            max_prefill_len=128, prefill_buckets=(128,), dtype="bfloat16")
        assert att._should_use_window_pallas(128, 32, 2, 16384, 8, "tpu")
        layout = kvcache.StateLayout.of(mc, 64, cfg.num_pages, 8, "bfloat16")
        text = _lower_mixed(
            mc, cfg, jax.eval_shape(layout.init_state), 8,
            monkeypatch).as_text()
        kernels = set(re.findall(r'kernel_name = "([a-z_]+)"', text))
        assert {"window_attention_ragged", "window_attention_decode",
                "kv_page_write"} <= kernels, kernels
        # no gathered ring a block of queries: [16 blocks, 256 ring pages, ..]
        assert not re.findall(r"tensor<16x256x2x2x64x128xbf16>", text)
        # 4 layers x (packed step + decode steps) x gate, up, down
        assert len(re.findall(r"ragged_dot", text)) >= 24
        assert DispatchShapes.of(mc, cfg, "tpu").align == pk.RAGGED_BQ
        report = att.describe_attention_dispatch(mc, cfg, "tpu")
        assert report["mixed"] == "pallas_window_ragged+pallas_ragged"
        assert report["kv_write"] == {"paged": "page_kernel", "window": "page_kernel"}


class TestSolarOpen2:
    """`model_type: solar_open2` (PR 49): the delta rule's two forms compile
    for the described v5e at the published sizes, and the packed form holds
    one piece's arrays at a time."""

    def test_both_forms_compile_at_the_published_sizes(self):
        """64 heads of 128, 48 lanes, a 4096-token buffer: the 112 piece slots
        run inside ONE loop, and ONE conditional skips a dead one.  The
        triangular system is inverted by products (no library call:
        ops/delta._unit_lower_inverse); nothing over [tokens, heads, d, d]
        (137 GB), no float32 array with the slots or a 58-piece chunk as an
        axis (index arrays only), none larger than the lanes' states (the
        piece's block of differences times its two row operands is a
        product inside a fusion), and no padded copy of the buffer's five
        inputs (680 MB).  The loop's carry IS the result (the states 201 MB,
        the output 134 MB: no lane is selected back afterwards, so neither
        is copied), and the temporaries are one piece's arrays, 5 MB of them
        outside the chip's fast memory."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        import math
        import re

        from kserve_tpu.ops import delta

        T, lanes, H, d = 4096, 48, 64, 128
        f32 = jnp.float32
        buf, state = _abstract((T, H, d), f32), _abstract((lanes, H, d, d), f32)
        compiled = jax.jit(delta.kda_ragged).lower(
            buf, buf, buf, buf, _abstract((T, H), f32), state, _i32(lanes),
            _i32(lanes), _abstract((lanes,), jnp.bool_)).compile()
        text = compiled.as_text()
        assert "InvertDiagBlocksLowerTriangular" not in text  # no library solve
        assert len(re.findall(r"\bconditional\(", text)) == 1  # a dead piece
        assert not re.findall(r"f32\[\d+,64,128,128,128\]", text)
        slots = T // delta.KDA_CHUNK + min(lanes, T // 2)
        shapes = {tuple(int(n) for n in dims.split(","))
                  for dims in re.findall(r"f32\[([\d,]+)\]", text)}
        for shape in shapes:
            assert not (len(shape) > 2 and shape[0] in (slots, 58)), shape
            assert math.prod(shape) <= lanes * H * d * d, shape
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 48e6, temp
        lane = _abstract((lanes, H, d), f32)
        step = jax.jit(delta.kda_step).lower(
            lane, lane, lane, lane, _abstract((lanes, H), f32), state,
            _abstract((lanes,), jnp.bool_)).compile()
        # the state read and written once: no copy of it beside the result
        assert step.memory_analysis().temp_size_in_bytes < lanes * H * d * d * 4

    def test_the_packed_convolution_is_one_pass_over_the_buffer(self):
        """`[4096, 24576]` bf16, 4 taps, 48 lanes (PR 52): the taps are
        static shifts inside ONE fusion that reads the bf16 buffer and
        writes the float32 result, the slices' first rows are put into that
        result in place, and nothing else has the buffer's rows: no float32
        copy of the buffer (403 MB) and no gathered tap (403 MB each; the
        parent held six) among the temporaries."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        import re

        from kserve_tpu.ops import ssm

        T, D, lanes, K = 4096, 24576, 48, 4
        bf16 = jnp.bfloat16
        compiled = jax.jit(ssm.causal_conv_ragged).lower(
            _abstract((T, D), bf16), _abstract((lanes, K - 1, D), bf16),
            _abstract((K, D), bf16), _abstract((), jnp.float32), _i32(T),
            _i32(T), _i32(lanes), _i32(lanes),
            _abstract((lanes,), jnp.bool_)).compile()
        entry = compiled.as_text().split("ENTRY")[1]
        passes = [line for line in entry.splitlines()
                  if re.search(rf"= \w+\[{T},{D}\]\S* (fusion|convert|copy|"
                               r"gather|scatter|pad|slice)\(", line)]
        # the shifted sum, and the scatter that aliases its result
        assert len(passes) == 2, passes
        assert "kind=kLoop" in passes[0] and "scatter" in passes[1], passes
        assert compiled.memory_analysis().temp_size_in_bytes < 32e6


class TestLfm2:
    """`model_type: lfm2_moe` (PR 54): heads of 64 lie two a 128-wide cache
    row, so the attention rows take the kernels the other tables' rows take;
    the gated short convolution is plain XLA around `in_proj` / `out_proj`."""

    def test_mixed_calls_the_paged_kernels_over_paired_rows(self, monkeypatch):
        """The `mixed` program's two steps (the packed step and the decode
        steps), lowered for the TPU: the ragged kernel and the decode kernel
        (the packed step's one-token lanes on it too), the page write, the
        grouped experts; no gathered history and no tail but [lanes, 2,
        hidden]."""
        import dataclasses
        import re

        from kserve_tpu.engine.shapes import DispatchShapes
        from kserve_tpu.engine.types import EngineConfig
        from kserve_tpu.models import llama
        from test_lfm2_model import CFG

        mc = dataclasses.replace(
            llama.LlamaConfig.from_hf_config(
                dict(CFG, hidden_size=256, num_attention_heads=8,
                     num_key_value_heads=4, intermediate_size=256,
                     moe_intermediate_size=128)),
            dtype="bfloat16")
        assert mc.pairs_kv_heads and (mc.cache_kv_heads, mc.cache_head_dim) == (2, 128)
        cfg = EngineConfig(
            max_batch_size=8, page_size=64, num_pages=64, max_pages_per_seq=8,
            max_prefill_len=128, prefill_buckets=(128,), dtype="bfloat16")
        layout = kvcache.StateLayout.of(mc, 64, cfg.num_pages, 8, "bfloat16")
        state = jax.eval_shape(layout.init_state)
        assert state["ssm"] == [] and len(state["conv"]) == 6
        text = _lower_mixed(mc, cfg, state, 8, monkeypatch).as_text()
        kernels = set(re.findall(r'kernel_name = "([a-z_]+)"', text))
        assert {"ragged_paged_attention", "paged_attention_decode",
                "kv_page_write"} <= kernels, kernels
        # the cache's rows as the kernels see them, and no history gathered
        # a lane ([lanes, 8 pages x 64 tokens, ..]) beside them
        assert "tensor<64x2x2x64x128xbf16>" in text
        assert not re.findall(r"tensor<8x512x\d+x(?:64|128)x", text)
        # 6 expert layers x (packed step + decode steps) x gate, up, down
        assert len(re.findall(r"ragged_dot", text)) >= 36
        assert "tensor<8x2x256xbf16>" in text  # the tails
        assert DispatchShapes.of(mc, cfg, "tpu").align == pk.RAGGED_BQ
        report = att.describe_attention_dispatch(mc, cfg, "tpu")
        assert report["mixed"] == "pallas_ragged"
        assert report["decode"] == "pallas_decode"
        assert report["packed_single_token_min_pages"] == 0
        assert report["kv_write"] == {"paged": "page_kernel"}

    def test_the_kernels_compile_at_the_published_widths(self):
        """32 query heads over 4 cache rows of 128 (8 K/V heads of 64), 48
        lanes, a 4096-token buffer, a 128-page table of 64-token pages: the
        ragged kernel, the decode kernel, the split of the packed step's
        one-token lanes and the page write compile for the described v5e."""
        if _tpu_sharding() is None:
            pytest.skip("no compile-only TPU topology here")
        T, lanes, nq, rows, d, ps, W = 4096, 48, 32, 4, 128, 64, 128
        bf16 = jnp.bfloat16
        pool = _abstract((6528, 2, rows, ps, d), bf16)
        q, lane = _abstract((T, nq, d), bf16), _i32(lanes)
        for entry in (pk.ragged_paged_attention_pallas,
                      pk.ragged_single_token_split_pallas):
            compiled = jax.jit(entry).lower(
                q, pool, _i32(lanes, W), lane, lane, lane).compile()
            assert "ragged_paged_attention" in compiled.as_text()
        jax.jit(pk.paged_attention_pallas).lower(
            _abstract((lanes, nq, d), bf16), pool, _i32(lanes, W), lane).compile()
        jax.jit(kw.write_runs).lower(
            pool, _abstract((T, rows, d), bf16), _abstract((T, rows, d), bf16),
            _i32(lanes, W), lane, lane, lane, lane).compile()
