"""engine/work.py on its own: hand-built plans through `DispatchWork` against
increments worked out by hand, a kind of model a case.  The counters are
functions of a launch's plan alone, so the same plan must give these numbers
whatever else moves (PR 47 moved the arithmetic out of the engine; the
families' engine tests hold it end to end)."""

import dataclasses

import numpy as np
import pytest
from prometheus_client import REGISTRY

from kserve_tpu.engine.kvcache import StateLayout
from kserve_tpu.engine.types import EngineConfig
from kserve_tpu.engine.work import DispatchWork
from kserve_tpu.models import llama
from kserve_tpu.ops.attention import describe_attention_dispatch
from test_command_a_model import CONFIG as RINGS
from test_nemotron_model import CONFIG as MAMBA2

PAGE = 4
PLAIN = llama.LlamaConfig.tiny()
#: a packed step of three lanes (a single token over 5 cached, a 3-token
#: chunk at 0, a single token over 9; lane 3 idle), then 2 decode steps in
#: which lane 2 has room for one
PLAN = dict(
    q_len=[1, 3, 1, 0], kv_start=[5, 0, 9, 0], scan_pos0=[6, 3, 10, 0],
    joins=[True, True, True, False], capacity=[16, 16, 11, 16],
    prefill_tokens=3, decode_tokens=2)
#: two lanes past and around a window of 16: a 5-token chunk at 14 (two of
#: its queries still see fewer than 16 keys), a single token at 20
RING_PLAN = dict(
    q_len=[5, 1], kv_start=[14, 20], scan_pos0=[19, 21], joins=[True, True],
    capacity=[64, 64], prefill_tokens=5, decode_tokens=1)


def _work(model, lanes, label, **report):
    config = EngineConfig(max_batch_size=lanes, page_size=PAGE, num_pages=64,
                          max_pages_per_seq=16, max_prefill_len=16,
                          prefill_buckets=(16,), dtype="float32")
    layout = StateLayout.of(model, PAGE, 64, lanes, "float32")
    attention = {**describe_attention_dispatch(model, config, "cpu"), **report}
    wrote = []
    return DispatchWork(model, layout, attention, label,
                        wrote=lambda path, n: wrote.append((path, n))), wrote


def _read(label):
    """Every engine_* sample of `label` that is not zero, by name and labels."""
    got = {}
    for metric in REGISTRY.collect():
        for s in metric.samples:
            if (s.labels.get("model_name") == label and s.value
                    and s.name.endswith("_total")):
                rest = ",".join(f"{k}={v}" for k, v in sorted(s.labels.items())
                                if k != "model_name")
                got[s.name + ("{" + rest + "}" if rest else "")] = s.value
    return got


def test_a_plain_model_whose_single_token_lanes_take_the_decode_kernel():
    """3 steps x 2 layers of writes; decode steps: lanes at 6, 3, 10 run 2,
    2, 1 steps = (6+7) + (3+4) + 10 + 5 tokens attended = 35 and 2+2 + 1+2 +
    3 = 10 pages held, 4 lanes a block x (3 + 2) pages of its longest = 20;
    the packed step's two single-token lanes as one decode call more: 6 + 10
    tokens, 2 + 3 pages held, 4 x 3 walked."""
    work, wrote = _work(PLAIN, 4, "work-plain", packed_single_token_min_pages=0)
    work.packed(PLAN, width=4, steps=3)
    assert _read("work-plain") == {
        "engine_layer_passes_total": 3,
        "engine_kv_write_calls_total{write_path=row_scatter}": 3 * PLAIN.n_layers,
        "engine_kv_context_tokens_total": 35 + 16,
        "engine_kv_decode_pages_total{reach=own}": 10 + 5,
        "engine_kv_decode_pages_total{reach=block}": 20 + 12,
        "engine_packed_lanes_total{attention_path=decode_kernel}": 2,
        "engine_packed_lanes_total{attention_path=ragged}": 1,
    }
    assert wrote == [("row_scatter", 3 * PLAIN.n_layers)]


@pytest.mark.parametrize("width, min_pages", [(4, None), (4, 8)])
def test_below_the_split_s_width_every_lane_is_the_ragged_kernel_s(
        width, min_pages):
    label = f"work-ragged-{min_pages}"
    work, _ = _work(PLAIN, 4, label, packed_single_token_min_pages=min_pages)
    work.packed(PLAN, width=width, steps=3)
    got = _read(label)
    assert got["engine_packed_lanes_total{attention_path=ragged}"] == 3
    assert got["engine_kv_context_tokens_total"] == 35
    assert got["engine_kv_decode_pages_total{reach=own}"] == 10


def test_a_legacy_prefill_writes_by_the_row_scatter_and_counts_its_pairs():
    """A plain model of experts holds them all: the host counts tokens x 2
    experts a token x its layers; a legacy prefill's writes are the row
    scatter's whatever the report says."""
    model = dataclasses.replace(PLAIN, n_experts=4)
    work, wrote = _work(model, 2, "work-moe",
                        kv_write={"paged": "page_kernel"})
    work.forward(1, packed_tokens=7, legacy_prefill=True)
    work.forward(2, [3, 8], [True, False], [16, 16], decode_steps=2)
    assert _read("work-moe") == {
        "engine_layer_passes_total": 3,
        "engine_kv_write_calls_total{write_path=row_scatter}": model.n_layers,
        "engine_kv_write_calls_total{write_path=page_kernel}": 2 * model.n_layers,
        "engine_kv_context_tokens_total": 4 + 5,
        "engine_kv_decode_pages_total{reach=own}": 1 + 2,
        "engine_kv_decode_pages_total{reach=block}": 2 * (1 + 2),
        "engine_moe_assignments_total": (7 + 2) * 2 * model.n_layers,
    }
    assert wrote == [("row_scatter", model.n_layers),
                     ("page_kernel", 2 * model.n_layers)]


def test_rings_and_a_share_of_the_experts():
    """Three ring layers of window 16 beside one full layer.  One decode
    step at 19 and 21: 20 + 22 tokens, 5 + 6 pages, both past the window.
    The packed step's 6 queries a ring layer; the chunk at 14 sees 15, 16,
    16, 16, 16 keys and the single token 16 = 95 pairs, and reads 14 + 5
    and 15 + 1 = 35 keys.  The program counts its own pairs: of 48 routed
    40 were multiplied here."""
    assert RINGS.sliding_window == 16 and RINGS.counts_routed_pairs
    work, _ = _work(RINGS, 2, "work-rings")
    work.packed(RING_PLAN, width=16, steps=2)
    rows = np.arange(24).reshape(6, 4)
    sums = np.array([[7, 0, 0, 0], [3, 0, 0, 0], [40, 0, 0, 0], [48, 0, 0, 0]])
    tokens = work.fetched(np.concatenate([rows, sums]))
    np.testing.assert_array_equal(tokens, rows)
    assert _read("work-rings") == {
        "engine_layer_passes_total": 2,
        "engine_kv_write_calls_total{write_path=row_scatter}": 2 * 4,
        "engine_kv_context_tokens_total": 42,
        "engine_kv_decode_pages_total{reach=own}": 11,
        "engine_kv_decode_pages_total{reach=block}": 12,
        "engine_packed_lanes_total{attention_path=ragged}": 2,
        "engine_window_lane_steps_total{bound=yes}": 2,
        "engine_window_ragged_work_total{unit=queries}": 6 * 3,
        "engine_window_ragged_work_total{unit=pairs}": 95 * 3,
        "engine_window_ragged_work_total{unit=keys}": 35 * 3,
        "engine_moe_expert_hits_total": 7,
        "engine_moe_peak_load_total": 3,
        "engine_moe_assignments_total": 40,
        "engine_moe_pairs_elsewhere_total": 8,
    }


def test_mamba_2_layers_scan_the_packed_tokens_and_update_a_lane_step():
    """5 packed tokens through every Mamba-2 layer's scan, then 2 decode
    steps: one update call a step and layer, 2 + 1 lane-steps a layer."""
    ssd = sum(row.kind == "mamba2" for row in MAMBA2.layer_table())
    assert ssd > 0
    work, _ = _work(MAMBA2, 2, "work-ssd")
    work.forward(3, [4, 9], [True, True], [16, 10], decode_steps=2,
                 packed_tokens=5)
    got = _read("work-ssd")
    assert got["engine_ssd_scan_tokens_total"] == 5 * ssd
    assert got["engine_ssd_update_calls_total"] == 2 * ssd
    assert got["engine_ssd_update_lane_steps_total"] == 3 * ssd
    assert got["engine_kv_context_tokens_total"] == (5 + 6) + 10
