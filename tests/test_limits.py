"""engine/limits.py on its own: the rows no family's matrix reaches (a plain
model's experts and windows, the topology, the sizes, a hybrid table of
full-attention rows only), several refusals in one message, the early call
that has no sizes yet, and a program set that lacks `mixed`.  The families'
matrices are tests/test_*_engine.py."""

import dataclasses

import pytest

from kserve_tpu.engine.compiled import build_compiled
from kserve_tpu.engine.engine import LLMEngine
from kserve_tpu.engine.limits import check_request, model_kinds, resolve_serving
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.shapes import DispatchShapes
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.engine.types import EngineConfig
from kserve_tpu.models import llama
from test_command_a_model import CFG as COMMAND_A
from test_hybrid_model import CFG

PLAIN = llama.LlamaConfig.tiny()
HYBRID = llama.LlamaConfig.from_hf_config(CFG)
#: a per-layer table whose rows all write pages: no rings, no slots, and the
#: forward is models/hybrid.py all the same
FULL_ROWS = llama.LlamaConfig.from_hf_config(
    {**COMMAND_A, "layer_types": ["full_attention"] * 4})


def engine_config(**over) -> EngineConfig:
    base = dict(max_batch_size=2, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=16,
                prefill_buckets=(16,), dtype="float32")
    base.update(over)
    return EngineConfig(**base)


def _resolve(model, sized=True, **over):
    lora = over.pop("lora", False)
    config = engine_config(**over)
    shapes = DispatchShapes.of(model, config, "cpu") if sized else None
    return resolve_serving(model, config, shapes=shapes, lora=lora)


@pytest.mark.parametrize("model, over, error, named", [
    (dataclasses.replace(PLAIN, n_experts=4), dict(lora=True),
     NotImplementedError, "LoRA over MoE layers"),
    (dataclasses.replace(PLAIN, sliding_window=8), dict(sp=2),
     NotImplementedError, "sliding windows"),
    (dataclasses.replace(PLAIN, query_pre_attn_scalar=64.0), dict(sp=2),
     NotImplementedError, "attention-scale overrides"),
    (PLAIN, dict(pp=2, sp=2), NotImplementedError, "do not compose"),
    (dataclasses.replace(PLAIN, n_layers=3), dict(pp=2), ValueError,
     "n_layers=3 not divisible by pp=2"),
    (PLAIN, dict(use_ragged=True, pp=2), NotImplementedError,
     "use_ragged=True (requires pp==1"),
    (PLAIN, dict(use_ragged=True, max_batch_size=32), NotImplementedError,
     "use_ragged=True (requires pp==1"),
    (PLAIN, dict(spec_decode_k=2, use_ragged=False), NotImplementedError,
     "unified ragged"),
    (PLAIN, dict(spec_decode_k=-1), ValueError, "must be >= 0"),
    (PLAIN, dict(spec_decode_k=2, tp=2, max_batch_size=3), ValueError,
     "divisible by the tensor-parallel mesh axis"),
    (PLAIN, dict(tp=3), ValueError, "not divisible by tp=3"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_no_family_s_matrix_reaches_is_refused_by_name(
        model, over, error, named):
    with pytest.raises(error) as info:
        _resolve(model, **over)
    assert named in str(info.value)


def test_every_refused_setting_is_named_in_one_message():
    with pytest.raises(NotImplementedError) as info:
        _resolve(HYBRID, pp=2, kv_quant="int8", prefix_cache=True, lora=True)
    message = str(info.value)
    for named in ("pp>1", "kv_quant=int8", "prefix_cache",
                  "LoRA adapters over a hybrid model"):
        assert named in message
    assert message.count("a model with Mamba-1") == 1


def test_the_rows_over_sizes_wait_for_the_sizes_and_a_plain_model_is_left_alone():
    for over in (dict(), dict(use_ragged=False), dict(pp=2),
                 dict(max_batch_size=32),
                 dict(prefix_cache=True, tp=2, kv_quant="int8")):
        assert _resolve(PLAIN, **over) is None
    # the server's early call: no sizes yet, nothing over sizes is judged
    _resolve(PLAIN, sized=False, use_ragged=True, pp=2)
    _resolve(HYBRID, sized=False, max_batch_size=32)
    with pytest.raises(NotImplementedError, match="mixed program only"):
        _resolve(HYBRID, max_batch_size=32)
    assert model_kinds(PLAIN) == {}
    assert set(model_kinds(HYBRID)) == {"hybrid", "lane_state", "windows"}


@pytest.mark.parametrize("over, named", [
    (dict(tp=2), "tp>1 over a hybrid model"),
    (dict(pp=2), "pp>1"),
    (dict(sp=2), "sp>1"),
    (dict(kv_quant="int8"), "kv_quant=int8"),
    (dict(weight_quant="int8"), "weight_quant=int8"),
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(kv_offload="host"), "kv_offload"),
    (dict(use_ragged=False), "use_ragged=False"),
    (dict(max_batch_size=32), "mixed program only"),
    (dict(role="prefill"), "role=prefill"),
    (dict(lora=True), "LoRA adapters over a hybrid model"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_table_of_full_attention_rows_is_a_hybrid_model_still(over, named):
    """Its forward is models/hybrid.py, which runs `mixed` only: what any
    hybrid model is refused it is refused, at start-up and at submit alike;
    its pages are a lane's whole state, so the prefix cache stays."""
    assert set(model_kinds(FULL_ROWS)) == {"hybrid", "experts", "windows"}
    role = over.pop("role", "both")
    config = engine_config(**{k: v for k, v in over.items() if k != "lora"})
    with pytest.raises(NotImplementedError) as info:
        resolve_serving(FULL_ROWS, config, role=role,
                        shapes=DispatchShapes.of(FULL_ROWS, config, "cpu"),
                        lora=over.get("lora", False))
    assert named in str(info.value)
    kept = engine_config(prefix_cache=None)
    resolve_serving(FULL_ROWS, kept)
    assert kept.prefix_cache is None
    with pytest.raises(ValueError, match="logprobs"):
        check_request(FULL_ROWS, SamplingParams(max_tokens=2, logprobs=1))


def test_a_program_set_without_mixed_is_refused_not_fallen_back_from():
    config = engine_config()
    programs = dataclasses.replace(
        build_compiled(PLAIN, config, None), mixed=None)
    with pytest.raises(NotImplementedError, match="no `mixed` program"):
        LLMEngine(PLAIN, config, ByteTokenizer(320),
                  compiled_programs=programs)
