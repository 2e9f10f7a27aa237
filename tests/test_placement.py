"""Weights and cache are created ON the devices that will hold them: never
staged whole on device 0 and moved (an 8B model at tp=4 does not fit one
chip, and every dp replica g>0 used to be built on chip 0)."""

import jax
from jax.sharding import PartitionSpec as P

from kserve_tpu.engine.dp import DataParallelEngine
from kserve_tpu.engine.engine import EngineConfig
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.models.llama import LlamaConfig
from kserve_tpu.parallel import sharding as shd


def _devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def test_random_init_lands_on_its_mesh():
    mc = LlamaConfig.tiny()
    devices = jax.devices()[4:6]
    mesh = shd.create_mesh(tp=2, devices=devices)
    for quant in ("none", "int8"):
        params = shd.init_params_on_mesh(
            mc, jax.random.PRNGKey(1), mesh, weight_quant=quant)
        assert _devices_of(params) == set(devices)
    wq = params["layers"][0]["wq"]["q"]
    assert wq.sharding.spec == P(None, shd.MODEL_AXIS)
    # each device holds its half of the heads, not a copy of the whole
    assert wq.addressable_shards[0].data.shape[1] == wq.shape[1] // 2


def test_dp_replicas_live_on_their_own_devices():
    mc = LlamaConfig.tiny(dtype="float32")
    cfg = EngineConfig(
        max_batch_size=2, page_size=8, num_pages=16, max_pages_per_seq=4,
        max_prefill_len=16, prefill_buckets=(16,), dtype="float32",
        use_pallas=False, dp=2)
    engine = DataParallelEngine(mc, cfg, ByteTokenizer(mc.vocab_size))
    for g, replica in enumerate(engine.replicas):
        own = {jax.devices()[g]}
        assert _devices_of(replica.params) == own
        assert _devices_of(replica.kv_pages) == own
    state = engine.scheduler_state()
    assert [[d["id"] for d in r["devices"]] for r in state["replicas"]] == [
        [0], [1]]
    assert state["free_pages"] == sum(
        r["free_pages"] for r in state["replicas"])
    # a dead loop in ANY replica must reach the server's fatal hook
    hook = object()
    engine.on_loop_crash = hook
    assert all(r.on_loop_crash is hook for r in engine.replicas)
