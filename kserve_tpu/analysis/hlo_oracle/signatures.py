"""Canonical program signatures: the tiny-model engine state plus the
exact per-program dispatch arguments the serving loop builds.

Each `args_for` branch mirrors one engine.py dispatch site (the arg
order, dtypes, page-table bucketing, and device commitment of
_dispatch_chunk / _step_mixed / _dispatch_dense / the inject paths), so
what the oracle lowers is signature-identical to what the engine
dispatches under the same config.  The model is LlamaConfig.tiny on the
tests' engine config (tests/test_engine.py:make_engine) — budgets track
RATIOS and structure, which the tiny model preserves, not absolute
chip-seconds.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...engine.compiled import program_defs
from ...engine.kvcache import KVCacheConfig, init_kv_pages, init_kv_scales
from ...engine.sampling import SamplingState
from ...engine.shapes import (
    PLAN_ROWS,
    SAMPLER_COLUMNS,
    DispatchShapes,
    MixedLayout,
)
from ...engine.types import EngineConfig
from ...models import llama
from ...parallel import sharding as shd

#: prefill rows per batched dispatch (the engine pads the admission
#: batch to a power of two; 4 is the tiny config's max_batch_size)
PREFILL_ROWS = 4

#: pages per inject dispatch before padding to a width bucket (a
#: mid-size P/D / tier-store payload)
INJECT_PAGES = 4


def tiny_model_config():
    return llama.LlamaConfig.tiny(dtype="float32")


def tiny_engine_config(**overrides) -> EngineConfig:
    base = dict(
        max_batch_size=4,
        page_size=8,
        num_pages=64,
        max_pages_per_seq=8,
        max_prefill_len=32,
        prefill_buckets=(16, 32),
        tp=1,
        dtype="float32",
        use_pallas=False,
    )
    base.update(overrides)
    return EngineConfig(**base)


@dataclass
class ProgramSet:
    """One (model, engine config, mesh) worth of compiled-program
    definitions plus the sharded state their dispatches close over."""

    mc: object
    cfg: EngineConfig
    mesh: object
    params: dict
    kv_pages: list
    defs: dict  # name -> (python fn, donate_argnums)
    spec_k: Optional[int] = None

    @property
    def shapes(self) -> DispatchShapes:
        """The dispatch shapes the engine would plan with under this
        config: what the canonical arguments below are sized from."""
        return DispatchShapes.of(self.mc, self.cfg, jax.default_backend())


def build_program_set(tp: int = 1, spec_k: Optional[int] = None,
                      **cfg_overrides) -> ProgramSet:
    """Engine-equivalent state without an engine: mesh, sharded params,
    sharded kv cache, and the program_defs table — everything needed to
    lower every program exactly as LLMEngine._build_compiled would."""
    mc = tiny_model_config()
    cfg = tiny_engine_config(tp=tp, **cfg_overrides)
    mesh = shd.create_mesh(tp=cfg.tp, dp=1, sp=cfg.sp, pp=cfg.pp)
    params = llama.init_params(mc, jax.random.PRNGKey(1))
    params = shd.shard_params(params, mc, mesh)
    cache_cfg = KVCacheConfig(
        n_layers=mc.n_layers,
        n_kv_heads=mc.n_kv_heads,
        head_dim=mc.head_dim,
        page_size=cfg.page_size,
        num_pages=cfg.num_pages,
        max_pages_per_seq=cfg.max_pages_per_seq,
        dtype=cfg.dtype,
    )
    if cfg.kv_quant == "int8":
        pages = init_kv_pages(
            dataclasses.replace(cache_cfg, dtype="int8"),
            shd.kv_pages_sharding(mesh))
        scale_sharding = shd.named_canonical(
            mesh,
            jax.sharding.PartitionSpec(None, None, shd.MODEL_AXIS, None))
        scales = init_kv_scales(cache_cfg, scale_sharding)
        kv_pages = list(zip(pages, scales))
    else:
        kv_pages = init_kv_pages(cache_cfg, shd.kv_pages_sharding(mesh))
    defs = program_defs(mc, cfg, mesh, spec_k=spec_k)
    return ProgramSet(mc=mc, cfg=cfg, mesh=mesh, params=params,
                      kv_pages=kv_pages, defs=defs, spec_k=spec_k)


def _kv_payload_shapes(ps: ProgramSet, n_pages: int):
    mc, cfg = ps.mc, ps.cfg
    return (mc.n_layers, n_pages, 2, mc.n_kv_heads, cfg.page_size,
            mc.head_dim)


def args_for(ps: ProgramSet, name: str,
             bucket: Optional[int] = None) -> Tuple[tuple, dict]:
    """(dispatch args, norm metadata) for one program.  `bucket` selects
    the prefill length bucket for the bucketed programs (defaults to the
    largest)."""
    mc, cfg = ps.mc, ps.cfg
    B = cfg.max_batch_size
    V = mc.vocab_size
    Bp = PREFILL_ROWS
    shapes = ps.shapes
    # the largest pair a mixed dispatch takes (_plan_ragged)
    T, width = shapes.pairs()[-1]
    bucket = bucket or shapes.token_budget
    rng = jax.random.PRNGKey(0)
    steps = cfg.steps_per_sync

    def i32(*shape, fill=0):
        return jnp.full(shape, fill, jnp.int32)

    if name in ("prefill", "prefill_lp"):
        args = (
            ps.params,
            i32(Bp, bucket),
            i32(Bp),
            ps.kv_pages,
            i32(Bp, cfg.max_pages_per_seq),
            SamplingState.defaults(Bp),
            rng,
            i32(Bp, fill=-1),
        )
        return args, {"batch": Bp, "tokens": Bp * bucket, "steps": 1}
    if name == "prefill_chunk":
        args = (
            ps.params,
            i32(Bp, bucket),
            i32(Bp),
            i32(Bp),
            ps.kv_pages,
            i32(Bp, cfg.max_pages_per_seq),
            i32(Bp, fill=-1),
        )
        return args, {"batch": Bp, "tokens": Bp * bucket, "steps": 1}
    if name in ("sample_first", "sample_first_lp"):
        args = (
            jnp.zeros((Bp, V), jnp.float32),
            SamplingState.defaults(Bp),
            rng,
            jnp.zeros((Bp, V), bool),
        )
        return args, {"batch": Bp, "tokens": Bp, "steps": 1}
    if name in ("decode", "decode_lp", "decode_penalized",
                "decode_penalized_lp"):
        args = (
            ps.params,
            i32(B),
            i32(B),
            ps.kv_pages,
            i32(B, width),
            jnp.ones((B,), bool),
            i32(B, fill=cfg.max_pages_per_seq * cfg.page_size),
            i32(B),
            SamplingState.defaults(B),
            rng,
            i32(B, fill=-1),
        )
        if name.startswith("decode_penalized"):
            args = args + (jnp.zeros((B, V), bool), i32(B, V))
        return args, {"batch": B, "tokens": B * steps, "steps": steps}
    if name == "inject":
        nb = shapes.width(INJECT_PAGES)
        args = (
            ps.kv_pages,
            jnp.zeros(_kv_payload_shapes(ps, nb), jnp.dtype(cfg.dtype)),
            i32(nb),
        )
        return args, {"pages": nb, "steps": 1}
    if name == "inject_q":
        nb = shapes.width(INJECT_PAGES)
        args = (
            ps.kv_pages,
            jnp.zeros(_kv_payload_shapes(ps, nb), jnp.int8),
            jnp.zeros(_kv_payload_shapes(ps, nb)[:-1], jnp.float32),
            i32(nb),
        )
        return args, {"pages": nb, "steps": 1}
    if name == "mixed":
        # the three packed buffers of _step_mixed, filled through the
        # program's own layout: no lane mid-resume, every lane joining the
        # scan at full capacity, the sampler's defaults, step 0
        defaults = SamplingState.defaults(B)
        columns = {name: np.zeros((B,), np.int32) for name in PLAN_ROWS}
        columns.update(
            {name: np.asarray(getattr(defaults, name))
             for name in SAMPLER_COLUMNS},
            q_tokens=[], token_seq=[], token_pos=[],
            joins=np.ones((B,), bool),
            scan_tok0=np.full((B,), -1, np.int32),
            capacity=np.full(
                (B,), cfg.max_pages_per_seq * cfg.page_size, np.int32),
            adapters=np.full((B,), -1, np.int32))
        tokens_buf, lanes_buf = MixedLayout(T, B, width).pack(columns, 0)
        args = (
            ps.params,
            jnp.asarray(tokens_buf),
            jnp.asarray(lanes_buf),
            ps.kv_pages,
            i32(B, width),       # page_table
            rng,                 # the base key: the program folds the step in
        )
        return args, {"batch": B, "tokens": T + (steps - 1) * B,
                      "steps": steps}
    if name == "mixed_decode":
        k = ps.spec_k or 0
        # _dispatch_dense commits the chained carries to the replicated
        # spelling and the draft table to draft_table_pspec — committed
        # inputs are part of the jit signature, so the oracle must match
        rep = shd.named(ps.mesh, jax.sharding.PartitionSpec())
        table_s = shd.named(ps.mesh, shd.draft_table_pspec())
        table_cols = V if k > 0 else 1
        args = (
            ps.params,
            jax.device_put(i32(B), rep),   # tokens (device carry)
            jax.device_put(i32(B), rep),   # pos
            ps.kv_pages,
            i32(B, width),                 # page_table
            jnp.ones((B,), bool),          # live
            i32(B, fill=cfg.max_pages_per_seq * cfg.page_size),  # capacity
            jax.device_put(i32(B), rep),   # counters
            jax.device_put(i32(B, table_cols, fill=-1), table_s),
            SamplingState.defaults(B),
            rng,
            i32(B, fill=-1),               # adapters
        )
        return args, {"batch": B, "tokens": B * (k + 1) * steps,
                      "steps": steps, "k": k}
    raise KeyError(f"no signature for program {name!r}")
