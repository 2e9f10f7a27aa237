"""Continuous-batching LLM engine on JAX/XLA.

The role vLLM's AsyncLLM plays for the reference huggingfaceserver
(python/huggingfaceserver/vllm/vllm_model.py:55, start_engine :83), rebuilt
TPU-first:

- fixed decode slots (static shapes: one compiled decode program, reused
  forever); prompts prefill into bucketed-length compiled programs
- paged KV in HBM (engine/kvcache.py), pages allocated incrementally as
  sequences grow, newest slot preempted back to the queue on exhaustion
- sampling fully on device (engine/sampling.py), per-slot params as arrays
- TP via the ("data","model") mesh (parallel/sharding.py) — weights, KV
  pages and logits sharded; XLA inserts ICI collectives
- async streaming: each request owns an asyncio queue fed by the decode loop

Host<->device traffic per step is one [B] token fetch + tiny control arrays.

This file is the host-side scheduler: admission, slots, chunked prefill,
preemption, offload, P/D, the run loop.  README.md maps the modules beside
it (types, shapes, limits, work, compiled, kvcache, prefix_cache).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import deque
from typing import Any, AsyncIterator, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..logging import logger
from ..metrics import (
    ENGINE_BATCH_OCCUPANCY,
    ENGINE_DISPATCH_PART_SECONDS,
    ENGINE_DISPATCH_PHASE_CPU_SECONDS,
    ENGINE_DISPATCH_PHASE_SECONDS,
    ENGINE_DISPATCH_UPLOADS,
    ENGINE_DISPATCHES,
    ENGINE_DISPATCH_DELIVERIES,
    ENGINE_FIRST_TOKEN_DISPATCHES,
    ENGINE_KV_DISK_BYTES,
    ENGINE_KV_OFFLOAD_BYTES,
    ENGINE_KV_PAGES_FREE,
    ENGINE_KV_PAGES_TOTAL,
    ENGINE_KV_TOKEN_BYTES,
    ENGINE_PREEMPTIONS,
    ENGINE_STATE_BYTES,
    ENGINE_STATE_RESETS,
    ENGINE_STATE_SLOTS_IN_USE,
    ENGINE_PREFILL_CHUNK_DURATION,
    ENGINE_QUEUE_DEPTH,
    ENGINE_DISPATCH_SHAPE,
    ENGINE_SAMPLER_DISPATCHES,
    ENGINE_STEP_BATCH_COMPOSITION,
    ENGINE_STEP_DURATION,
    ENGINE_WEDGED,
    GENERATED_TOKENS,
    PROMPT_TOKENS,
    DEADLINE_REJECTED,
    GENERATION_CHECKPOINTS,
    GENERATION_RESUMES,
    KV_PAGEIN_SECONDS,
    KV_PREFIX_HIT_TOKENS,
    SPEC_TOKENS,
    TOKENS_SALVAGED,
    observe_request_timeline,
    observe_startup_phase,
)
from ..lifecycle.checkpoint import GenerationCheckpoint, GenerationPreempted
from ..lifecycle.state import ReplicaDrainingError
from ..models import llama
from ..models.moe import device_layout
from ..observability import (
    DELIVERIES,
    CPU_COLUMNS,
    DISPATCH_COLUMNS,
    PARTS,
    PHASES,
    DispatchPhases,
    RequestTimeline,
    TimelineRecorder,
    emit_timeline_spans,
    pauses,
)
from ..parallel import sharding as shd
from ..resilience import (
    MONOTONIC,
    Clock,
    Deadline,
    DeadlineExceededError,
    ReplicaCrashError,
    current_deadline,
)
from .kvcache import (
    KVCacheConfig,
    PageAllocator,
    StateLayout,
    device_filler,
    init_kv_pages,
    init_kv_scales,
    pages_needed,
    pages_of_passes,
)
from .limits import check_request, resolve_serving
from .sampling import SAMPLER_PATHS, SamplingParams, SamplingState, unpacked
from .shapes import (
    FITS,
    DispatchShapes,
    LoadedPairs,
    MixedLayout,
)
from .tokenizer import BaseTokenizer, IncrementalDetokenizer
from .work import DispatchWork


from .types import (  # noqa: F401 — re-exported: the public engine surface
    EngineConfig,
    EngineWedgedError,
    GenerationOutput,
    _DeadlineFetcher,
    _Delivery,
    _QueuedRequest,
    _Slot,
)


def _device_row(device) -> dict:
    stats = device.memory_stats() or {}
    return {
        "id": device.id,
        "platform": device.platform,
        "kind": device.device_kind,
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


#: where a dispatch row (observability.DISPATCH_COLUMNS) holds what the
#: counters are fed from: the program, the six phases with `wait_lag`, the
#: tokens the iteration handed to its streams, the parts of its phases and
#: the transfers of its launches' inputs
_PROGRAM_COLUMN = DISPATCH_COLUMNS.index("program")
_PHASE_COLUMNS = slice(DISPATCH_COLUMNS.index(PHASES[0]),
                       DISPATCH_COLUMNS.index("wait_lag") + 1)
_DELIVERED_COLUMNS = slice(DISPATCH_COLUMNS.index(DELIVERIES[0]),
                           DISPATCH_COLUMNS.index(DELIVERIES[-1]) + 1)
_PART_COLUMNS = [DISPATCH_COLUMNS.index(part) for part in PARTS]
_UPLOADS_COLUMN = DISPATCH_COLUMNS.index("uploads")
_CPU_COLUMNS = slice(DISPATCH_COLUMNS.index(CPU_COLUMNS[0]),
                     DISPATCH_COLUMNS.index(CPU_COLUMNS[-1]) + 1)


class LLMEngine:
    """Drive with `await engine.start()`, submit with `generate()`."""

    def __init__(
        self,
        model_config: llama.LlamaConfig,
        engine_config: EngineConfig,
        tokenizer: BaseTokenizer,
        params: Optional[Any] = None,
        rng_seed: int = 0,
        devices: Optional[list] = None,
        metrics_label: str = "engine",
        checkpoint_label: Optional[str] = None,  # weights identity for resume
        lora_adapters: Optional[Dict[str, str]] = None,
        lora_stacked=None,  # (adapter_ids, per-layer stacks) pre-loaded
        clock: Optional[Clock] = None,  # telemetry clock (FakeClock in chaos tests)
        # CPU seconds of the calling thread, for the loop's phases; under
        # an injected `clock` none unless a test passes its own
        cpu_clock: Optional[Callable[[], float]] = None,
        # the fleet-simulator stub seam (kserve_tpu/sim): an object with
        # the CompiledPrograms attribute surface replaces the jitted device
        # programs, and a fetch/fetch_async/close duck of _DeadlineFetcher
        # replaces the daemon fetch worker — so admission, batching,
        # preemption, drain and checkpointing all run the REAL scheduler
        # against a cycle-accurate stub device, deterministically on the
        # event-loop thread (no fetch-thread scheduling jitter)
        compiled_programs=None,
        fetcher=None,
    ):
        if engine_config.dp > 1:
            raise ValueError(
                "LLMEngine is a single data-parallel replica (dp=1); use "
                "engine.dp.DataParallelEngine for dp>1 — decode batches are "
                "independent, so DP runs as disjoint replicas, not a lockstep "
                "mesh axis"
            )
        self.model_config = model_config
        # startup-phase accounting (docs/coldstart.md): wall seconds per
        # phase, observed into engine_startup_seconds once the engine is
        # serving.  perf_counter (not the injectable telemetry clock) —
        # startup is host wall time, and the sim replica injects stub
        # programs so this path never runs under virtual time.
        self._construct_t0 = time.perf_counter()
        self.startup_phases: Dict[str, float] = {}
        # wall seconds spent BEFORE engine construction that belong to
        # this replica's startup (the server's checkpoint read) — folded
        # into the ready phase so ready stays the true total and never
        # reads smaller than the weights phase it contains
        self.startup_external_s = 0.0
        self._startup_recorded = False
        # own copy: prefix_cache=None resolves below, and resolving in the
        # caller's dataclass would make a reused config look explicitly set
        engine_config = dataclasses.replace(engine_config)
        self.config = engine_config
        self.tokenizer = tokenizer
        if tokenizer is not None and tokenizer.vocab_size > model_config.vocab_size:
            # loud, not silent: ids past the embedding table clamp inside
            # jit (garbage lookups) and crash the host-side prompt mask
            raise ValueError(
                f"tokenizer vocab ({tokenizer.vocab_size}) exceeds model "
                f"vocab ({model_config.vocab_size}); ids past the embedding "
                "table would silently clamp under jit")
        self._mlabel = metrics_label
        # every lifecycle stamp goes through this injectable clock, so the
        # FakeClock chaos suite asserts exact TTFT/ITL/queue-wait values
        # (docs/observability.md); real time is the production default
        if cpu_clock is None and clock is None:
            cpu_clock = time.thread_time
        self._clock = clock or MONOTONIC
        # bounded ring of finished timelines + rolling percentile windows
        # behind GET /admin/telemetry
        self.telemetry = TimelineRecorder()
        # the loop's phases per dispatch and their parts
        # (docs/observability.md): host spans on the profiler's clock while
        # a capture runs, the engine_dispatch_{phase,part}_seconds_total
        # and engine_dispatch_phase_cpu_seconds_total counters, one ring
        # row each
        self._phases = DispatchPhases(
            self._clock, annotate=jax.profiler.TraceAnnotation,
            cpu_clock=cpu_clock)
        self._phase_seconds = [
            ENGINE_DISPATCH_PHASE_SECONDS.labels(
                model_name=metrics_label, phase=phase)
            for phase in (*PHASES, "wait_lag")]
        self._phase_cpu_seconds = [
            ENGINE_DISPATCH_PHASE_CPU_SECONDS.labels(
                model_name=metrics_label, phase=phase)
            for phase in PHASES]
        self._part_seconds = [
            ENGINE_DISPATCH_PART_SECONDS.labels(
                model_name=metrics_label, part=part)
            for part in PARTS]
        self._uploads = ENGINE_DISPATCH_UPLOADS.labels(
            model_name=metrics_label)
        # a compile of something that is none of the engine's programs,
        # after warm-up, is logged once (_paused)
        self._warned_other_compile = False
        # deferred delivery (the `mixed` path): what a routed dispatch's
        # tokens owe their streams, in the order they were produced, until
        # the next dispatch is launched; never left standing across an
        # await of the loop
        self._undelivered: "deque[_Delivery]" = deque()
        self._deliveries = [
            ENGINE_DISPATCH_DELIVERIES.labels(
                model_name=metrics_label, when=when)
            for when in DELIVERIES]
        # engine_sampler_dispatches_total by the path a dispatch's batch
        # takes through the sampler (sampling.SAMPLER_PATHS)
        self._sampler_dispatches = {
            path: ENGINE_SAMPLER_DISPATCHES.labels(
                model_name=metrics_label, sampler_path=path)
            for path in SAMPLER_PATHS}
        # engine_dispatch_shape_total by how a mixed dispatch's (T, W) pair
        # was found among the loaded ones (shapes.FITS)
        self._dispatch_fits = {
            fit: ENGINE_DISPATCH_SHAPE.labels(model_name=metrics_label, fit=fit)
            for fit in FITS}
        # when the fetch worker last had a result on the host
        self._fetch_ready_at: Optional[float] = None
        # checkpoints carry this as model_name; resume_generation rejects a
        # mismatch.  Distinct from the metrics label so DP sub-engines
        # (engine-dp0, engine-dp1, ...) share one weights identity and a
        # checkpoint from any of them resumes on any other
        self._ckpt_label = checkpoint_label or metrics_label
        # which (T, W) program a dispatch runs in: engine/shapes.py decides,
        # every planner below asks it
        self._shapes = DispatchShapes.of(
            model_config, engine_config, jax.default_backend())
        # what this model cannot be served with is refused here, by name; the
        # engine steps through `mixed` where topology and sizes admit it
        resolve_serving(
            model_config, engine_config, shapes=self._shapes,
            lora=bool(lora_adapters or lora_stacked))
        self._use_mixed = (
            self._shapes.admits_mixed(engine_config)
            if engine_config.use_ragged is None
            else bool(engine_config.use_ragged))
        if engine_config.prefix_cache is None:
            engine_config.prefix_cache = True
        self.mesh = shd.create_mesh(
            tp=engine_config.tp, dp=1, sp=engine_config.sp,
            pp=engine_config.pp, devices=devices,
        )
        self._base_rng = jax.random.PRNGKey(rng_seed)
        self._step_counter = 0

        if engine_config.weight_quant not in ("none", "int8"):
            raise ValueError(f"weight_quant={engine_config.weight_quant!r}")
        _weights_t0 = time.perf_counter()
        if params is None:
            params = shd.init_params_on_mesh(
                model_config, jax.random.PRNGKey(1), self.mesh,
                weight_quant=engine_config.weight_quant,
            )
        elif engine_config.weight_quant == "int8":
            from ..models.quant import is_quantized, quantize_params

            if not any(
                is_quantized(v) for v in params["layers"][0].values()
                if isinstance(v, dict)
            ):
                params = quantize_params(params, model_config)
        # multi-adapter LoRA stacks load BEFORE any pp stacking so the
        # adapter tensors ride the same stage-sharded layer pytree
        self.adapter_ids: Dict[str, int] = {}
        lora_layer_stacks = None
        if lora_adapters or lora_stacked:
            from ..models import lora as lora_mod

            if lora_stacked is not None:
                self.adapter_ids, lora_layer_stacks = lora_stacked
            else:
                self.adapter_ids, lora_layer_stacks = lora_mod.stack_adapters(
                    lora_adapters, model_config.n_layers, dtype=model_config.dtype
                )
            logger.info("LoRA adapters loaded: %s", sorted(self.adapter_ids))
        if engine_config.pp > 1:
            if lora_layer_stacks is not None:
                # the stage-sharded stack needs UNIFORM adapter coverage:
                # every layer must carry the same projection set or the
                # layer pytrees cannot stack
                shape_sets = {
                    tuple(sorted(
                        (proj, tuple(t["A"].shape), tuple(t["B"].shape))
                        for proj, t in stack.items()
                    ))
                    for stack in lora_layer_stacks
                }
                if len(shape_sets) != 1:
                    # covers both ragged projection sets AND layer-varying
                    # ranks (PEFT rank_pattern) — jnp.stack would otherwise
                    # die with an opaque shape error
                    raise NotImplementedError(
                        "pp>1 requires every layer to share one LoRA "
                        "projection set and rank; got differing per-layer "
                        f"shapes: {sorted(shape_sets)[:2]}"
                    )
                for layer, stack in zip(params["layers"], lora_layer_stacks):
                    layer["lora"] = stack
            # stage-sharded layers: the per-layer list stacks into one
            # pytree with a leading L axis placed on the pipe mesh axis,
            # each leaf keeping its megatron TP spec on the trailing dims;
            # embed/final_norm/lm_head stay pipe-replicated with their
            # usual TP shardings
            params = llama.stack_layer_params(params)
            all_flat = shd.param_pspecs(model_config)
            flat_specs = shd.expand_quant_specs(
                {k: v for k, v in params.items() if k != "layers"},
                {k: v for k, v in all_flat.items() if k != "layers"},
            )
            layer_specs = shd.stacked_layer_pspecs(
                model_config, params["layers"],
                layer_specs=all_flat["layers"][0])
            if lora_layer_stacks is not None:
                layer_specs["lora"] = jax.tree.map(
                    lambda s: jax.sharding.PartitionSpec(shd.PIPE_AXIS, *s),
                    lora_mod.lora_pspecs(lora_layer_stacks[0]),
                    is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec),
                )
            specs = dict(flat_specs, layers=layer_specs)
            self.params = jax.tree.map(
                lambda arr, spec: jax.device_put(
                    arr, shd.named(self.mesh, spec)),
                params, specs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
        else:
            self.params = shd.shard_params(params, model_config, self.mesh)
            # the held experts' tensors as the grouped matmul wants them to
            # lie (models/moe.device_layout: by their shape; most layers come
            # back as they are), one layer at a time, the tensors it replaces
            # let go before the next layer's are made
            params = None
            layers = self.params["layers"]
            for i in range(len(layers)):
                layers[i] = jax.block_until_ready(device_layout(layers[i]))

        # multi-adapter LoRA (pp==1 path): stacked [n_adapters, ...]
        # tensors attached per layer; a per-slot id selects at runtime
        # (models/lora.py).  Under pp the stacks were folded into the
        # stage-sharded pytree above.
        if lora_layer_stacks is not None and engine_config.pp == 1:
            for i, stack in enumerate(lora_layer_stacks):
                if not stack:
                    continue
                lspecs = lora_mod.lora_pspecs(stack)
                self.params["layers"][i]["lora"] = jax.tree.map(
                    lambda arr, spec: jax.device_put(
                        arr, jax.sharding.NamedSharding(self.mesh, spec)
                    ),
                    stack,
                    lspecs,
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
                )

        # device placement done: everything from the quantize/init above
        # through the LoRA stacks landing on device is the weights phase
        self.startup_phases["weights"] = time.perf_counter() - _weights_t0

        # what a lane owns, per layer kind (engine/kvcache.StateLayout): the
        # pool's pages belong to the layers that write paged K/V
        layout = StateLayout.of(
            model_config, engine_config.page_size, engine_config.num_pages,
            engine_config.max_batch_size, engine_config.dtype)
        self.state_layout = layout
        cache_cfg = KVCacheConfig(
            n_layers=len(layout.paged_layers),
            n_kv_heads=layout.kv_heads,
            head_dim=layout.head_dim,
            page_size=engine_config.page_size,
            num_pages=engine_config.num_pages,
            max_pages_per_seq=engine_config.max_pages_per_seq,
            dtype=engine_config.dtype,
            n_passes=model_config.n_passes,
        )
        self.cache_config = cache_cfg
        if engine_config.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"unknown kv_quant {engine_config.kv_quant!r}; supported: none, int8"
            )
        stacked_shape = (  # pp > 1 only, which a looped model refuses
            cache_cfg.cache_rows, cache_cfg.num_pages, 2,
            cache_cfg.n_kv_heads, cache_cfg.page_size, cache_cfg.head_dim,
        )
        if model_config.is_hybrid:
            self.kv_pages = layout.init_state(
                shd.named_canonical(self.mesh, jax.sharding.PartitionSpec()))
            logger.info(
                "per-lane state: %s B a token over %d pages of %d "
                "tokens; a lane holds window K/V %d B, ssm %d B, conv %d B",
                layout.bytes_per_token(), layout.num_pages, layout.page_size,
                *(layout.lane_bytes()[k] for k in ("window_kv", "ssm", "conv")))
        elif engine_config.kv_quant == "int8":
            if engine_config.use_pallas:
                # fail at init, not inside the jitted decode trace where the
                # error would kill the engine loop for all traffic
                raise NotImplementedError(
                    "the pallas kernels do not compile over int8 KV pages "
                    "(docs/kernels.md); use kv_quant=int8 with use_pallas "
                    "None/False"
                )
            if engine_config.pp > 1:
                # stacked quantized cache: an (int8 pages, scales) tuple,
                # layer axis on pipe, KV heads on model
                self.kv_pages = (
                    device_filler(
                        shd.named(self.mesh, shd.stacked_kv_pages_pspec()),
                        stacked_shape, jnp.int8, 0)(),
                    device_filler(
                        shd.named(self.mesh, jax.sharding.PartitionSpec(
                            shd.PIPE_AXIS, None, None, shd.MODEL_AXIS,
                            None)),
                        stacked_shape[:-1], jnp.float32, 1)(),
                )
            else:
                pages = init_kv_pages(
                    dataclasses.replace(cache_cfg, dtype="int8"),
                    shd.kv_pages_sharding(self.mesh))
                scale_sharding = shd.named_canonical(
                    self.mesh,
                    jax.sharding.PartitionSpec(None, None, shd.MODEL_AXIS, None),
                )
                scales = init_kv_scales(cache_cfg, scale_sharding)
                self.kv_pages = list(zip(pages, scales))
        elif engine_config.pp > 1:
            # pipeline mode: one stacked [L, ...] array, layer axis on pipe
            # NOT canonicalized (unlike the flat cache): the staged pp
            # shard_map needs the explicit full-rank spec on this jax, and
            # pp keeps its benign one-time settle retrace anyway
            self.kv_pages = device_filler(
                shd.named(self.mesh, shd.stacked_kv_pages_pspec()),
                stacked_shape, jnp.dtype(cache_cfg.dtype), 0)()
        else:
            self.kv_pages = init_kv_pages(
                cache_cfg, shd.kv_pages_sharding(self.mesh))
        self.allocator = PageAllocator(cache_cfg.num_pages)

        B = engine_config.max_batch_size
        self._slots: List[_Slot] = [_Slot() for _ in range(B)]
        self._waiting: List[_QueuedRequest] = []
        self._wake = asyncio.Event()
        self._detached_lock = asyncio.Lock()
        self._detached_queue: List[tuple] = []
        self._detached_task: Optional[asyncio.Task] = None
        self._stopped = False
        # lifecycle (kserve_tpu/lifecycle): once draining, new admission is
        # refused (503 upstream) and drain() checkpoints whatever the drain
        # budget cannot finish; resume_count/checkpointed_count are the
        # test/observability counters behind the prometheus metrics
        self._draining = False
        self.resume_count = 0
        self.checkpointed_count = 0
        # requests popped from _waiting by an in-flight _admit_batch; the
        # crash handler fails these too (they are otherwise unreachable)
        self._admitting: List[tuple] = []
        self._task: Optional[asyncio.Task] = None
        # set by the run loop's crash handler: a dead loop can never serve
        # again, so admission refuses new work (instead of queueing it for
        # nobody) and the owning server — which sets on_loop_crash — ends
        # the process instead of idling live-but-never-ready
        self._loop_error: Optional[BaseException] = None
        self.on_loop_crash = None
        self._pipeline_busy = False
        self._deferred_free: List[int] = []
        # hierarchical KV store (kserve_tpu/kvstore, docs/kv_hierarchy.md):
        # host-RAM/disk tiers take preempted-sequence spills AND demoted
        # prefix-cache pages; the content-addressed persistent layer keeps
        # prefix pages across restarts.  Clock-injectable so sim spill
        # traffic stays byte-identical per seed.
        self._kv_store = None
        if engine_config.kv_offload == "host" or engine_config.kv_persist_dir:
            from ..kvstore import HierarchicalKVStore, KVStoreConfig

            self._kv_store = HierarchicalKVStore(KVStoreConfig(
                host_bytes=int(engine_config.kv_offload_gib * (1 << 30)),
                disk_bytes=int(engine_config.kv_offload_disk_gib * (1 << 30)),
                disk_dir=engine_config.kv_offload_dir,
                policy=engine_config.kv_offload_policy,
                persist_dir=engine_config.kv_persist_dir,
            ), clock=self._clock)
        # async prefix page-in / persist write-through bookkeeping: tasks
        # are tracked so stop() can cancel them, and in-flight persist
        # digests are deduplicated across admission passes
        self._pagein_tasks: set = set()
        self._persisting: set = set()
        # cross-replica page fabric (kvstore/peer.py): set_peer_client
        # attaches the verified peer fetch path; None = local tiers only
        self._peer_client = None
        self.preemption_count = 0
        # wedge detection: device fetches run on a DAEMON worker with a
        # deadline; a timeout flips `wedged` (liveness).  Daemon, not a
        # ThreadPoolExecutor: its non-daemon workers are joined at
        # interpreter exit, so one stuck fetch would hang process shutdown —
        # the exact failure mode this exists to escape.  The simulator
        # injects a synchronous fetcher instead (thread handoff order is
        # the one nondeterminism a deterministic fleet sim cannot keep).
        self._fetcher = fetcher if fetcher is not None else _DeadlineFetcher()
        self._wedged = False
        # chaos seam (resilience/faults.py): a FaultPlan whose "wedge"
        # specs targeting "engine.fetch" the device-fetch path honors
        self.fault_plan = None
        # gray-failure watchdog (engine/watchdog.py, docs/resilience.md):
        # seated-or-queued work with no forward motion past the stall
        # budget flips readiness and self-drains with checkpoints.  The
        # owning server (or SimReplica) hooks on_stall_confirmed to flip
        # its ReplicaLifecycle so readiness probes go red too.
        self._watchdog = None
        self.on_stall_confirmed = None
        if engine_config.watchdog:
            from .watchdog import EngineWatchdog, WatchdogConfig

            self._watchdog = EngineWatchdog(
                WatchdogConfig(
                    interval_s=engine_config.watchdog_interval_s,
                    suspect_after_s=engine_config.watchdog_suspect_s,
                    confirm_after_s=engine_config.watchdog_confirm_s,
                    task_stall_s=engine_config.watchdog_task_stall_s,
                    salvage_grace_s=engine_config.watchdog_salvage_grace_s,
                ),
                clock=self._clock,
                busy=self._has_live_work,
                on_confirmed=self._stall_confirmed,
                tasks=lambda: self._pagein_tasks,
            )
        # prefix cache (engine/prefix_cache.py): chained page key -> page
        # id, LRU-evicted on pressure; holds one allocator ref per page.
        # Evictions are offered to the hierarchical store's demote seam
        # instead of being dropped (HBM -> host RAM -> disk -> persist).
        from .prefix_cache import PrefixCache

        self._prefix_cache = PrefixCache(
            engine_config.page_size, engine_config.prefix_cache,
            self.allocator,
            demote_cb=self._demote_prefix_pages,
        )
        # device-resident [B, V] penalty state; row-level updates on batch
        # composition changes (dirty_rows None => full rebuild needed)
        self._penalty_counts = None
        self._penalty_prompt = None
        self._penalty_dirty_rows: Optional[set] = None
        # deterministic admission stamp: a strictly-increasing sequence the
        # preemption policy orders victims by (newest-first).  A sequence,
        # not a wall/virtual clock read — two admissions inside one virtual
        # instant must still have a defined age order or the simulator's
        # preemption choice (and therefore its whole report) would hinge on
        # a tie-break
        self._admission_seq = 0.0
        # speculative decoding + dense decode packing (docs/kernels.md):
        # spec_decode_k=None keeps today's mixed-only behavior; an int K
        # adds the decode-only `mixed_decode` program — dense (K+1)-token
        # slices, on-device draft/verify/accept, depth-2 chaining
        self._spec_k = spec_k = engine_config.spec_decode_k
        # worst-case per-lane advance of one dispatch: every round accepts
        # all K drafts plus the bonus token.  Page growth and the
        # predictable-finish chain gate both plan against it.
        self._max_step_advance = self._shapes.steps * (
            (spec_k or 0) + 1 if spec_k is not None else 1)
        # hard per-lane kv ceiling: a dense round needs a full (K+1)-token
        # write window, so a lane within K tokens of this cap can NEVER
        # run another dense round — _step_mixed hands such batches to the
        # plain mixed path (1 token/step, same tokens) for the final
        # stretch instead of livelocking on capacity-skipped dispatches
        self._dense_lane_cap = min(
            engine_config.max_model_len,
            engine_config.max_pages_per_seq * engine_config.page_size)
        # per-lane bigram draft table ([B, V] int32 on device, -1 = unseen)
        # + the dirty-row set driving host re-seeding from prompt +
        # generated tokens on every batch-composition change (None = all)
        self._draft_table = None
        self._draft_dirty: Optional[set] = None
        self.spec_stats = {"drafted": 0, "accepted": 0, "rejected": 0}
        # per-step mixed composition (prefill-token vs decode-token counts)
        # — exported via ENGINE_STEP_BATCH_COMPOSITION and inspectable by
        # tests/the telemetry endpoint
        self.last_step_composition: Dict[str, int] = {}
        self._build_compiled(compiled_programs)
        self._dense_ok = (
            self._use_mixed
            and self._spec_k is not None
            and self._mixed_decode_fn is not None
        )
        if self._spec_k is not None and not self._dense_ok:
            logger.info(
                "spec_decode_k=%s set but the program set has no "
                "mixed_decode; dense/speculative stepping disabled",
                self._spec_k)
        if self._mixed_fn is None and self._use_mixed:
            # build_compiled builds `mixed` wherever engine/limits.py admits
            # the regime: only an injected program set can lack it
            raise NotImplementedError(
                "the compiled program set has no `mixed` program "
                "(a pre-ragged stub)")
        # what this replica was BUILT with — dispatch regime and, per
        # program family, the attention implementation — logged at start
        # and served on /v1/internal/scheduler/state, so a TPU replica
        # running an `*_xla` correctness path is visible, not inferred
        from ..ops.attention import describe_attention_dispatch

        self.dispatch_report = {
            "regime": "mixed" if self._use_mixed else "legacy",
            "attention": describe_attention_dispatch(
                model_config, engine_config, jax.default_backend()),
            "shapes": self._shapes.published(),
        }
        # what a launch runs, counted for the roofline readers
        self._work = DispatchWork(
            model_config, layout, self.dispatch_report["attention"],
            metrics_label, wrote=self._phases.wrote)
        self._set_state_gauges()
        # what the pool is made of does not change while the engine lives
        ENGINE_KV_PAGES_TOTAL.labels(model_name=metrics_label).set(
            layout.num_pages - 1)
        ENGINE_KV_TOKEN_BYTES.labels(model_name=metrics_label).set(
            layout.token_bytes())

    # ---------------- compiled programs ----------------

    def _next_admission_seq(self) -> float:
        self._admission_seq += 1.0
        return self._admission_seq

    def _build_compiled(self, override=None):
        """Jit the device programs (engine/compiled.py) and bind them under
        the historical attribute names the loop dispatches through.
        `override` (the simulator's stub seam) supplies a pre-built program
        set with the same attribute surface instead.

        With config.aot_cache_dir set, programs build as persistent AOT
        executables (engine/aot_cache.py) and every entry already on disk
        for this config digest is deserialized NOW — a warm start reaches
        its first request with zero traces, zero XLA compiles."""
        self._aot_cache = None
        if override is not None:
            p = override
        else:
            from .compiled import build_compiled

            cache = None
            if self.config.aot_cache_dir:
                from .aot_cache import AOTExecutableCache

                try:
                    cache = AOTExecutableCache(
                        self.config.aot_cache_dir, self.model_config,
                        self.config, self.mesh, label=self._mlabel,
                    )
                except OSError as exc:
                    # an unwritable cache volume must not take down the
                    # replica — it degrades to today's compile-on-start
                    logger.warning(
                        "aot-cache-disabled dir=%s error=%s",
                        self.config.aot_cache_dir,
                        f"{type(exc).__name__}: {exc}")
            if self.config.spec_decode_k is not None and cache is not None:
                # spec_decode_k is deliberately NOT in the AOT cache key
                # until hardware-validated: a spec engine sharing a
                # non-spec digest would load stale executables, so the
                # persistent cache is disabled outright for spec engines
                # (they compile on start like pre-AOT replicas)
                logger.info(
                    "aot-cache-disabled: spec_decode_k=%s is not part of "
                    "the AOT cache key yet", self.config.spec_decode_k)
                cache = None
            p = build_compiled(
                self.model_config, self.config, self.mesh, aot_cache=cache,
                spec_k=self.config.spec_decode_k)
            self._aot_cache = cache
            if cache is not None:
                loaded = sum(
                    prog.preload()
                    for prog in (
                        getattr(p, f.name)
                        for f in dataclasses.fields(type(p))
                    )
                    if prog is not None and hasattr(prog, "preload")
                )
                logger.info(
                    "aot-cache ready: digest=%s preloaded=%d executables "
                    "(%.3fs)", cache.digest, loaded,
                    cache.stats.aot_load_s)
        self._prefill_fn = p.prefill
        self._prefill_lp_fn = p.prefill_lp
        self._prefill_chunk_fn = p.prefill_chunk
        self._sample_first_fn = p.sample_first
        self._sample_first_lp_fn = p.sample_first_lp
        self._decode_fn = p.decode
        self._decode_lp_fn = p.decode_lp
        self._decode_penalized_fn = p.decode_penalized
        self._decode_penalized_lp_fn = p.decode_penalized_lp
        self._inject_fn = p.inject
        self._inject_q_fn = p.inject_q
        # the unified ragged program: absent on pp / sp builds, which step
        # through the legacy programs; an injected program set that lacks it
        # where the engine steps through it is refused (__init__)
        self._mixed_fn = getattr(p, "mixed", None)
        # the (T, W) pairs `mixed` is loaded in, which its planner fits a
        # dispatch to (shapes.LoadedPairs): what the AOT cache preloaded
        # above, then every pair a launch runs in.  Arguments 1 and 4 of
        # `mixed` are the tokens' buffer [3, T] and the page table [B, W]
        # (shapes.MixedLayout, _step_mixed's call)
        preloaded = getattr(self._mixed_fn, "loaded_shapes", None)
        self._loaded = LoadedPairs(
            (tokens[1], table[1])
            for tokens, table in (preloaded(1, 4) if preloaded else ()))
        # dense/speculative decode-only program (docs/kernels.md); present
        # only when spec_decode_k is configured (stubs included)
        self._mixed_decode_fn = getattr(p, "mixed_decode", None)

    # ---------------- public API ----------------

    async def start(self):
        if self._task is None:
            pauses.watch(self._paused)
            self._task = asyncio.create_task(self._run_loop())
            if self._watchdog is not None:
                self._watchdog.start()
            logger.info(
                "LLM engine started: slots=%d pages=%d page_size=%d tp=%d "
                "dispatch=%s",
                self.config.max_batch_size, self.config.num_pages,
                self.config.page_size, self.config.tp, self.dispatch_report,
            )
            warmup = self.config.aot_warmup
            if warmup is None:
                warmup = self._aot_cache is not None
            if warmup and not self._stopped:
                await self._aot_warmup()
            self._record_startup_ready()

    async def _aot_warmup(self):
        """Drive one tiny generation per prefill bucket through the REAL
        serving loop before the replica turns ready, so the program
        signatures those generations reach are compiled (cold start — and
        persisted to the AOT cache) or deserialized (warm start) ahead
        of the first real request.  Driving generate() instead of
        hand-building abstract signatures means warmup can never drift
        from what the scheduler actually dispatches.

        The `mixed` pairs loaded here are where a settled engine runs a
        dispatch whose own pair is not loaded: padded into the smallest of
        them that holds it (shapes.LoadedPairs)."""
        params = SamplingParams(
            max_tokens=min(4, max(1, self._shapes.steps)),
            temperature=0.0, ignore_eos=True,
        )
        for bucket in self._shapes.token_buckets:
            n = min(bucket, self.config.max_model_len - params.max_tokens)
            if n <= 0:
                continue
            # a failure here (a compile the backend refuses) propagates out
            # of start(): the loop that raised it is dead, and a replica
            # that cannot run its programs must not turn ready
            async for _ in self.generate(
                [1] * n, params, request_id=f"aot-warmup-{bucket}"
            ):
                pass
        # warmup generations are not traffic: give the telemetry ring a
        # clean start (prometheus counters do keep the handful of warmup
        # observations — documented in docs/coldstart.md)
        self.telemetry = TimelineRecorder()

    def _record_startup_ready(self) -> None:
        """Stamp the ready phase and export every startup phase once
        (engine_startup_seconds — docs/coldstart.md)."""
        if self._startup_recorded:
            return
        self._startup_recorded = True
        if self._aot_cache is not None:
            s = self._aot_cache.stats
            self.startup_phases["trace"] = s.trace_s
            self.startup_phases["compile"] = s.compile_s
            self.startup_phases["aot_load"] = s.aot_load_s
        self.startup_phases["ready"] = (
            time.perf_counter() - self._construct_t0
            + self.startup_external_s)
        for phase, seconds in self.startup_phases.items():
            observe_startup_phase(self._mlabel, phase, seconds)

    async def stop(self):
        self._stopped = True
        self.stop_watchdog()
        self._wake.set()
        # fail queued-but-unseated requests NOW, before waiting on the loop
        # task: their asyncio queues would otherwise never see another put
        # and the consumer side would hang forever (a stop mid-drain leaves
        # exactly these behind)
        self._fail_waiting(lambda req: RuntimeError(
            f"engine stopped before request {req.request_id} was seated"
        ))
        # fail queued detached-prefill waiters before cancelling the worker —
        # otherwise prefill-role HTTP handlers awaiting prefill_detached()
        # hang until client timeout
        pending, self._detached_queue = self._detached_queue, []
        for _, _, fut, _ in pending:
            if not fut.done():
                fut.set_exception(RuntimeError("engine stopped"))
        if self._detached_task is not None and not self._detached_task.done():
            self._detached_task.cancel()
            self._detached_task = None
        if self._task is not None:
            try:
                await asyncio.wait_for(self._task, timeout=5)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._task.cancel()
            self._task = None
        # the loop is down: fail whatever is still seated (and anything the
        # loop's final iteration re-queued) so no stream outlives the engine
        self._fail_waiting(lambda req: RuntimeError(
            f"engine stopped before request {req.request_id} was seated"
        ))
        for slot in self._slots:
            if slot.request_id is not None:
                self._evict_slot(slot, RuntimeError("engine stopped"))
        # page-in / persist write-through tasks park on the fetch worker;
        # cancel them before closing it so none awakens into a dead engine
        for task in list(self._pagein_tasks):
            task.cancel()
        self._pagein_tasks.clear()
        # close AFTER the loop task is done: an in-flight chunk draining
        # through _fetch must reach a live worker (close-first would stall
        # the drain a full step deadline, then false-flag a wedge)
        self._fetcher.close()
        if self._kv_store is not None:
            self._kv_store.close()
        pauses.unwatch(self._paused)

    def _paused(self, pause: str, seconds: float, what: str = "") -> None:
        """observability.pauses' watcher: the whole process stood still for
        `seconds` (a compile of something that is none of the engine's
        programs, `what` its name where JAX gives one; a pause of the
        collector); noted on the row of the iteration it fell in."""
        self._phases.paused(pause, seconds)
        if (pause == "other_compile" and self._startup_recorded
                and not self._warned_other_compile):
            self._warned_other_compile = True
            logger.warning(
                "a compile outside the engine's programs after warm-up: %s "
                "took %.3f s (engine_other_compile_seconds_total, the "
                "dispatch row's other_compile; logged once)",
                what or "<unnamed>", seconds)

    def _discard_resume_kv(self, req) -> None:
        """Release a queued request's spilled resume KV to the tier store
        (shared by every path that fails/checkpoints waiting requests)."""
        if (req.resume is not None and req.resume["kv"] is not None
                and self._kv_store is not None):
            self._kv_store.discard(req.resume["kv"])
            self._set_offload_gauges()

    def _fail_waiting(self, make_exc) -> None:
        """Fail every queued-but-unseated request with make_exc(req),
        releasing any spilled resume KV back to the tier store."""
        pending, self._waiting = self._waiting, []
        for req in pending:
            self._discard_resume_kv(req)
            req.queue.put_nowait(make_exc(req))
            self._record_terminal(req.timeline, "error")
        self._set_queue_gauge()

    @property
    def running(self) -> bool:
        return self._task is not None and not self._task.done()

    @property
    def wedged(self) -> bool:
        """True once a device fetch blew the step deadline (a wedged
        device); consumed by liveness so the pod restarts."""
        return self._wedged

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission (the EPP's primary load signal)."""
        return len(self._waiting)

    def scheduler_state(self, max_digests: int = 512) -> dict:
        """Snapshot for the EPP endpoint picker: live load plus the
        hottest prefix-cache digests (hex, most-recently-used last) so
        the picker can route prefix-sharing requests back here.  Parity:
        the role the GIE EPP's metrics scrape plays for the reference
        (ref llmisvc/scheduler.go:73-521)."""
        digests = self._prefix_cache.hottest_digests(max_digests)
        state = {
            "queue_depth": self.queue_depth,
            # seated generations: the "work already admitted" half of the
            # autoscaler's load signal (queue_depth is the waiting half)
            "inflight": sum(
                1 for s in self._slots if s.request_id is not None),
            "free_pages": self.allocator.free_pages,
            "page_size": self.config.page_size,
            # what the seated lanes hold, per kind of state
            "state": self._state_occupancy(),
            # the pool: pages held / in all, and what a token holds of it
            "cache": self._cache_report(),
            "running": self.running,
            "wedged": self._wedged,
            "prefix_digests": digests,
            # rolling TTFT/ITL percentile windows (observability ring):
            # previously internal to telemetry, surfaced here so the EPP —
            # and the autoscaler behind it — sees SLO pressure per replica
            "telemetry": self.telemetry.signal_windows(),
            "dispatch": {**self.dispatch_report, "shapes": {
                **self.dispatch_report["shapes"],
                "loaded": self._loaded.published()}},
            # where this replica's params and cache live, and how much of
            # each device they hold (None where the backend keeps no stats)
            "devices": [_device_row(d) for d in self.mesh.devices.flat],
        }
        if self._spec_k is not None and self._spec_k > 0:
            # speculative-decoding block (docs/kernels.md): lifetime
            # draft/accept tallies — accepted/drafted is this replica's
            # live acceptance rate, the signal a drafter regression
            # surfaces on before it surfaces as tok/s
            state["spec"] = dict(self.spec_stats)
        if self._watchdog is not None:
            # gray-failure watchdog block (docs/resilience.md): the EPP's
            # fleet health scoring quarantines on stall_suspected /
            # stall_confirmed — the signal a liveness probe cannot see
            state["watchdog"] = self._watchdog.snapshot()
        if self._kv_store is not None:
            # hierarchical prefix-store block (docs/kv_hierarchy.md): the
            # resident-digest count + hit/miss/demotion/page-in tallies the
            # EPP fleet block re-exports — the first cut of item 2's global
            # prefix index.  adopted_hit_tokens counts hits served from
            # pages this process NEVER prefilled (the hot-wake proof).
            stats = self._kv_store.stats_dict()
            stats["adopted_hit_tokens"] = (
                self._prefix_cache.adopted_hits * self.config.page_size)
            state["prefix_store"] = stats
            # peer-servable digest set (kvstore/peer.py digest_set_wire):
            # the bounded, generation-stamped summary the EPP re-serves so
            # a woken replica knows WHICH peer holds which pages.  A
            # separate key, not a prefix_store field — the picker's
            # multi-model prefix_store merge sums numbers and would mangle
            # a nested digest list.
            wire = self._kv_store.resident_digest_wire()
            if wire is not None:
                state["peer_pages"] = wire
        if self._peer_client is not None:
            # peer-fetch outcomes + per-peer bad-page evidence: the
            # production channel health.note_bad_page rides (the EPP
            # diffs bad_pages counts per poll — scheduler/picker.py)
            state["peer"] = self._peer_client.snapshot()
        return state

    # -------- cross-replica page fabric (docs/kv_hierarchy.md) --------

    def set_peer_client(self, client) -> None:
        """Attach a kvstore.peer.PeerPageClient: _maybe_page_in then
        extends its longest-run search past the local tiers into
        peer-resident digests, and _page_in fetches + verifies them."""
        self._peer_client = client

    def read_peer_page(self, digest: bytes):
        """Wire-encoded page bytes for the REST page server, or None.
        Pure store read — never touches the engine loop."""
        if self._kv_store is None:
            return None
        return self._kv_store.read_peer_page(digest)

    @property
    def _offload_bytes(self) -> int:
        """Bytes currently parked in the offload tiers (host + disk).
        Returns to 0 once every spilled sequence has been restored or
        discarded — the observable the spill/restore tests assert on."""
        if self._kv_store is None:
            return 0
        return int(self._kv_store.host_used + self._kv_store.disk_used)

    def _set_offload_gauges(self) -> None:
        if self._kv_store is None:
            return
        ENGINE_KV_OFFLOAD_BYTES.labels(model_name=self._mlabel).set(
            self._kv_store.host_used)
        ENGINE_KV_DISK_BYTES.labels(model_name=self._mlabel).set(
            self._kv_store.disk_used)

    # ---------------- hierarchical prefix store (docs/kv_hierarchy.md) ----------------

    def _gather_pages_device(self, page_ids: List[int]) -> Dict[str, Any]:
        """Dispatch-only gather of whole KV pages into host-layout device
        arrays ({name: [L, P, ...]}).  Callers either fetch synchronously
        (the preemption spill) or hand the arrays to the fetch worker
        (persist write-through) — the dispatch itself never blocks, and
        the four cache layouts (plain/int8 x flat/pp-stacked) live in ONE
        place instead of one per caller."""
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        if self.config.kv_quant == "int8" and self.config.pp > 1:
            pages, scales = self.kv_pages
            return {"kv_q": pages[:, ids], "kv_s": scales[:, ids]}
        if self.config.kv_quant == "int8":
            return {
                "kv_q": self._gather_rows([q for q, _ in self.kv_pages], ids),
                "kv_s": self._gather_rows([s for _, s in self.kv_pages], ids),
            }
        if self.config.pp > 1:
            # stacked cache: one gather covers every stage's layers
            return {"kv": self.kv_pages[:, ids]}
        return {"kv": self._gather_rows(self.kv_pages, ids)}

    def _gather_rows(self, arrays: list, ids) -> jnp.ndarray:
        """Pages `ids` of the flat cache in the wire's layout
        [cache_rows, P, ...]: row u * n_layers + l is (pass u, layer l),
        which layer l's array holds at page id + u * num_pages
        (engine/kvcache.py; compiled._inject is the way back)."""
        cc = self.cache_config
        if cc.n_passes == 1:
            return jnp.stack([layer[ids] for layer in arrays])
        of_pass = pages_of_passes(ids, cc.n_passes, cc.num_pages)
        by_layer = jnp.stack([layer[of_pass] for layer in arrays])
        return jnp.swapaxes(by_layer, 0, 1).reshape(
            (cc.cache_rows,) + by_layer.shape[2:])

    def _demote_prefix_pages(self, evicted: List[tuple]) -> None:
        """PrefixCache eviction seam: gather the evicted pages' KV (one
        device gather + fetch) and demote them into the host/disk tiers
        keyed by their digest chain keys.  The fetch is SYNCHRONOUS by
        design — the allocator reuses these pages the moment the seam
        returns, so their contents must be captured first (the same
        contract as the preemption spill); the cost is bounded by the
        eviction burst.  Demotion is tiers-only (persist=False): the
        persistent layer is fed exclusively by persist-on-REUSE, so
        one-shot prompts being evicted can never grow the uncapped
        durable directory.  Content addressing makes re-demotion free:
        digests already resident below HBM skip the gather.  Skipped
        while a chained decode chunk is in flight (the gather would read
        a cache version the in-flight program is superseding) — those
        pages simply drop, the pre-store behavior, and a drop is a perf
        event never a correctness one."""
        store = self._kv_store
        if (store is None or not store.accepts_prefix_pages
                or self._pipeline_busy or self._stopped):
            return
        pairs = [(k, p) for k, p in evicted
                 if store.prefix_tier_of(k) is None]
        if not pairs:
            return
        dev = self._gather_pages_device([p for _, p in pairs])
        fetched = {name: self._fetch(v) for name, v in dev.items()}
        for i, (key, _) in enumerate(pairs):
            # contiguous copy, not a view: a view would pin the WHOLE
            # multi-page gather in host RAM while the tier accounts for
            # one page of it
            store.put_prefix(
                key,
                {name: np.ascontiguousarray(arr[:, i:i + 1])
                 for name, arr in fetched.items()},
                persist=False,
            )
        store.record_demotion(len(pairs))
        self._set_offload_gauges()

    def _count_prefix_hits(self, keys: List[bytes], hits: List[int]) -> None:
        """Admission served `hits` pages from the HBM prefix cache: count
        pages + tokens, and trigger the persist-on-reuse write-through —
        a HIT proves the prefix is shared, which is exactly the page
        worth keeping across restarts (one-shot prompts never reach the
        persistent layer, so it cannot thrash)."""
        if not hits:
            return
        self._prefix_cache.hits += len(hits)
        # adopted hits are counted HERE, per admission actually served —
        # counting inside lookup_run would tally every retried lookup of
        # a held request and inflate the hot-wake metric
        if keys:
            self._prefix_cache.count_adopted_hits(keys[:len(hits)])
        KV_PREFIX_HIT_TOKENS.labels(model_name=self._mlabel, tier="hbm").inc(
            len(hits) * self.config.page_size)
        if self._kv_store is not None and keys:
            self._maybe_persist_prefix(keys[:len(hits)], hits)

    def _track_task(self, coro) -> None:
        task = asyncio.get_running_loop().create_task(coro)
        # start stamp for the watchdog's task-stall accounting: a tracked
        # task alive past the stall budget is cancelled, not left pinning
        # the request it was supposed to unblock
        task._wd_started_s = self._clock.now()
        self._pagein_tasks.add(task)
        task.add_done_callback(self._pagein_tasks.discard)

    def _maybe_persist_prefix(self, keys: List[bytes],
                              pages: List[int]) -> None:
        store = self._kv_store
        if self._stopped:
            return
        need = [k for k in store.needs_persist(keys)
                if k not in self._persisting]
        if not need:
            return
        page_of = dict(zip(keys, pages))
        # the gather is DISPATCHED now, while the pages are live and
        # referenced; the blocking device->host read and the file writes
        # ride the fetch worker so decode never waits on them
        dev = self._gather_pages_device([page_of[k] for k in need])
        self._persisting.update(need)
        self._track_task(self._persist_pages(need, dev))

    async def _persist_pages(self, keys: List[bytes], dev: Dict) -> None:
        try:
            fetched = await self._fetcher.fetch_async(
                lambda: {k: np.asarray(v) for k, v in dev.items()},
                self.config.step_deadline_s)
            store = self._kv_store
            for i, key in enumerate(keys):
                store.put_prefix(
                    key,
                    {name: np.ascontiguousarray(arr[:, i:i + 1])
                     for name, arr in fetched.items()})
            self._set_offload_gauges()
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — persistence is an optimization;
            # the page stays HBM-resident and serving continues
            logger.exception("prefix persist write-through failed")
        finally:
            for key in keys:
                self._persisting.discard(key)

    def _maybe_page_in(self, req: "_QueuedRequest", keys: List[bytes],
                       n_hbm: int) -> bool:
        """Hierarchical-store admission hook: when a request's digest
        chain continues past its HBM-cached run into tier-resident pages,
        schedule an ASYNC page-in (tier/disk read on the fetch worker,
        one inject dispatch, adopt into the HBM cache) and hold the
        request back; decode keeps running under the upload, and the
        retried admission prefills only the still-uncached tail.  True =
        page-in pending, do not seat this request yet."""
        if req.pagein == "pending":
            return True
        if (req.pagein == "done" or self._kv_store is None
                or self._draining or self._stopped or len(keys) <= n_hbm):
            return False
        run = self._kv_store.longest_prefix_run(keys[n_hbm:])
        # peer leg (docs/kv_hierarchy.md "Cross-replica page serving"):
        # the longest-run search continues past the local tiers into
        # digests some OTHER replica advertises as persist-resident.
        # Chain contiguity is preserved — peer entries only ever extend
        # the local run's tail, and _page_in truncates back to the
        # longest VERIFIED prefix if a fetch fails mid-transfer.
        peer = self._peer_client
        if peer is not None:
            for digest in keys[n_hbm + len(run):]:
                if not any(u != peer.self_url
                           for u in peer.index.peers_for(digest)):
                    break
                run.append((digest, "peer"))
        # room for the incoming pages may come from evicting COLD cached
        # pages (which demote in turn — hierarchy rotation, not loss);
        # only a cache that stays full of hotter pages vetoes the page-in
        if not run or not self._prefix_cache.ensure_allocatable(len(run)):
            # nothing resident (or no headroom worth competing for):
            # remember the verdict so every admission retry is O(1)
            req.pagein = "done"
            return False
        req.pagein = "pending"
        self._track_task(self._page_in(req, run))
        return True

    async def _page_in(self, req: "_QueuedRequest", run: List[tuple]) -> None:
        """Upload one tier-resident prefix run back into device pages.
        The tier/disk reads happen off the event loop (fetch_async — the
        PR 5 seam, so decode overlaps the I/O); the device upload is the
        same inject scatter the P/D and spill-resume paths already
        dispatch, so no new program shape is traced and steady-state
        compile counts hold.  NO host syncs on this path: the inject is
        dispatch-only, nothing fetches its result (jaxlint
        pagein-host-sync guards exactly this)."""
        store = self._kv_store
        t0 = self._clock.now()
        try:
            # peer entries only ever sit at the tail (how _maybe_page_in
            # builds the run); the local head reads off the fetch worker,
            # the peer tail fetches over the verified fabric
            n_local = sum(1 for _, tier in run if tier != "peer")
            digests = [d for d, _ in run[:n_local]]
            peer_digests = [d for d, _ in run[n_local:]]

            def read():
                out = []
                for digest in digests:
                    got = store.get_prefix(digest)
                    if got is None:
                        break  # dropped/corrupt underneath us: truncate
                    out.append(got)
                return out

            try:
                payloads = await self._fetcher.fetch_async(
                    read, self.config.step_deadline_s)
            except (RuntimeError, TimeoutError):
                return  # engine stopping / fetcher closed
            if self._stopped or self._draining:
                return
            entries = []  # (digest, payload, source tier)
            for digest, got in zip(digests, payloads):
                if self._prefix_cache.contains_key(digest):
                    continue  # a concurrent page-in/prefill won the race
                entries.append((digest, got[0], got[1]))
            # peer leg: chain contiguity first — a truncated LOCAL run
            # means the peer tail no longer extends a verified prefix, so
            # drop it; otherwise fetch + verify page by page, truncating
            # at the first failure (mid-transfer peer death degrades to
            # the longest verified prefix run, never a failed admission)
            adopted_from_peer = []  # (digest, payload) for write-through
            if (peer_digests and self._peer_client is not None
                    and len(payloads) == len(digests)):
                for digest in peer_digests:
                    if self._stopped or self._draining:
                        return
                    if self._prefix_cache.contains_key(digest):
                        continue
                    payload = await self._peer_client.fetch_page(digest)
                    if payload is None:
                        break  # verify failure / partition / deadline
                    entries.append((digest, payload, "peer"))
                    adopted_from_peer.append((digest, payload))
            if self._stopped or self._draining:
                return
            if not entries or not self.allocator.can_allocate(len(entries)):
                return
            pages = self.allocator.allocate(len(entries))
            try:
                n = len(entries)
                bucket = self._shapes.width(n)
                ids = np.zeros((bucket,), np.int32)
                ids[:n] = pages

                def packed(name: str):
                    arr = np.concatenate(
                        [payload[name] for _, payload, _ in entries], axis=1)
                    out = np.zeros(
                        arr.shape[:1] + (bucket,) + arr.shape[2:], arr.dtype)
                    out[:, :n] = arr
                    return jnp.asarray(out)

                if "kv_q" in entries[0][1]:
                    self.kv_pages = self._inject_q_fn(
                        self.kv_pages, packed("kv_q"), packed("kv_s"),
                        jnp.asarray(ids))
                else:
                    self.kv_pages = self._inject_fn(
                        self.kv_pages, packed("kv"), jnp.asarray(ids))
                # the cache takes ownership of the freshly-allocated refs
                self._prefix_cache.adopt(
                    [(digest, page) for (digest, _, _), page
                     in zip(entries, pages)])
            except BaseException:
                self.allocator.free(pages)
                raise
            # write-through: a page fetched from a peer becomes locally
            # resident (tiers + persistent layer) so the NEXT wake in
            # this zone serves it without crossing the fabric again, and
            # this replica starts advertising it in its digest-set wire
            for digest, payload in adopted_from_peer:
                store.put_prefix(digest, payload)
            ps = self.config.page_size
            pages_by_tier: Dict[str, int] = {}
            for _, _, tier in entries:
                pages_by_tier[tier] = pages_by_tier.get(tier, 0) + 1
            tokens_by_tier = {t: c * ps for t, c in pages_by_tier.items()}
            store.record_pagein(pages_by_tier, tokens_by_tier)
            for tier, tokens in tokens_by_tier.items():
                KV_PREFIX_HIT_TOKENS.labels(
                    model_name=self._mlabel, tier=tier).inc(tokens)
            KV_PAGEIN_SECONDS.labels(model_name=self._mlabel).observe(
                self._clock.now() - t0)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — page-in is an optimization;
            # the held request re-prefills its whole tail instead
            logger.exception("prefix page-in failed")
        finally:
            req.pagein = "done"
            self._wake.set()

    def _set_queue_gauge(self) -> None:
        """THE queue-depth gauge writer.  Every mutation of _waiting calls
        this unconditionally — a conditional zeroing on one path (the r5
        fail-all bug) left the gauge stale after stop/drain whenever the
        queue happened to be empty at flush time."""
        ENGINE_QUEUE_DEPTH.labels(model_name=self._mlabel).set(
            len(self._waiting))

    def _set_composition_gauge(self, n_decoding: int) -> None:
        """Per-step batch composition: how the fixed decode slots split
        between decoding lanes, long-prompt prefills, and free capacity."""
        n_prefilling = sum(
            1 for s in self._slots
            if s.request_id is not None and s.prefilling is not None
        )
        g = ENGINE_STEP_BATCH_COMPOSITION
        g.labels(model_name=self._mlabel, role="decoding").set(n_decoding)
        g.labels(model_name=self._mlabel, role="prefilling").set(n_prefilling)
        g.labels(model_name=self._mlabel, role="free").set(
            self.config.max_batch_size - n_decoding - n_prefilling)

    def telemetry_snapshot(self) -> dict:
        """Rolling latency percentiles + recent request timelines (the
        GET /admin/telemetry payload; observability/introspection.py)."""
        snap = self.telemetry.snapshot(now=self._clock.now())
        snap["queue_depth"] = self.queue_depth
        snap["prefix_cache_hits"] = self.prefix_cache_hits
        snap["preemptions"] = self.preemption_count
        snap["state"] = self._state_occupancy()
        return snap

    def _record_terminal(self, tl: Optional[RequestTimeline],
                         reason: Optional[str]) -> None:
        """A timeline reached a terminal state: stamp it, feed the ring
        buffer, export the Prometheus series (finished generations only),
        and emit the engine child spans when a tracer is configured."""
        if tl is None or tl.recorded:
            return
        tl.recorded = True
        tl.mark_finished(self._clock.now(), reason)
        self.telemetry.observe(tl)
        if reason in ("stop", "length"):
            observe_request_timeline(self._mlabel, tl)
        from ..tracing import get_tracer

        tracer = get_tracer()
        if tracer is not None:
            try:
                emit_timeline_spans(tracer, tl)
            except Exception:  # noqa: BLE001 — telemetry must never kill the loop
                logger.exception("engine span emission failed")

    # ---------------- gray-failure watchdog (docs/resilience.md) ----------------

    def _has_live_work(self) -> bool:
        """Watchdog busy probe: anything seated or queued that should be
        making forward progress."""
        return bool(self._waiting) or any(
            s.request_id is not None for s in self._slots)

    def _note_progress(self) -> None:
        if self._watchdog is not None:
            self._watchdog.note_progress()

    def stop_watchdog(self) -> None:
        """Stop the watchdog tick task (engine.stop does this; the fleet
        simulator also calls it before draining its timer heap — a live
        watchdog re-arms a virtual timer every interval forever)."""
        if self._watchdog is not None:
            self._watchdog.stop()

    def _stall_confirmed(self, reason: str) -> None:
        """Watchdog confirm hook: flip readiness and self-drain with
        checkpoints.  The drain salvages every in-flight token through
        the PR 5 checkpoint path — each stream sees GenerationPreempted
        with a portable checkpoint and resumes on a healthy replica —
        instead of holding streams hostage until the client deadline or
        a kubelet SIGKILL loses everything."""
        if self.on_stall_confirmed is not None:
            try:
                self.on_stall_confirmed(reason)
            except Exception:  # noqa: BLE001 — a broken lifecycle hook must
                # not block the salvage drain below
                logger.exception("on_stall_confirmed hook failed")
        # tracked for stop() cancellation but deliberately NOT stamped
        # with _wd_started_s (_track_task would): the watchdog's task
        # reaper must never cancel its own salvage drain mid-checkpoint
        task = asyncio.get_running_loop().create_task(
            self._stall_self_drain())
        self._pagein_tasks.add(task)
        task.add_done_callback(self._pagein_tasks.discard)

    async def _stall_self_drain(self) -> None:
        deadline = Deadline.after(
            self.config.watchdog_salvage_grace_s, self._clock)
        try:
            checkpoints = await self.drain(
                deadline=deadline, clock=self._clock, reason="stall")
            logger.error(
                "watchdog self-drain complete: %d generation(s) "
                "checkpointed for migration", len(checkpoints))
        except Exception:  # noqa: BLE001 — the stall state is already
            # exported; a failed salvage must not crash the process
            logger.exception("watchdog self-drain failed")

    def _fetch_fault_check(self) -> None:
        """Shared fault seam for _fetch/_fetch_async — one copy, so a new
        fault kind can't be honored in one fetch path and not the other."""
        if self.fault_plan is not None:
            spec = self.fault_plan.decide("engine.fetch")
            if spec is not None and spec.kind == "wedge":
                raise self._wedge("injected wedge (fault plan)")
            if spec is not None and spec.kind == "replica_crash":
                # the process died: no wedge flag, no drain, no checkpoint —
                # the run loop's crash handler fails every in-flight stream
                # and clients must recover by retrying from scratch
                raise ReplicaCrashError("injected replica crash (fault plan)")

    def _wedge(self, msg: str) -> EngineWedgedError:
        self._wedged = True
        ENGINE_WEDGED.labels(model_name=self._mlabel).set(1)
        return EngineWedgedError(msg)

    def _fetch_timeout(self) -> EngineWedgedError:
        return self._wedge(
            f"device fetch exceeded step_deadline_s="
            f"{self.config.step_deadline_s}s — device wedged?"
        )

    def _fetch(self, x) -> np.ndarray:
        """Device->host fetch with the wedge deadline (see step_deadline_s)."""
        self._fetch_fault_check()
        try:
            return self._fetcher.fetch(
                lambda: np.asarray(x), self.config.step_deadline_s)
        except TimeoutError:
            raise self._fetch_timeout() from None

    async def _fetch_async(self, x, meanwhile=None) -> np.ndarray:
        """_fetch for the decode hot loop: AWAITS the device->host fetch so
        the event loop keeps serving (probes, /admin/drain, the drain
        budget loop, admission rejects) while the chunk computes — a
        blocking wait here starves every other coroutine for the full step
        duration.  Same fault seam and wedge mapping as _fetch.
        `meanwhile` runs on this thread once the fetch is with its worker,
        before the first await: the result is stamped when the device has
        it, however long `meanwhile` takes."""
        self._fetch_fault_check()
        wd = self._watchdog
        if wd is not None:
            wd.fetch_started()

        def fetch():
            out = np.asarray(x)
            # stamped by the worker: the loop's resumption less this is
            # how long a finished result waited for the event loop
            self._fetch_ready_at = self._clock.now()
            return out

        try:
            return await self._fetcher.fetch_async(
                fetch, self.config.step_deadline_s, meanwhile)
        except TimeoutError:
            raise self._fetch_timeout() from None
        finally:
            if wd is not None:
                wd.fetch_done()

    def generate(
        self,
        prompt_ids: List[int],
        params: SamplingParams,
        request_id: Optional[str] = None,
        adapter: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        """Submit a request; yields GenerationOutput per emitted token.
        `adapter` selects a loaded LoRA adapter by name (None = base).
        Validation runs HERE, not at first __anext__ — callers get their
        ValueError before any stream machinery is involved.  Prompts longer
        than max_prefill_len prefill in chunks (one compiled program per
        chunk bucket), so only the model length bounds them."""
        if len(prompt_ids) + params.max_tokens > self.config.max_model_len:
            raise ValueError(
                f"prompt+max_tokens exceeds max_model_len {self.config.max_model_len}"
            )
        check_request(self.model_config, params)
        self._check_accepting()
        deadline = self._admission_deadline()
        queue: asyncio.Queue = asyncio.Queue()
        rid = request_id or f"req-{time.monotonic_ns()}"
        req = _QueuedRequest(
            rid, list(prompt_ids), params, queue,
            adapter_id=self._resolve_adapter(adapter),
            deadline=deadline,
            timeline=self._new_timeline(rid, len(prompt_ids)),
        )
        return self._submit_and_stream(req)

    def _new_timeline(self, rid: str, n_prompt: int) -> RequestTimeline:
        """Stamp `received` NOW (the sync part of submit) and capture the
        caller's trace context so engine spans join the request's trace."""
        from ..tracing import current_trace_context

        tl = RequestTimeline(rid, model_name=self._mlabel,
                             trace=current_trace_context())
        tl.n_prompt_tokens = n_prompt
        tl.mark_received(self._clock.now())
        return tl

    def _check_accepting(self) -> None:
        """Admission gate for the lifecycle layer: a draining (or stopped)
        engine refuses new work synchronously — 503 + Retry-After upstream —
        instead of queueing it into a replica that is going away."""
        if self._loop_error is not None:
            raise RuntimeError(
                "engine loop crashed ("
                f"{type(self._loop_error).__name__}: {self._loop_error}); "
                "this replica cannot serve until it restarts"
            ) from self._loop_error
        if self._stopped or self._draining:
            raise ReplicaDrainingError(
                "engine is "
                + ("stopped" if self._stopped else "draining")
                + "; retry another replica"
            )

    def _admission_deadline(self):
        """The propagated request deadline (resilience contextvar), checked
        HERE so an already-dead budget is rejected synchronously — before
        any stream machinery, queue slot, or prefill work is committed."""
        deadline = current_deadline()
        if deadline is not None and deadline.expired:
            DEADLINE_REJECTED.labels(component="engine").inc()
            raise DeadlineExceededError(
                "request deadline expired before engine admission"
            )
        return deadline

    def _resolve_adapter(self, adapter: Optional[str]) -> int:
        if adapter is None:
            return -1
        if adapter not in self.adapter_ids:
            raise ValueError(
                f"unknown LoRA adapter {adapter!r}; loaded: "
                f"{sorted(self.adapter_ids) or 'none'}"
            )
        return self.adapter_ids[adapter]

    def generate_injected(
        self,
        prompt_ids: List[int],
        params: SamplingParams,
        kv_data: np.ndarray,  # [L, P, 2, n_kv, ps, d] from prefill_detached
        first_token: int,
        request_id: Optional[str] = None,
        adapter: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        """P/D disaggregation, decode side: admit a request whose prompt KV
        was computed by a prefill-role server.  The KV pages are scattered
        into this engine's cache and decoding starts at pos=len(prompt).
        Sync validation, async stream (see generate)."""
        if len(prompt_ids) + params.max_tokens > self.config.max_model_len:
            raise ValueError(
                f"prompt+max_tokens exceeds max_model_len {self.config.max_model_len}"
            )
        if self.config.kv_quant != "none":
            raise NotImplementedError(
                "KV injection over a quantized cache is not supported yet"
            )
        check_request(self.model_config, params, kv_wire=True)
        # validation runs HERE (sync), not at first __anext__: a shape
        # mismatch inside _run_loop would kill the engine for all traffic,
        # not just this request (version-skewed prefill peer)
        kv_data = np.asarray(kv_data)
        cc = self.cache_config
        expect = (
            cc.cache_rows, pages_needed(len(prompt_ids), cc.page_size), 2,
            cc.n_kv_heads, cc.page_size, cc.head_dim,
        )
        if tuple(kv_data.shape) != expect:
            raise ValueError(
                f"injected KV shape {tuple(kv_data.shape)} incompatible with "
                f"this engine's cache (expected {expect}); prefill peer and "
                "decode server must share model + page_size configuration"
            )
        self._check_accepting()
        deadline = self._admission_deadline()
        queue: asyncio.Queue = asyncio.Queue()
        rid = request_id or f"req-{time.monotonic_ns()}"
        req = _QueuedRequest(
            rid, list(prompt_ids), params, queue,
            kv_data=kv_data, first_token=int(first_token),
            adapter_id=self._resolve_adapter(adapter),
            deadline=deadline,
            timeline=self._new_timeline(rid, len(prompt_ids)),
        )
        return self._submit_and_stream(req)

    async def _submit_and_stream(self, req: "_QueuedRequest"):
        # re-check admission at ENQUEUE time: _check_accepting ran in the
        # sync part of the caller, but the first __anext__ can land after a
        # drain that already flushed _waiting for the last time — appending
        # now would strand this request forever (nothing re-flushes once
        # drain() has returned)
        self._check_accepting()
        self._waiting.append(req)
        self._set_queue_gauge()
        self._wake.set()
        try:
            while True:
                out = await req.queue.get()
                if isinstance(out, Exception):
                    raise out
                yield out
                if out.finished:
                    return
        finally:
            # client went away (generator closed / task cancelled): release
            # the slot and pages instead of decoding to max_tokens for nobody
            self.cancel(req.request_id)

    async def prefill_detached(
        self, prompt_ids: List[int], params: SamplingParams,
        adapter: Optional[str] = None,
    ) -> Tuple[int, np.ndarray]:
        """P/D disaggregation, prefill side: compute the prompt's KV and the
        first sampled token, extract the KV pages to host, release the pages.
        Returns (first_token, kv [L, P, 2, n_kv, ps, d]).

        Concurrent callers are micro-batched: a worker drains the queue and
        prefills up to `prefill_batch` prompts per compiled call, so a
        prefill-role server gets the same batching as co-located admission.

        Parity: the KV-connector role of the reference's disaggregated
        serving (workload_kvcache.go, llm_inference_service_types.go:105-110)
        with the transfer payload produced TPU-side in one gather."""
        check_request(self.model_config, params, kv_wire=True)
        if self.config.kv_quant != "none":
            raise NotImplementedError(
                "detached prefill (P/D transfer) over a quantized KV cache "
                "is not supported yet"
            )
        if params.logprobs is not None:
            # the P/D wire format carries (kv, first_token) only; the decode
            # role would be missing the first token's logprobs.  Explicit
            # here beats a silently-None first entry.
            raise ValueError(
                "logprobs is not supported with prefill/decode disaggregation"
            )
        n = len(prompt_ids)
        if n > self.config.max_prefill_len:
            raise ValueError(
                f"prompt length {n} exceeds max_prefill_len "
                f"{self.config.max_prefill_len}"
            )
        self._check_accepting()
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._detached_queue.append(
            (list(prompt_ids), params, fut, self._resolve_adapter(adapter))
        )
        if self._detached_task is None or self._detached_task.done():
            self._detached_task = asyncio.create_task(self._detached_worker())
        return await fut

    async def _detached_worker(self):
        """Drains queued detached prefills in micro-batches; exits when the
        queue empties (restarted lazily by the next request)."""
        while self._detached_queue and not self._stopped:
            batch = self._detached_queue[: self.config.prefill_batch]
            del self._detached_queue[: len(batch)]
            async with self._detached_lock:
                try:
                    self._prefill_detached_batch(batch)
                except Exception as e:  # noqa: BLE001 — fail the waiters, not the engine
                    for _, _, fut, _ in batch:
                        if not fut.done():
                            fut.set_exception(e)
            await asyncio.sleep(0)
        if self._stopped:
            # exiting on shutdown: fail anything enqueued after stop()'s drain
            pending, self._detached_queue = self._detached_queue, []
            for _, _, fut, _ in pending:
                if not fut.done():
                    fut.set_exception(RuntimeError("engine stopped"))

    def _prefill_detached_batch(self, batch) -> None:
        """One compiled prefill over up to prefill_batch detached prompts;
        per-row KV extraction; pages freed after extraction."""
        runnable = []
        for prompt_ids, params, fut, adapter_id in batch:
            n_pages = pages_needed(len(prompt_ids), self.config.page_size)
            if not self.allocator.can_allocate(n_pages):
                fut.set_exception(
                    MemoryError("KV pages exhausted for detached prefill")
                )
                continue
            runnable.append(
                (prompt_ids, params, fut, adapter_id,
                 self.allocator.allocate(n_pages))
            )
        if not runnable:
            return
        bucket = self._shapes.bucket(max(len(r[0]) for r in runnable))
        Bp = 1
        while Bp < len(runnable):
            Bp *= 2
        tokens = np.zeros((Bp, bucket), np.int32)
        valid = np.zeros((Bp,), np.int32)
        page_ids = np.zeros((Bp, self.config.max_pages_per_seq), np.int32)
        adapter_arr = np.full((Bp,), -1, np.int32)
        params_list = [SamplingParams() for _ in range(Bp)]
        for j, (prompt_ids, params, _, adapter_id, pages) in enumerate(runnable):
            n = len(prompt_ids)
            tokens[j, :n] = prompt_ids
            valid[j] = n
            page_ids[j, : len(pages)] = pages
            adapter_arr[j] = adapter_id
            params_list[j] = params
        state = SamplingState.from_params(params_list)
        rng = jax.random.fold_in(self._base_rng, self._next_step())
        try:
            self._work.forward(1, legacy_prefill=True)
            first, self.kv_pages = self._prefill_fn(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(valid),
                self.kv_pages,
                jnp.asarray(page_ids),
                state,
                rng,
                jnp.asarray(adapter_arr),
            )
            first_np = self._fetch(first)
            for j, (prompt_ids, _, fut, _, pages) in enumerate(runnable):
                ids = jnp.asarray(np.asarray(pages, np.int32))
                # deadline-guarded: this is the engine's LARGEST device->
                # host copy — a device wedge mid-DMA must trip liveness,
                # not hang the prefill-role handlers forever
                if self.config.pp > 1:
                    # stacked cache: one cross-stage gather; the wire
                    # payload layout ([L, P, 2, nkv, ps, d]) is identical,
                    # so prefill and decode tiers may run DIFFERENT
                    # pp/tp topologies
                    kv = self._fetch(self.kv_pages[:, ids])
                else:
                    kv = self._fetch(self._gather_rows(self.kv_pages, ids))
                if not fut.done():
                    fut.set_result((int(first_np[j]), kv))
        finally:
            for *_, pages in runnable:
                self._free_pages(pages)

    def cancel(self, request_id: str) -> None:
        kept = []
        for r in self._waiting:
            if r.request_id != request_id:
                kept.append(r)
            else:
                self._discard_resume_kv(r)
                self._record_terminal(r.timeline, "cancelled")
        self._waiting = kept
        self._set_queue_gauge()
        for i, slot in enumerate(self._slots):
            if slot.request_id == request_id:
                tl = slot.timeline
                if tl is not None and tl.finished_at is None:
                    # client went away mid-generation (stream closed):
                    # terminal for telemetry even though nothing was sent
                    self._record_terminal(tl, "cancelled")
                self._free_pages(slot.pages)
                slot.reset()
                self._mark_penalty_dirty(i)
                self._wake.set()

    # ---------------- lifecycle: drain + resumable generation ----------------

    @property
    def draining(self) -> bool:
        return self._draining

    def _adapter_name(self, adapter_id: int) -> Optional[str]:
        if adapter_id < 0:
            return None
        for name, i in self.adapter_ids.items():
            if i == adapter_id:
                return name
        return None

    def _checkpoint(self, request_id, prompt_ids, generated, params,
                    adapter_id, deadline, reason) -> GenerationCheckpoint:
        ckpt = GenerationCheckpoint.capture(
            request_id=request_id,
            prompt_ids=prompt_ids,
            generated=generated,
            params=params,
            adapter=self._adapter_name(adapter_id),
            model_name=self._ckpt_label,
            deadline=deadline,
            reason=reason,
        )
        self.checkpointed_count += 1
        GENERATION_CHECKPOINTS.labels(
            model_name=self._mlabel, reason=reason).inc()
        return ckpt

    def _checkpoint_slot(self, slot: _Slot, reason: str) -> GenerationCheckpoint:
        """Snapshot a seated slot.  A slot still chunk-prefilling has
        emitted nothing; its checkpoint carries only the prompt (plus any
        prior resume progress), so resume costs exactly one prefill."""
        if slot.prefilling is not None:
            req = slot.prefilling["req"]
            generated = req.resume["generated"] if req.resume is not None else []
            return self._checkpoint(
                req.request_id, req.prompt_ids, generated, req.params,
                req.adapter_id, req.deadline, reason,
            )
        return self._checkpoint(
            slot.request_id, slot.prompt_ids, slot.generated, slot.params,
            slot.adapter_id, slot.deadline, reason,
        )

    def _evict_slot(self, slot: _Slot, exc: Exception) -> None:
        """Deliver exc to the slot's stream and release its resources
        (deferred-free-safe: legal while a chained chunk is in flight).
        Whatever the stream is still owed goes out first."""
        self._deliver()
        slot.queue.put_nowait(exc)
        if slot.timeline is not None:
            if isinstance(exc, GenerationPreempted):
                slot.timeline.add_event(self._clock.now(), "checkpoint")
                self._record_terminal(slot.timeline, "preempted")
            else:
                self._record_terminal(slot.timeline, "error")
        self._free_pages(slot.pages)
        idx = self._slots.index(slot)
        slot.reset()
        self._mark_penalty_dirty(idx)

    def _checkpoint_waiting(self, reason: str,
                            out: List[GenerationCheckpoint]) -> None:
        """Checkpoint + fail every queued-but-unseated request (fresh
        arrivals and KV-pressure preemptions alike).  Their streams see
        GenerationPreempted; spilled resume KV is released."""
        pending, self._waiting = self._waiting, []
        for req in pending:
            self._discard_resume_kv(req)
            generated = (
                list(req.resume["generated"]) if req.resume is not None else []
            )
            ckpt = self._checkpoint(
                req.request_id, req.prompt_ids, generated, req.params,
                req.adapter_id, req.deadline, reason,
            )
            out.append(ckpt)
            req.queue.put_nowait(GenerationPreempted(ckpt))
            if req.timeline is not None:
                req.timeline.add_event(
                    self._clock.now(), "checkpoint", reason=reason)
                self._record_terminal(req.timeline, "preempted")
        self._set_queue_gauge()

    async def drain(self, deadline: Optional[Deadline] = None,
                    clock=None, poll_s: float = 0.01,
                    reason: str = "drain") -> List[GenerationCheckpoint]:
        """Graceful drain (SIGTERM / POST /admin/drain): stop admitting,
        give in-flight generations until `deadline` (the replica's drain
        budget — lifecycle.begin_drain()) to finish, then snapshot whatever
        remains into portable GenerationCheckpoints delivered to each
        stream as GenerationPreempted.  Queued-but-unseated requests are
        checkpointed immediately — re-seating them here would burn budget a
        healthy replica could spend better.  `clock` is the chaos-test seam
        (FakeClock => the wait is virtual); escalation (second SIGTERM)
        expires `deadline` in place, which this loop observes on its next
        poll.  `reason` labels the checkpoints ("drain" for lifecycle
        drains, "stall" for the watchdog's self-drain — the sim's client
        layer counts stall-reason resumes as migrations).  Returns the
        checkpoints, newest last."""
        self._draining = True
        clk = clock or MONOTONIC
        checkpoints: List[GenerationCheckpoint] = []
        while True:
            # KV-pressure preemptions during the drain land back in
            # _waiting; flush them each pass instead of re-seating
            self._checkpoint_waiting(reason, checkpoints)
            active = [s for s in self._slots if s.request_id is not None]
            if not active:
                break
            if deadline is not None and deadline.expired:
                for slot in active:
                    ckpt = self._checkpoint_slot(slot, reason)
                    checkpoints.append(ckpt)
                    self._evict_slot(slot, GenerationPreempted(ckpt))
                self._wake.set()
                break
            await clk.sleep(poll_s)
        if checkpoints:
            logger.info(
                "drain: %d generation(s) checkpointed (%d tokens salvaged)",
                len(checkpoints),
                sum(c.tokens_salvaged for c in checkpoints),
            )
        return checkpoints

    def resume_generation(
        self,
        checkpoint: GenerationCheckpoint,
        request_id: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        """Admit a checkpointed generation from another (drained/preempted)
        replica.  Resume rides the existing preemption-resume machinery: a
        prefill of prompt+generated[:-1] (cheap under the prefix cache)
        re-creates the KV, the detokenizer is replayed to the checkpoint
        point, and decoding continues at the NEXT token — the re-prefill
        emits nothing, so the spliced stream has zero duplicated and zero
        dropped tokens.  Sync validation, async stream (see generate)."""
        if checkpoint.model_name and checkpoint.model_name != self._ckpt_label:
            raise ValueError(
                f"checkpoint was captured on model {checkpoint.model_name!r} "
                f"but this engine serves {self._ckpt_label!r}; resume "
                "requires identical weights"
            )
        # header-sourced checkpoints are untrusted input: normalize token
        # ids and sampling types HERE, synchronously, so a malformed value
        # fails this request instead of crashing the shared run loop
        checkpoint.validate(self.model_config.vocab_size)
        params = checkpoint.sampling_params()
        prompt_ids = list(checkpoint.prompt_ids)
        if len(prompt_ids) + params.max_tokens > self.config.max_model_len:
            raise ValueError(
                f"prompt+max_tokens exceeds max_model_len {self.config.max_model_len}"
            )
        # max_tokens is the TOTAL budget (pre-drain tokens count toward it),
        # so this bound plus the one above also caps prompt+generated at
        # max_model_len — an oversized crafted checkpoint must fail HERE
        # with a 400, not detonate allocation inside the shared run loop
        if len(checkpoint.generated) >= params.max_tokens:
            raise ValueError(
                f"checkpoint already holds {len(checkpoint.generated)} "
                f"generated tokens with max_tokens={params.max_tokens}; "
                "nothing left to resume"
            )
        self._check_accepting()
        # the effective budget is the min of the snapshot-time remainder
        # and the retry's own propagated deadline: the time a client spent
        # backing off between drain and resume is SLA time spent, and the
        # snapshot must not re-grant it (an expired propagated deadline is
        # rejected synchronously inside _admission_deadline)
        deadline = self._admission_deadline()
        if checkpoint.deadline_remaining_s is not None:
            if checkpoint.deadline_remaining_s <= 0:
                DEADLINE_REJECTED.labels(component="engine").inc()
                raise DeadlineExceededError(
                    "checkpoint deadline budget exhausted before resume"
                )
            # anchored on the ENGINE's clock (clock-injection audit): under
            # a virtual clock the snapshot budget must expire in virtual
            # time like every other deadline, or resumes would outlive the
            # budget their checkpoint carried
            snapshot = Deadline.after(
                checkpoint.deadline_remaining_s, self._clock)
            if deadline is None or snapshot.remaining() < deadline.remaining():
                deadline = snapshot
        generated = [int(t) for t in checkpoint.generated]
        queue: asyncio.Queue = asyncio.Queue()
        # the engine-side id must be unique even when the SAME checkpoint
        # is replayed twice (exactly the retry-storm case this feature
        # serves): cancel() tears down every slot matching the id, so two
        # resumes sharing checkpoint.request_id would have the first
        # finisher silently evict its live sibling and hang that stream.
        # The suffix keeps the original id traceable in logs/checkpoints.
        if request_id is not None:
            rid = request_id
        elif checkpoint.request_id:
            rid = f"{checkpoint.request_id}~r{time.monotonic_ns()}"
        else:
            rid = f"req-{time.monotonic_ns()}"
        tl = self._new_timeline(rid, len(prompt_ids))
        tl.add_event(self._clock.now(), "resume",
                     tokens_salvaged=len(generated))
        req = _QueuedRequest(
            rid, prompt_ids, params, queue,
            adapter_id=self._resolve_adapter(checkpoint.adapter),
            deadline=deadline,
            timeline=tl,
        )
        if generated:
            # replay the detokenizer so continuation text deltas pick up
            # exactly where the drained replica's stream stopped
            detok = IncrementalDetokenizer(self.tokenizer)
            for t in generated:
                detok.push(t)
            req.resume = {
                "generated": generated,
                "detok": detok,
                "stop_texts": list(params.stop or []),
                "pos": len(prompt_ids) + len(generated) - 1,
                "admitted_at": self._next_admission_seq(),
                "kv": None,  # cross-replica: always re-prefill
            }
        self.resume_count += 1
        GENERATION_RESUMES.labels(model_name=self._mlabel).inc()
        TOKENS_SALVAGED.labels(model_name=self._mlabel).inc(len(generated))
        return self._submit_and_stream(req)

    # ---------------- engine loop ----------------

    async def _run_loop(self):
        try:
            while not self._stopped:
                did_work = False
                self._phases.mark("admit")
                # deadline enforcement: a queued request whose budget died
                # is failed upfront — seating it would burn prefill+decode
                # on an answer nobody is waiting for
                self._drop_expired_waiting()
                # admission: seat waiting requests into free slots.  Paused
                # while draining — anything queued (including KV-pressure
                # preemptions) belongs to drain()'s checkpoint flush, not a
                # re-seat on a replica that is going away.  Under the
                # unified ragged program admission is pure bookkeeping
                # (every request enters as a prefilling slot; its chunks
                # ride the next mixed dispatches); the legacy path
                # dispatches the batched prefill program here.
                admit = self._admit_mixed if self._use_mixed else self._admit_batch
                while (not self._draining and self._waiting
                       and self._free_slot_index() is not None):
                    if not admit():
                        break
                    did_work = True
                self._set_queue_gauge()
                if self._use_mixed:
                    if await self._step_mixed():
                        did_work = True
                else:
                    if self._advance_prefills():
                        did_work = True
                    active = self._active_decode_slots()
                    self._set_occupancy_gauges(active)
                    if active:
                        await self._decode_once()
                        did_work = True
                if did_work:
                    # watchdog heartbeat: the loop completed an iteration
                    # that moved work forward (admission, prefill chunk,
                    # or a routed dispatch)
                    self._note_progress()
                if not did_work:
                    self._phases.pause()  # idle time is in no phase
                    self._wake.clear()
                    await self._wake.wait()
                else:
                    self._phases.mark("yield")
                    if not self._undelivered:
                        # yield to the event loop so streams flush between
                        # steps; where tokens are still owed, the next
                        # launch comes first and the streams flush while
                        # the loop awaits its fetch
                        await asyncio.sleep(0)
                    self._commit_dispatch()
            self._deliver()
        except Exception as e:  # noqa: BLE001 — engine death must surface
            logger.exception("engine loop crashed")
            self._loop_error = e
            self._pipeline_busy = False  # frees must not defer post-mortem
            # a request that finished in the advance has no slot left to
            # be found below: its last chunk goes out before any error (a
            # record that raises is logged and the rest still go out: each
            # call drops what it met)
            while self._undelivered:
                try:
                    self._deliver()
                except Exception:  # noqa: BLE001 — the loop is already dead
                    logger.exception(
                        "a token could not be handed to its stream")
            for slot in self._slots:
                if slot.request_id is not None:
                    slot.queue.put_nowait(e)
                    self._record_terminal(slot.timeline, "error")
                    # release the seat's pages: the allocator outlives the
                    # loop (stop() can no longer evict a reset slot)
                    self._free_pages(slot.pages)
                    slot.reset()
            for req in self._waiting:
                req.queue.put_nowait(e)
                self._record_terminal(req.timeline, "error")
            self._waiting.clear()
            self._set_queue_gauge()
            # requests a crashed _admit_batch popped but never seated: fail
            # their streams and release the pages admission allocated
            for _, req, pages, _, _ in self._admitting:
                self.allocator.free(pages)
                req.queue.put_nowait(e)
                self._record_terminal(req.timeline, "error")
            self._admitting = []
            if self.on_loop_crash is not None:
                self.on_loop_crash(e)
        finally:
            self._phases.close()

    def _commit_dispatch(self) -> None:
        """Close the loop iteration's phases; if it dispatched, the row
        goes to the telemetry ring and its seconds to the counters."""
        row = self._phases.commit()
        if row is None:
            return
        self.telemetry.record_dispatch(row)
        ENGINE_DISPATCHES.labels(
            model_name=self._mlabel, program=row[_PROGRAM_COLUMN]).inc()
        for counter, seconds in zip(self._phase_seconds, row[_PHASE_COLUMNS]):
            counter.inc(seconds)
        for counter, column in zip(self._part_seconds, _PART_COLUMNS):
            counter.inc(row[column])
        self._uploads.inc(row[_UPLOADS_COLUMN])
        for counter, seconds in zip(self._phase_cpu_seconds,
                                    row[_CPU_COLUMNS]):
            counter.inc(seconds)
        for counter, tokens in zip(self._deliveries, row[_DELIVERED_COLUMNS]):
            counter.inc(tokens)

    def _drop_expired_waiting(self) -> None:
        """Fail queued requests whose propagated deadline expired before a
        slot freed up (504 at the protocol layer); spilled resume KV is
        released back to the tier store."""
        kept: List[_QueuedRequest] = []
        for req in self._waiting:
            if req.deadline is None or not req.deadline.expired:
                kept.append(req)
                continue
            self._discard_resume_kv(req)
            DEADLINE_REJECTED.labels(component="engine").inc()
            req.queue.put_nowait(DeadlineExceededError(
                f"request {req.request_id} deadline expired while queued"
            ))
            self._record_terminal(req.timeline, "error")
        if len(kept) != len(self._waiting):
            self._waiting = kept
            self._set_queue_gauge()

    def _free_slot_index(self) -> Optional[int]:
        for i, slot in enumerate(self._slots):
            if slot.request_id is None:
                return i
        return None

    def _admit_batch(self) -> bool:
        """Prefill up to `prefill_batch` waiting requests in ONE compiled
        call (padded to the widest TAIL bucket among them); False when no
        request can be admitted (no slots / no pages).

        Each row carries its own chunk_start, so prefix-cache hits stay
        BATCHED: a row with cached pages prefills only its uncached tail
        while attending to the cached history.  sp>1 engines use the fused
        ring-attention prefill instead (no cache; whole prompt per row)."""
        use_fused = self.config.sp > 1
        ps = self.config.page_size
        chunk_cap = self._shapes.token_budget
        admitted: List[tuple] = []  # (slot_index, request, pages, n_cached, seq)
        # aliased (not assigned after the loop) so the run-loop crash
        # handler sees every popped-but-unseated request even when a later
        # iteration raises mid-admission: a prefill or allocation that
        # raises must fail these requests (they are in neither _waiting nor
        # a slot — losing them hangs their streams forever)
        self._admitting = admitted
        free = [i for i, s in enumerate(self._slots) if s.request_id is None]
        while (
            self._waiting
            and free
            and len(admitted) < self.config.prefill_batch
        ):
            req = self._waiting[0]
            has_kv = req.kv_data is not None or (
                req.resume is not None and req.resume["kv"] is not None
            )
            if has_kv:
                if admitted:
                    break  # flush the batched prefill first
                return self._admit_injected(req)
            seq = (
                req.prompt_ids + req.resume["generated"][:-1]
                if req.resume is not None else req.prompt_ids
            )
            if req.adapter_id < 0 and not use_fused:
                hits, pkeys = self._prefix_cache.lookup_run(seq)
                if self._maybe_page_in(req, pkeys, len(hits)):
                    # tier-resident prefix uploading; hold this request
                    # (decode keeps running) and flush what we have
                    if admitted:
                        break
                    return False
            else:
                hits, pkeys = [], []
            tail = req.kv_len - len(hits) * ps
            if tail > chunk_cap:
                if admitted:
                    break  # flush the batched prefill first
                return self._admit_chunked(req, hits, pkeys)
            need = pages_needed(req.kv_len + 1, ps)
            # pin cache hits before eviction can free them (see
            # _admit_chunked for why this must precede _ensure_allocatable)
            self.allocator.share(hits)
            if not self._prefix_cache.ensure_allocatable(
                self._admission_pages(req, need - len(hits))
            ):
                self.allocator.free(hits)
                break
            # allocate BEFORE popping: if allocate raises, the request is
            # still in _waiting and the crash handler fails it there
            pages = list(hits) + self.allocator.allocate(need - len(hits))
            self._waiting.pop(0)
            if req.timeline is not None:
                req.timeline.mark_admitted(
                    self._clock.now(), self._phases.serial)
            self._count_prefix_hits(pkeys, hits)
            admitted.append((free.pop(0), req, pages, len(hits), seq))
        if not admitted:
            return False

        bucket = self._shapes.bucket(
            max(len(seq) - c * ps for _, _, _, c, seq in admitted)
        )
        # pad the batch dim to pow2 so the compile cache stays small
        Bp = 1
        while Bp < len(admitted):
            Bp *= 2
        # history-attending chunk prefill only pays off when a row actually
        # HAS history: cold batches take the fused program (no masked
        # history gather, on-device prompt mask, single dispatch)
        use_fused_call = use_fused or all(c == 0 for _, _, _, c, _ in admitted)
        tokens = np.zeros((Bp, bucket), np.int32)
        valid = np.zeros((Bp,), np.int32)
        width = (
            self.config.max_pages_per_seq if use_fused_call
            else self._shapes.width(
                max(len(pages) for _, _, pages, _, _ in admitted)
            )
        )
        page_ids = np.zeros((Bp, width), np.int32)
        adapter_arr = np.full((Bp,), -1, np.int32)
        params_list = [SamplingParams() for _ in range(Bp)]
        if not use_fused_call:
            chunk_start = np.zeros((Bp,), np.int32)
            in_prompt = np.zeros((Bp, self.model_config.vocab_size), bool)
        for j, (_, req, pages, n_cached, seq) in enumerate(admitted):
            start = n_cached * ps
            tail_tokens = seq[start:]
            tokens[j, : len(tail_tokens)] = tail_tokens
            valid[j] = len(tail_tokens)
            page_ids[j, : len(pages)] = pages
            adapter_arr[j] = req.adapter_id
            params_list[j] = req.params
            if not use_fused_call:
                chunk_start[j] = start
                in_prompt[j, np.asarray(seq, np.int64)] = True
        state = SamplingState.from_params(params_list)
        rng = jax.random.fold_in(self._base_rng, self._next_step())
        # logprob-emitting program variants only when some fresh row asked —
        # ordinary admissions never pay the top_k
        want_lp = any(
            req.resume is None and req.params.logprobs is not None
            for _, req, _, _, _ in admitted
        )
        lp_tuple = None
        prefill_t0 = self._clock.now()
        # one legacy prefill forward, either program
        self._work.forward(1, legacy_prefill=True)
        if use_fused_call:
            prefill_fn = self._prefill_lp_fn if want_lp else self._prefill_fn
            out = prefill_fn(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(valid),
                self.kv_pages,
                jnp.asarray(page_ids),
                state,
                rng,
                jnp.asarray(adapter_arr),
            )
            if want_lp:
                first, lp_tuple, self.kv_pages = out
            else:
                first, self.kv_pages = out
        else:
            logits, self.kv_pages = self._prefill_chunk_fn(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(chunk_start),
                jnp.asarray(valid),
                self.kv_pages,
                jnp.asarray(page_ids),
                jnp.asarray(adapter_arr),
            )
            if want_lp:
                first, lp_tuple = self._sample_first_lp_fn(
                    logits, state, rng, jnp.asarray(in_prompt)
                )
            else:
                first = self._sample_first_fn(
                    logits, state, rng, jnp.asarray(in_prompt)
                )
        first_np = self._fetch(first)
        lp_np = (
            tuple(self._fetch(a) for a in lp_tuple)
            if lp_tuple is not None else None
        )
        prefill_t1 = self._clock.now()
        ENGINE_PREFILL_CHUNK_DURATION.labels(model_name=self._mlabel).observe(
            prefill_t1 - prefill_t0)
        self.telemetry.record_prefill_chunk(prefill_t1 - prefill_t0)
        for j, (idx, req, pages, _, seq) in enumerate(admitted):
            if req.timeline is not None:
                req.timeline.mark_prefill_start(prefill_t0)
                req.timeline.mark_prefill_end(prefill_t1)
            if req.resume is None:
                # resume re-prefills are recompute overhead, not new prompt
                # traffic — don't double-count them
                PROMPT_TOKENS.labels(model_name=self._mlabel).inc(len(seq))
            slot = self._slots[idx]
            if req.resume is not None:
                # stream state survives preemption; the re-prefill's sampled
                # token is discarded (the real next token comes from decode)
                self._seat_resumed(slot, req, pages)
                self._mark_penalty_dirty(idx)
                continue
            first_token = int(first_np[j])
            self._seat_fresh(slot, req, pages, first_token)
            if req.adapter_id < 0:
                self._prefix_cache.register(req.prompt_ids, pages)
            self._mark_penalty_dirty(idx)
            self._advance(slot, first_token, *self._lp_for(req.params, lp_np, j))
        self._admitting = []
        return True

    @staticmethod
    def _lp_for(params: SamplingParams, lp_np, j: int, s: Optional[int] = None):
        """(logprob, top_logprobs) for row j (step s) of a device lp tuple,
        sliced to the request's asked-for top-k; (None, None) when the
        request didn't ask or the chunk didn't compute them."""
        if lp_np is None or params.logprobs is None:
            return None, None
        lp, tv, ti = lp_np
        if s is not None:
            lp, tv, ti = lp[s], tv[s], ti[s]
        k = min(int(params.logprobs), tv.shape[-1])
        top = [(int(ti[j, i]), float(tv[j, i])) for i in range(k)]
        return float(lp[j]), top

    def _seat_fresh(self, slot: _Slot, req: "_QueuedRequest",
                    pages: List[int], first_token: int) -> None:
        """Single source of truth for seating a freshly-prefilled request —
        the batched, chunked and injected admission paths all use it."""
        n_prompt = len(req.prompt_ids)
        slot.request_id = req.request_id
        slot.prompt_len = n_prompt
        slot.prompt_ids = req.prompt_ids
        slot.pages = pages
        slot.pos = n_prompt  # position of the token being decoded next
        slot.generated = [first_token]
        slot.params = req.params
        slot.queue = req.queue
        slot.detok = IncrementalDetokenizer(self.tokenizer)
        slot.stop_texts = list(req.params.stop or [])
        slot.admitted_at = self._next_admission_seq()
        slot.adapter_id = req.adapter_id
        slot.deadline = req.deadline
        slot.timeline = req.timeline

    @property
    def prefix_cache_hits(self) -> int:
        """Pages reused via the prefix cache (observability/tests)."""
        return self._prefix_cache.hits

    def _active_decode_slots(self) -> List[_Slot]:
        return [
            s for s in self._slots
            if s.request_id is not None and s.prefilling is None
        ]

    def _set_occupancy_gauges(self, active: List[_Slot]) -> None:
        ENGINE_BATCH_OCCUPANCY.labels(model_name=self._mlabel).set(len(active))
        ENGINE_KV_PAGES_FREE.labels(model_name=self._mlabel).set(
            self.allocator.free_pages
        )
        self._set_composition_gauge(len(active))
        self._set_state_gauges()

    def _state_occupancy(self) -> dict:
        """Per-kind occupancy (kvcache.StateLayout): lanes seated, pages of
        the pool held, and the bytes both come to, beside what one token
        and one lane cost."""
        layout = self.state_layout
        seated = sum(1 for s in self._slots if s.request_id is not None)
        held = layout.num_pages - 1 - self.allocator.free_pages
        return {
            "slots_in_use": seated,
            "slots": layout.lanes,
            "pages_in_use": held,
            "bytes_in_use": layout.bytes_in_use(seated, held),
            "bytes_per_token": layout.bytes_per_token(),
            "bytes_per_lane": layout.lane_bytes(),
        }

    def _cache_report(self) -> dict:
        """The pool as /v1/internal/scheduler/state publishes it (`cache`):
        pages held (not free: the lanes' and the prefix cache's, which are
        given up under pressure) and in all, the rows a token holds (passes
        x layers) and their bytes."""
        layout = self.state_layout
        total = layout.num_pages - 1  # page 0 is the null page
        return {
            "pages_held": total - self.allocator.free_pages,
            "pages_cached": len(self._prefix_cache),
            "pages_total": total,
            "page_size": layout.page_size,
            "passes": layout.n_passes,
            "cache_rows": layout.cache_rows,
            "token_bytes": layout.token_bytes(),
        }

    def _set_state_gauges(self) -> None:
        occupancy = self._state_occupancy()
        ENGINE_STATE_SLOTS_IN_USE.labels(model_name=self._mlabel).set(
            occupancy["slots_in_use"])
        for kind, n in occupancy["bytes_in_use"].items():
            ENGINE_STATE_BYTES.labels(model_name=self._mlabel, kind=kind).set(n)

    def _admit_mixed(self) -> bool:
        """Admission under the unified ragged program: requests with
        host-resident KV (P/D transfer, tier-store resume) take the inject
        path; everything else seats as a prefilling slot whose chunks —
        whether one covering the whole prompt or many — ride the mixed
        dispatches.  No prefill program runs here."""
        req = self._waiting[0]
        has_kv = req.kv_data is not None or (
            req.resume is not None and req.resume["kv"] is not None
        )
        if has_kv:
            return self._admit_injected(req)
        return self._admit_prefilling(req)

    def _admit_chunked(self, req: "_QueuedRequest",
                       hits: Optional[List[int]] = None,
                       keys: Optional[List[bytes]] = None) -> bool:
        """Admit one long-prompt request by chunked prefill (legacy path:
        the run loop advances its chunks through the prefill_chunk
        program).  Unblocks prompts up to max_model_len without sequence
        parallelism."""
        return self._admit_prefilling(req, hits, keys)

    def _admit_prefilling(self, req: "_QueuedRequest",
                          hits: Optional[List[int]] = None,
                          keys: Optional[List[bytes]] = None) -> bool:
        """Seat one request as a prefilling slot: allocate its pages (with
        prefix-cache hits pinned), pop it from the queue, and record the
        chunk cursor.  Shared by the legacy chunked admission and by EVERY
        mixed-mode admission (where even short prompts are a single chunk
        riding the next mixed dispatch)."""
        idx = self._free_slot_index()
        if idx is None:
            return False
        total = req.kv_len
        need = pages_needed(total + 1, self.config.page_size)
        if need > self.config.max_pages_per_seq:
            self._waiting.remove(req)
            self._set_queue_gauge()
            req.queue.put_nowait(ValueError(
                f"prompt needs {need} pages > max_pages_per_seq "
                f"{self.config.max_pages_per_seq}"
            ))
            self._record_terminal(req.timeline, "error")
            return True
        if req.resume is not None:
            seq = req.prompt_ids + req.resume["generated"][:-1]
        else:
            seq = req.prompt_ids
        # LoRA adapters produce adapter-specific KV: only base-model
        # requests share the prefix cache
        if hits is None:
            if req.adapter_id < 0:
                hits, keys = self._prefix_cache.lookup_run(seq)
                if self._maybe_page_in(req, keys, len(hits)):
                    return False  # tier pages uploading; retried on wake
            else:
                hits = []
        cached = list(hits)
        # take our reference BEFORE eviction runs: eviction may drop these
        # pages from the cache, but a live ref keeps them off the free list
        # (evicted-then-shared pages would otherwise be re-allocated while
        # this sequence reads them)
        self.allocator.share(cached)
        fresh_needed = need - len(cached)
        # decode headroom only for genuinely long admissions (many chunks
        # in flight before first token) — a short mixed-mode admission
        # must not demand more pages than the legacy batched path did
        headroom = (
            total - len(cached) * self.config.page_size
            > self._shapes.token_budget
        )
        if not self._prefix_cache.ensure_allocatable(
            self._admission_pages(req, fresh_needed, headroom=headroom)
        ):
            self.allocator.free(cached)  # release the early reference
            return False
        # allocate BEFORE popping: if allocate raises, the request is still
        # in _waiting and the crash handler fails it there (everything after
        # this is infallible python bookkeeping until the slot — whose queue
        # the handler covers — owns the request)
        pages = cached + self.allocator.allocate(fresh_needed)
        self._waiting.remove(req)
        self._set_queue_gauge()
        if req.timeline is not None:
            req.timeline.mark_admitted(
                self._clock.now(), self._phases.serial)
        self._count_prefix_hits(keys or [], cached)
        # the slot enters "prefilling" state immediately and the run loop
        # advances ONE chunk per iteration — in-flight decode streams keep
        # emitting between chunks, and the queue behind this request isn't
        # head-of-line blocked for its whole prefill
        slot = self._slots[idx]
        slot.request_id = req.request_id
        slot.pages = pages
        slot.queue = req.queue  # engine-crash propagation needs the stream
        slot.prefilling = {
            "req": req,
            "seq": seq,
            "done": len(cached) * self.config.page_size,
            "logits": None,
        }
        if not cached:
            # the lane's first slice starts at position 0: the program
            # starts its ring and recurrent slot from zero
            ENGINE_STATE_RESETS.labels(model_name=self._mlabel).inc()
        return True

    def _advance_prefills(self) -> bool:
        """One chunk of progress for every prefilling slot; completes slots
        whose prompt is fully prefilled (sampling the first token)."""
        progressed = False
        chunk_cap = self._shapes.token_budget
        for idx, slot in enumerate(self._slots):
            pf = slot.prefilling
            if slot.request_id is None or pf is None:
                continue
            seq, done = pf["seq"], pf["done"]
            total = len(seq)
            if done < total:
                n = min(chunk_cap, total - done)
                bucket = self._shapes.bucket(n)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = seq[done : done + n]
                page_ids = np.zeros((self.config.max_pages_per_seq,), np.int32)
                page_ids[: len(slot.pages)] = slot.pages
                # table width must cover this chunk's writes (the history
                # gather reads the same table, masked by history length)
                width = self._shapes.width(
                    pages_needed(done + n, self.config.page_size)
                )
                chunk_t0 = self._clock.now()
                tl = pf["req"].timeline
                if tl is not None:
                    tl.mark_prefill_start(chunk_t0)
                self._work.forward(1, legacy_prefill=True)
                pf["logits"], self.kv_pages = self._prefill_chunk_fn(
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(np.asarray([done], np.int32)),
                    jnp.asarray(np.asarray([n], np.int32)),
                    self.kv_pages,
                    jnp.asarray(page_ids[None, :width]),
                    jnp.asarray(np.asarray([pf["req"].adapter_id], np.int32)),
                )
                pf["done"] = done + n
                chunk_t1 = self._clock.now()
                ENGINE_PREFILL_CHUNK_DURATION.labels(
                    model_name=self._mlabel).observe(chunk_t1 - chunk_t0)
                self.telemetry.record_prefill_chunk(chunk_t1 - chunk_t0)
                if tl is not None:
                    tl.mark_prefill_end(chunk_t1)
                if pf["req"].adapter_id < 0 and pf["req"].resume is None:
                    # register only the pages COMPLETED by this chunk — a
                    # full re-register would re-hash the whole prefix per
                    # chunk (O(L^2) host work on the engine loop)
                    covered = min(pf["done"], len(pf["req"].prompt_ids))
                    self._prefix_cache.register(
                        pf["req"].prompt_ids[:covered],
                        slot.pages,
                        start_page=pf.get("registered", 0),
                    )
                    pf["registered"] = covered // self.config.page_size
                progressed = True
            if pf["done"] >= total:
                self._finish_prefilling(idx, slot, pf)
                progressed = True
        return progressed

    def _complete_prefilling(self, idx: int, slot: _Slot, req,
                             first_token: Optional[int],
                             lp: tuple = (None, None),
                             defer: bool = False) -> None:
        """A prefilling slot's prompt is fully in the cache: seat it and
        (fresh path) emit its first token.  The single completion path
        shared by the legacy chunk loop (_finish_prefilling, which samples
        the token itself) and the mixed route (where the token is the
        dispatch's step-0 sample) — the two dispatchers must not drift."""
        pages = slot.pages
        slot.prefilling = None
        if req.resume is not None:
            if req.adapter_id < 0:
                # non-resume prompts registered incrementally per chunk;
                # the resume path registers its prompt prefix once here
                self._prefix_cache.register(req.prompt_ids, pages)
            self._seat_resumed(slot, req, pages)
            self._mark_penalty_dirty(idx)
            return
        PROMPT_TOKENS.labels(model_name=self._mlabel).inc(
            len(req.prompt_ids))
        self._seat_fresh(slot, req, pages, first_token)
        self._mark_penalty_dirty(idx)
        self._advance(slot, first_token, *lp, defer=defer)

    def _finish_prefilling(self, idx: int, slot: _Slot, pf: dict) -> None:
        req = pf["req"]
        if req.resume is not None:
            self._complete_prefilling(idx, slot, req, None)
            return
        seq = pf["seq"]
        state = SamplingState.from_params([req.params])
        rng = jax.random.fold_in(self._base_rng, self._next_step())
        in_prompt = np.zeros((1, self.model_config.vocab_size), bool)
        in_prompt[0, np.asarray(seq, np.int64)] = True
        lp_np = None
        if req.params.logprobs is not None:
            first, lp_tuple = self._sample_first_lp_fn(
                pf["logits"], state, rng, jnp.asarray(in_prompt)
            )
            lp_np = tuple(np.asarray(a) for a in lp_tuple)
        else:
            first = self._sample_first_fn(
                pf["logits"], state, rng, jnp.asarray(in_prompt)
            )
        first_token = int(self._fetch(first)[0])
        self._complete_prefilling(
            idx, slot, req, first_token,
            self._lp_for(req.params, lp_np, 0))

    def _admission_pages(self, req: "_QueuedRequest", need: int,
                         headroom: bool = False) -> int:
        """Pages that must be free to admit.  Resumes and long chunked
        admissions additionally require a couple of chunks of decode
        headroom (capped at what the cache can ever provide) — admitting
        into an immediately-starving cache would just bounce the work back
        out (KV ping-pong for resumes, aborted prefills for long prompts)."""
        if req.resume is None and not headroom:
            return need
        extra = pages_needed(2 * self._shapes.steps, self.config.page_size)
        return min(need + extra, self.config.num_pages - 1)

    def _seat_resumed(self, slot: _Slot, req: "_QueuedRequest", pages: List[int]) -> None:
        r = req.resume
        slot.request_id = req.request_id
        slot.prompt_len = len(req.prompt_ids)
        slot.prompt_ids = req.prompt_ids
        slot.pages = pages
        slot.pos = r["pos"]
        slot.generated = r["generated"]
        slot.params = req.params
        slot.queue = req.queue
        slot.detok = r["detok"]
        slot.stop_texts = r["stop_texts"]
        slot.admitted_at = r["admitted_at"]
        slot.adapter_id = req.adapter_id
        slot.deadline = req.deadline
        slot.timeline = req.timeline
        if req.timeline is not None:
            req.timeline.mark_admitted(
                self._clock.now(), self._phases.serial)

    def _admit_injected(self, req: "_QueuedRequest") -> bool:
        """Admit a request whose KV already exists on host: either P/D
        transfer from a prefill peer (seat at pos=len(prompt), emit the
        peer's first token) or a preemption resume from the host tier
        (restore the full stream state, emit nothing)."""
        idx = self._free_slot_index()
        if idx is None:
            return False
        total = req.kv_len
        need = pages_needed(total + 1, self.config.page_size)
        if need > self.config.max_pages_per_seq:
            return False
        if not self._prefix_cache.ensure_allocatable(self._admission_pages(req, need)):
            return False
        # fetch AFTER the capacity checks — get() consumes the spill, and a
        # transient no-capacity return must leave it stored
        if req.resume is not None:
            payload = (self._kv_store.get(req.resume["kv"])
                       if self._kv_store is not None else None)
            if payload is None:
                # dropped under tier pressure: recompute on the normal
                # re-prefill path (returning True = progress; the next
                # admission pass takes the prefill branch)
                req.resume["kv"] = None
                self._set_offload_gauges()
                return True
            self._set_offload_gauges()
        else:
            payload = {"kv": req.kv_data}
        quantized = "kv_q" in payload
        kv = payload["kv_q"] if quantized else payload["kv"]
        # allocate BEFORE popping (a raise leaves req in _waiting for the
        # crash handler), then register the popped request in _admitting so
        # a device inject that raises fails this stream instead of hanging
        # it — same contract as the batched-prefill path
        pages = self.allocator.allocate(need)
        self._waiting.remove(req)
        self._set_queue_gauge()
        if req.timeline is not None:
            req.timeline.mark_admitted(
                self._clock.now(), self._phases.serial)
            req.timeline.mark_prefill_start(self._clock.now())
        entry = (idx, req, pages, 0, None)
        self._admitting.append(entry)
        P = kv.shape[1]
        # pad the page dim to the standard width buckets (small compile cache)
        bucket = self._shapes.width(P)
        ids = np.zeros((bucket,), np.int32)
        ids[:P] = pages[:P]

        def pad(arr):
            out = np.zeros(arr.shape[:1] + (bucket,) + arr.shape[2:], arr.dtype)
            out[:, :P] = arr
            return out

        if quantized:
            self.kv_pages = self._inject_q_fn(
                self.kv_pages, jnp.asarray(pad(kv)),
                jnp.asarray(pad(payload["kv_s"])), jnp.asarray(ids)
            )
        else:
            self.kv_pages = self._inject_fn(
                self.kv_pages, jnp.asarray(pad(kv)), jnp.asarray(ids)
            )
        if req.timeline is not None:
            # KV injection replaces prefill for this request (P/D transfer
            # or tier-store resume): the scatter IS its prefill phase
            req.timeline.mark_prefill_end(self._clock.now())
        slot = self._slots[idx]
        if req.resume is not None:
            self._seat_resumed(slot, req, pages)
            self._admitting.remove(entry)
            self._mark_penalty_dirty(idx)
            return True
        self._seat_fresh(slot, req, pages, req.first_token)
        self._admitting.remove(entry)
        PROMPT_TOKENS.labels(model_name=self._mlabel).inc(len(req.prompt_ids))
        self._mark_penalty_dirty(idx)
        self._advance(slot, req.first_token)
        return True

    def _ensure_pages_at(self, slot: _Slot, base: int, extra: int) -> bool:
        """Best-effort grow of the slot's page list toward positions
        base..base+extra-1 (capped at the per-seq limit); partial growth is
        kept — the chunk capacity mask lets a lane run however many steps
        its pages cover.  Returns True when the full range is covered."""
        needed = min(
            pages_needed(base + extra, self.config.page_size),
            self.config.max_pages_per_seq,
        )
        while len(slot.pages) < needed and self.allocator.can_allocate(1):
            slot.pages.extend(self.allocator.allocate(1))
        return len(slot.pages) >= pages_needed(base + extra, self.config.page_size)

    def _grow_and_preempt(self) -> None:
        """Before an unchained chunk: grow every active slot's pages toward
        the chunk's writes; on allocator exhaustion, preempt the NEWEST
        non-oldest slot back to the queue (freeing its pages) and retry.
        The oldest slot is never preempted, so it always finishes — liveness.
        A single slot that exhausts the whole cache alone is truncated
        honestly (config smaller than one max-length sequence)."""
        # worst-case advance of ONE dispatch: steps_per_sync tokens on the
        # plain paths, steps_per_sync * (K+1) under speculative decoding
        # (every round accepts everything)
        steps = self._max_step_advance
        ps = self.config.page_size
        # chaos seam (resilience/faults.py): a "preempt" spec targeting
        # "engine.preempt" forcibly requeues the newest active sequence —
        # the deterministic stand-in for spot/KV-pressure preemption the
        # drain/resume chaos tests fire under FakeClock
        if self.fault_plan is not None:
            spec = self.fault_plan.decide("engine.preempt")
            if spec is not None and spec.kind == "preempt":
                victims = [
                    s for s in self._slots
                    if s.request_id is not None and s.prefilling is None
                ]
                if victims:
                    self._preempt(max(victims, key=lambda s: s.admitted_at))
        while True:
            active = [
                s for s in self._slots
                if s.request_id is not None and s.prefilling is None
            ]
            if not active:
                return
            starved = []
            for slot in active:
                base = slot.pos
                if base >= self.config.max_model_len:
                    continue  # finished as "length" in _prepare_chunk
                grow = min(steps, self.config.max_model_len - base)
                self._ensure_pages_at(slot, base, grow)
                if len(slot.pages) * ps <= base:
                    starved.append(slot)
            if not starved:
                return
            # cold cached pages go before anyone gets preempted
            if self._prefix_cache.ensure_allocatable(1):
                continue
            # a long admission still prefilling is the preferred victim: it
            # has emitted nothing, its pages requeue cleanly, and truncating
            # a LIVE decode stream to protect it would be backwards
            prefilling = [
                s for s in self._slots
                if s.request_id is not None and s.prefilling is not None
            ]
            if prefilling:
                self._preempt_prefilling(prefilling[-1])
                continue
            oldest = min(active, key=lambda s: s.admitted_at)
            candidates = [
                s for s in active if s is not oldest and self._can_preempt(s)
            ]
            if not candidates:
                # nothing can legally be preempted (kv_offload contract:
                # "none"/exhausted budget must not pin host RAM, and a
                # too-long sequence can't re-prefill)
                if len(starved) < len(active):
                    # other lanes are still decoding and will free pages on
                    # finish; starved lanes pause (capacity mask) and retry
                    return
                for s in starved:
                    if self._draining:
                        # mid-drain, a starved lane must not be truncated
                        # with a dishonest "length": checkpoint it so a
                        # healthy replica finishes the generation
                        ckpt = self._checkpoint_slot(s, "preempt")
                        self._evict_slot(s, GenerationPreempted(ckpt))
                    else:
                        self._finish(s, "length")  # no page source left anywhere
                continue
            self._preempt(max(candidates, key=lambda s: s.admitted_at))

    def _can_preempt(self, slot: _Slot) -> bool:
        """Every slot has a resume path now: chunked re-prefill covers any
        length, and the host tier (when budgeted) avoids the recompute."""
        return True

    def _preempt_prefilling(self, slot: _Slot) -> None:
        """Abort an in-progress long admission: requeue its request (front)
        and free its pages.  Nothing was emitted, so nothing is lost but
        the chunks already computed."""
        req = slot.prefilling["req"]
        if req.timeline is not None:
            req.timeline.add_event(self._clock.now(), "preempt",
                                   phase="prefill")
        self._free_pages(slot.pages)
        self._mark_penalty_dirty(self._slots.index(slot))
        slot.reset()
        self._waiting.insert(0, req)
        self._set_queue_gauge()
        self.preemption_count += 1
        ENGINE_PREEMPTIONS.labels(model_name=self._mlabel).inc()
        logger.info("preempted prefilling request %s", req.request_id)

    def _preempt(self, slot: _Slot) -> None:
        """Requeue a running slot (front of queue), freeing its pages.  With
        the host tier enabled (and budget left) its KV spills to host RAM
        and re-injects on resume; otherwise resume re-prefills
        prompt+generated[:-1].  Nothing is emitted — the client stream just
        pauses.  Parity: vLLM preemption + KVCacheOffloadingSpec
        (llm_inference_service_types.go:188-232)."""
        pos = slot.pos  # KV on device covers positions 0..pos-1
        P = pages_needed(pos, self.config.page_size)
        kv_key = None
        nbytes = P * self.state_layout.page_bytes()
        # spill into the tier store when it can fit; otherwise chunked
        # re-prefill recomputes the KV on resume.  Quantized caches spill
        # both tensors (int8 pages + scales) as one payload.  Mid-drain the
        # spill is skipped outright: the drain loop checkpoints the requeued
        # request on its next pass and discards any resume KV (resume is
        # cross-replica, always re-prefilled), so the device fetch would
        # only burn drain budget and stall the loop for zero benefit.
        if (
            self._kv_store is not None
            and not self._draining
            and self._kv_store.would_fit(nbytes)
        ):
            payload = {
                name: self._fetch(v)
                for name, v in self._gather_pages_device(
                    slot.pages[:P]).items()
            }
            if self._kv_store.put(slot.request_id, payload):
                kv_key = slot.request_id
            self._set_offload_gauges()
        req = _QueuedRequest(slot.request_id, slot.prompt_ids, slot.params, slot.queue,
                             adapter_id=slot.adapter_id, deadline=slot.deadline,
                             timeline=slot.timeline)
        if slot.timeline is not None:
            slot.timeline.add_event(
                self._clock.now(), "preempt", pos=pos,
                spilled=kv_key is not None)
        req.resume = {
            "generated": slot.generated,
            "detok": slot.detok,
            "stop_texts": slot.stop_texts,
            "pos": pos,
            "admitted_at": slot.admitted_at,
            # the spill, if stored, lives in the tier store under this key
            # (None = recompute on resume)
            "kv": kv_key,
        }
        self._free_pages(slot.pages)
        self._mark_penalty_dirty(self._slots.index(slot))
        slot.reset()
        self._waiting.insert(0, req)
        self._set_queue_gauge()
        self.preemption_count += 1
        ENGINE_PREEMPTIONS.labels(model_name=self._mlabel).inc()
        logger.info(
            "preempted %s at pos=%d (%s)", req.request_id, pos,
            "KV spilled to tier store" if kv_key is not None
            else "will re-prefill",
        )

    def _free_pages(self, pages: List[int]) -> None:
        """Page frees are deferred while a chained chunk is in flight — a
        reused page could otherwise be written by the stale lanes of the
        in-flight program."""
        if self._pipeline_busy:
            self._deferred_free.extend(pages)
        else:
            self.allocator.free(pages)

    def _flush_deferred_frees(self) -> None:
        if self._deferred_free:
            self.allocator.free(self._deferred_free)
            self._deferred_free = []

    def _page_table_of(self, lanes) -> np.ndarray:
        """The [B, W] page table of one dispatch: W from the most pages a
        seated lane of `lanes` ([B] bool) owns, those lanes' rows filled
        from their slots, the rest zero (the null page).  WHICH lanes
        count is the caller's: the mixed step counts every seated lane,
        the decode chunk and the chained dense dispatch only the lanes
        they run."""
        rows = self._page_rows(lanes)
        return self._page_table(rows, self._width_of(rows))

    def _page_rows(self, lanes) -> list:
        return [(i, slot.pages) for i, slot in enumerate(self._slots)
                if lanes[i] and slot.request_id is not None]

    def _width_of(self, rows) -> int:
        """W that `rows` need: the rung of the most pages one of them owns."""
        return self._shapes.width(max([len(p) for _, p in rows] or [1]))

    def _page_table(self, rows, width: int) -> np.ndarray:
        page_table = np.zeros((self.config.max_batch_size, width), np.int32)
        for i, pages in rows:
            page_table[i, : len(pages)] = pages
        return page_table

    def _prepare_chunk(self, prev: Optional[dict]) -> Optional[dict]:
        """Build host-side inputs for a decode chunk.  `prev` chains the
        chunk after an in-flight one: positions advance speculatively by
        min(steps, prev capacity) without reading prev's tokens."""
        B = self.config.max_batch_size
        steps = self._shapes.steps
        if prev is None:
            # page growth + preemption happen only between pipelines (the KV
            # extraction in _preempt needs no chunk in flight)
            self._grow_and_preempt()
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        capacity = np.zeros((B,), np.int32)
        counters = np.zeros((B,), np.int32)
        adapters = np.full((B,), -1, np.int32)
        params_list = [SamplingParams() for _ in range(B)]
        for i, slot in enumerate(self._slots):
            if slot.request_id is None or slot.prefilling is not None:
                continue
            if prev is not None:
                if not prev["active"][i]:
                    continue
                base = min(int(prev["pos"][i]) + steps, int(prev["capacity"][i]))
            else:
                base = slot.pos
                tokens[i] = slot.generated[-1]
            grow = min(self._max_step_advance,
                       self.config.max_model_len - base)
            if grow <= 0:
                if prev is None:
                    self._finish(slot, "length")  # genuinely at max_model_len
                continue
            if prev is not None:
                # best-effort growth for chained chunks; no preemption while
                # the previous chunk is in flight
                self._ensure_pages_at(slot, base, grow)
            if len(slot.pages) * self.config.page_size <= base:
                continue  # no capacity this chunk; retried after the drain
            pos[i] = base
            active[i] = True
            capacity[i] = len(slot.pages) * self.config.page_size
            # tokens generated when this chunk starts (for seeded lanes)
            counters[i] = base - slot.prompt_len + 1
            adapters[i] = slot.adapter_id
            params_list[i] = slot.params
        if not active.any():
            return None
        # as wide as the longest ACTIVE lane: a seated lane that sits this
        # chunk out is not read
        page_table = self._page_table_of(active)
        # penalized chunks use device-resident [B, V] count/prompt arrays,
        # rebuilt from the host-side slot lists only when batch composition
        # changed; such chunks are never pipeline-chained so the counts are
        # always accurate at dispatch time
        penalized = any(
            slot.request_id is not None and active[i] and slot.params.has_penalties
            for i, slot in enumerate(self._slots)
        )
        if penalized:
            self._refresh_penalty_state()
        want_logprobs = any(
            slot.request_id is not None and active[i]
            and slot.params.logprobs is not None
            for i, slot in enumerate(self._slots)
        )
        return {
            "tokens": tokens,
            "pos": pos,
            "active": active,
            "capacity": capacity,
            "page_table": page_table,
            "counters": counters,
            "adapters": adapters,
            # the lanes' sampling rows: whichever launch takes this chunk
            # builds its state from them (_sampling_state; `mixed` packs its
            # own lanes' in _plan_ragged)
            "params_list": params_list,
            "penalized": penalized,
            "want_logprobs": want_logprobs,
        }

    def _refresh_penalty_state(self) -> None:
        """Bring the device [B, V] count/prompt arrays up to date.  Rows for
        lanes that stayed resident are already correct on device (the
        penalized decode returns updated counts); only rows touched by
        admission/finish/cancel are re-uploaded — O(changed rows), not O(B)."""
        V = self.model_config.vocab_size
        B = self.config.max_batch_size

        def row_data(i):
            counts_row = np.zeros((V,), np.int32)
            prompt_row = np.zeros((V,), bool)
            slot = self._slots[i]
            # gate on residency, NOT on active[i]: a resident lane skipped
            # from this chunk (KV-page starvation) must keep its counts —
            # zeroing it during a full rebuild would silently drop its
            # penalties for the rest of the request (it is not marked dirty
            # when it reactivates)
            if slot.request_id is not None:
                np.add.at(counts_row, slot.generated, 1)
                prompt_row[slot.prompt_ids] = True
            return counts_row, prompt_row

        if self._penalty_counts is None or self._penalty_dirty_rows is None:
            rows = [row_data(i) for i in range(B)]
            self._penalty_counts = jnp.asarray(np.stack([r[0] for r in rows]))
            self._penalty_prompt = jnp.asarray(np.stack([r[1] for r in rows]))
        elif self._penalty_dirty_rows:
            idx = sorted(self._penalty_dirty_rows)
            rows = [row_data(i) for i in idx]
            at = jnp.asarray(idx)
            self._penalty_counts = self._penalty_counts.at[at].set(
                jnp.asarray(np.stack([r[0] for r in rows]))
            )
            self._penalty_prompt = self._penalty_prompt.at[at].set(
                jnp.asarray(np.stack([r[1] for r in rows]))
            )
        self._penalty_dirty_rows = set()

    def _mark_penalty_dirty(self, slot_index: Optional[int]) -> None:
        """Record a batch-composition change; None invalidates everything.
        The speculative draft table shares the same dirty tracking: any
        seat/finish/preempt that changes a row's occupant must re-seed
        that row from the new occupant's prompt + generated tokens."""
        if slot_index is None:
            self._penalty_dirty_rows = None
            self._draft_dirty = None
        else:
            if self._penalty_dirty_rows is not None:
                self._penalty_dirty_rows.add(slot_index)
            if self._draft_dirty is not None:
                self._draft_dirty.add(slot_index)

    def _refresh_draft_table(self) -> None:
        """Bring the device [B, V] bigram draft table up to date for rows
        whose occupant changed: each dirty row is re-seeded host-side from
        prompt + generated bigrams (later occurrences win — numpy fancy
        assignment applies in order), empty rows reset to -1 (unseen).
        Rows that stayed resident are NOT touched: the device keeps the
        bigrams it learned from accepted tokens between dispatches.

        Every path commits the table to ONE replicated NamedSharding —
        the spelling the program pins its table output to.  A host-fresh
        table (UnspecifiedValue) and a device-output table would
        otherwise be two different jit signatures: one retrace per
        composition change (the kv_pages settle hazard again, pinned by
        tests/test_retrace_budget.py)."""
        if self._spec_k is None or self._spec_k == 0:
            if self._spec_k == 0 and self._draft_table is None:
                # K=0 (dense packing alone): the program never reads the
                # table, but the signature still carries one — a [B, 1]
                # placeholder keeps the dispatch shape static
                self._draft_table = jax.device_put(
                    jnp.zeros((self.config.max_batch_size, 1), jnp.int32),
                    self._table_sharding)
            return
        V = self.model_config.vocab_size
        B = self.config.max_batch_size

        def row_data(i):
            row = np.full((V,), -1, np.int32)
            slot = self._slots[i]
            if slot.request_id is not None and slot.prefilling is None:
                seq = np.asarray(
                    slot.prompt_ids + slot.generated, np.int64)
                if seq.shape[0] >= 2:
                    row[seq[:-1]] = seq[1:]
            return row

        if self._draft_table is None or self._draft_dirty is None:
            self._draft_table = jnp.asarray(
                np.stack([row_data(i) for i in range(B)]))
        elif self._draft_dirty:
            idx = sorted(self._draft_dirty)
            rows = np.stack([row_data(i) for i in idx])
            self._draft_table = self._draft_table.at[
                jnp.asarray(idx)].set(jnp.asarray(rows))
        self._draft_table = jax.device_put(
            self._draft_table, self._table_sharding)
        self._draft_dirty = set()

    @property
    def _replicated_sharding(self):
        """The canonical replicated NamedSharding small per-lane control
        arrays commit to before a mixed_decode dispatch, matching the
        program's pinned output spelling (one jit signature whether the
        array came from the host or from a previous dispatch's carry)."""
        return shd.named(self.mesh, jax.sharding.PartitionSpec())

    @property
    def _table_sharding(self):
        """Commit target for the draft table: the spelling GSPMD settles
        the mixed_decode table output on (parallel/sharding.py
        draft_table_pspec) — refresh-built and dispatch-output tables
        must share one jit signature."""
        return shd.named(self.mesh, shd.draft_table_pspec())

    def _upload(self, array: np.ndarray) -> jax.Array:
        """One host-to-device transfer of a launch's input, counted on the
        row of the dispatch under way (engine_dispatch_uploads_total): the
        one way such an input reaches the device.  Uncommitted, as
        `jnp.asarray` leaves it: the program's input shardings place it."""
        self._phases.uploaded()
        return jnp.asarray(array)

    def _sampling_state(self, params_list) -> Tuple[SamplingState, str]:
        """The lanes' sampling state on the device, in ONE transfer, and the
        path the sampler takes for it: for the launches whose program takes
        the state as an argument (the legacy decode, the dense path)."""
        with self._phases.span("sampling"):
            cols, sampler_path = SamplingState.planned(params_list)
            packed = SamplingState.packed(cols)
        return unpacked(self._upload(packed)), sampler_path

    def _dispatch_chunk(self, meta: dict, tokens_dev=None):
        """Launch one decode chunk (async); tokens_dev chains the previous
        chunk's device-resident last tokens, skipping a host round-trip."""
        phases = self._phases
        meta["_dispatched_at"] = phases.mark("launch")
        with phases.span("upload"):
            state, sampler_path = self._sampling_state(meta["params_list"])
            tokens = (tokens_dev if tokens_dev is not None
                      else self._upload(meta["tokens"]))
            pos = self._upload(meta["pos"])
            page_table = self._upload(meta["page_table"])
            active = self._upload(meta["active"])
            capacity = self._upload(meta["capacity"])
            counters = self._upload(meta["counters"])
            adapters = self._upload(meta["adapters"])
        with phases.span("account"):
            n_active = int(np.count_nonzero(meta["active"]))
            phases.launched(
                "decode", n_active, meta["page_table"].shape[1], 0, n_active,
                chained=tokens_dev is not None)
            self._sampler_dispatches[sampler_path].inc()
            self._work.forward(
                self._shapes.steps, meta["pos"], meta["active"],
                meta["capacity"], decode_steps=self._shapes.steps)
        with phases.span("call"):
            return self._call_chunk(meta, (
                self.params, tokens, pos, self.kv_pages, page_table, active,
                capacity, counters, state,
                jax.random.fold_in(self._base_rng, self._next_step()),
                adapters))

    def _call_chunk(self, meta: dict, args: tuple):
        """The decode program's variant that `meta` asks for, called."""
        want_lp = meta.get("want_logprobs", False)
        if meta.get("penalized"):
            fn = self._decode_penalized_lp_fn if want_lp else self._decode_penalized_fn
            chunk, self.kv_pages, self._penalty_counts = fn(
                *args, self._penalty_prompt, self._penalty_counts
            )
        else:
            fn = self._decode_lp_fn if want_lp else self._decode_fn
            chunk, self.kv_pages = fn(*args)
            if self._penalty_counts is not None:
                # a non-penalized chunk advances lanes without updating the
                # device counts; they are stale for every resident row now
                self._mark_penalty_dirty(None)
        return chunk

    async def _route_chunk(self, meta: dict, chunk) -> bool:
        """Read a finished chunk and stream its tokens.  True when any slot
        finished (the pipeline must drain: chained lanes are stale).  Async
        because the fetch awaits the device (loop stays responsive); slot
        state is only mutated in the sync stretch after the fetches, so a
        drain evicting a slot during the await is observed (request_id
        None) rather than raced."""
        steps = self._shapes.steps
        self._phases.mark("wait")
        if isinstance(chunk, tuple):  # logprobs variant: (tokens, lp, tv, ti)
            chunk_np = await self._fetch_async(chunk[0])  # [steps, B]
            lp_np = tuple([await self._fetch_async(a) for a in chunk[1:]])
        else:
            chunk_np = await self._fetch_async(chunk)  # [steps, B]
            lp_np = None
        self._phases.resumed(self._fetch_ready_at)
        step_s = self._clock.now() - meta["_dispatched_at"]
        ENGINE_STEP_DURATION.labels(model_name=self._mlabel).observe(step_s)
        self.telemetry.record_step(step_s)
        active = meta["active"]
        finished_any = False
        routed = 0  # tokens actually delivered — the speculative tail after
        # a mid-chunk EOS/stop is discarded and must not count as generated
        for i, slot in enumerate(self._slots):
            if slot.request_id is None or not active[i]:
                continue
            lane_steps = min(steps, int(meta["capacity"][i]) - int(meta["pos"][i]))
            for s in range(lane_steps):
                if slot.request_id is None:
                    break  # finished mid-chunk; discard speculative tail
                token = int(chunk_np[s, i])
                slot.pos += 1
                slot.generated.append(token)
                self._advance(slot, token, *self._lp_for(slot.params, lp_np, i, s))
                routed += 1
            if slot.request_id is None:
                finished_any = True
            elif slot.pos >= self.config.max_model_len:
                self._finish(slot, "length")
                finished_any = True
        GENERATED_TOKENS.labels(model_name=self._mlabel).inc(routed)
        if routed or finished_any:
            # stamp here, not only in the run loop: the depth-2 pipeline
            # can chain chunks for a long stretch without returning to it
            self._note_progress()
        return finished_any

    async def _decode_once(self):
        """Decode with a depth-2 dispatch pipeline: chunk N+1 launches
        (chained on N's device tokens) before N's tokens are fetched, so the
        host round-trip hides behind device compute.  Each routed chunk
        commits its own dispatch row; phases are recorded as they occur."""
        self._phases.mark("plan")
        with self._phases.span("prepare"):
            meta = self._prepare_chunk(prev=None)
        if meta is None:
            return
        chunk = self._dispatch_chunk(meta)
        while True:
            self._phases.mark("plan")
            meta2 = None
            chunk2 = None
            # chain when admission couldn't run anyway (no waiting work, or
            # no free slot to admit into) and no lane is guaranteed to finish
            # inside the in-flight chunk (a predictable max_tokens finish
            # would force a drain, wasting the whole chained chunk)
            admission_blocked = (
                not self._waiting or self._free_slot_index() is None
            )
            prefill_pending = any(
                s.prefilling is not None for s in self._slots
            )
            predictable_finish = any(
                s.request_id is not None
                and meta["active"][i]
                and len(s.generated) + self._shapes.steps
                >= s.params.max_tokens
                for i, s in enumerate(self._slots)
            )
            if (
                admission_blocked
                and not predictable_finish
                and not prefill_pending  # alternate with prefill chunks
                and not meta.get("penalized")
                # draining: no chaining — the drain loop must observe the
                # budget (and the preempt fault seam must run) between
                # every chunk, not once per arbitrarily long pipeline
                and not (self._stopped or self._draining)
            ):
                with self._phases.span("prepare"):
                    meta2 = self._prepare_chunk(prev=meta)
            if meta2 is not None:
                last_tokens = (
                    chunk[0][-1] if isinstance(chunk, tuple) else chunk[-1]
                )
                chunk2 = self._dispatch_chunk(meta2, tokens_dev=last_tokens)
                self._pipeline_busy = True
            finished_any = await self._route_chunk(meta, chunk)
            # flush streams while the chained chunk runs on device
            self._phases.mark("yield")
            await asyncio.sleep(0)
            self._commit_dispatch()
            if chunk2 is None:
                break
            meta, chunk = meta2, chunk2
            if finished_any or self._stopped or self._draining or (
                self._waiting and self._free_slot_index() is not None
            ):
                # in-flight chunk has stale lanes (or admission can now
                # proceed); drain and re-plan
                self._pipeline_busy = False
                await self._route_chunk(meta, chunk)
                break
        self._pipeline_busy = False
        self._flush_deferred_frees()

    # ---------------- unified ragged (mixed) stepping ----------------

    def _needs_legacy_step(self) -> bool:
        """Per-iteration fallback gate: the mixed program covers neither
        per-step logprobs nor sampling penalties (engine/compiled.py), so
        an iteration with any such lane seated runs the legacy dispatches
        — chunked prefill via prefill_chunk, decode via the penalized /
        logprob program variants."""
        for s in self._slots:
            if s.request_id is None:
                continue
            p = (s.prefilling["req"].params if s.prefilling is not None
                 else s.params)
            if p.has_penalties or p.logprobs is not None:
                return True
        return False

    async def _step_mixed(self) -> bool:
        """One engine step under the unified ragged program
        (docs/kernels.md): every prefilling slot contributes its next
        prompt chunk and every decode lane its next token slice — ONE
        device dispatch per step, so decode lanes keep advancing while
        prompts prefill (the prefill/decode scheduler barrier the legacy
        paths worked around).  Lanes whose prompt completes inside the
        dispatch seat and keep decoding in the same program (the scan
        tail), so a short request can prefill AND decode its whole budget
        in a single dispatch."""
        phases = self._phases
        phases.mark("plan")
        # `prepare` is all of `plan` that precedes the packing, so that the
        # parts account for the phase: the legacy gate, the decode lanes'
        # inputs, the gauges
        with phases.span("prepare"):
            legacy = self._needs_legacy_step()
            if not legacy:
                meta = self._prepare_chunk(prev=None)
                prefilling = [
                    (i, s) for i, s in enumerate(self._slots)
                    if s.request_id is not None and s.prefilling is not None
                ]
                self._set_occupancy_gauges(self._active_decode_slots())
        if legacy:
            self._deliver()  # the legacy paths hand over in place
            did = self._advance_prefills()
            active = self._active_decode_slots()
            self._set_occupancy_gauges(active)
            if active:
                await self._decode_once()
                did = True
            return did
        if meta is None and not prefilling:
            self._deliver()  # nothing to launch
            return False
        if self._dense_ok and not prefilling and meta is not None:
            # pure-decode step with the dense/speculative program
            # available: every lane packs a (K+1)-token slice at the
            # dense stride, K draft tokens verify per round, and the
            # next dispatch chains on this one's device carries
            # (docs/kernels.md) — the decode-heavy fast path.  A lane
            # within K tokens of its hard kv ceiling can never fit
            # another full (K+1)-token slice: the whole batch runs the
            # plain mixed path for that lane's final stretch (<= K+1
            # tokens, token-identical) instead of dispatching rounds the
            # device would skip forever.
            kp = (self._spec_k or 0) + 1
            if all(
                s.request_id is None or not meta["active"][i]
                or s.pos + kp <= self._dense_lane_cap
                for i, s in enumerate(self._slots)
            ):
                self._deliver()  # the dense path hands over in place
                await self._step_dense(meta)
                return True
        with phases.span("pack"):
            plan = self._plan_ragged(meta, prefilling)
        dispatched_at = phases.mark("launch")
        # everything the host built reaches the device in three transfers
        # (shapes.MixedLayout); the dispatch's key is folded in the program
        # from the base key, which stays on the device
        with phases.span("upload"):
            tokens_buf = self._upload(plan["tokens_buf"])
            lanes_buf = self._upload(plan["lanes_buf"])
            page_table = self._upload(plan["page_table"])
        with phases.span("call"):
            compiles = getattr(self._mixed_fn, "compiles", 0)
            out, self.kv_pages = self._mixed_fn(
                self.params, tokens_buf, lanes_buf, self.kv_pages,
                page_table, self._base_rng)
        with phases.span("account"):
            ran = (plan["tokens_buf"].shape[1], plan["page_table"].shape[1])
            phases.launched(
                "mixed", *ran, plan["prefill_tokens"], plan["decode_tokens"],
                compiled=getattr(self._mixed_fn, "compiles", 0) != compiles,
                need=plan["need"])
            self._loaded.ran(ran)
            self._dispatch_fits[plan["fit"]].inc()
            self._sampler_dispatches[plan["sampler_path"]].inc()
            self._work.packed(plan, ran[1], self._shapes.steps)
        phases.mark("wait")
        # the fetch is handed to its worker first, so that the result is
        # stamped when the device has it and a delivery that outlasts the
        # device shows as wait_lag; then the previous dispatch's tokens go
        # to their streams while this one runs, and the streams' writes
        # happen in the turns of the event loop that the await leaves
        chunk_np = await self._fetch_async(out, self._deliver_overlapped)
        phases.resumed(self._fetch_ready_at)
        self._route_mixed(plan, self._work.fetched(chunk_np), dispatched_at)
        return True

    def _plan_ragged(self, meta: Optional[dict], prefilling) -> dict:
        """Pack this step's ragged token buffer (host side, numpy): decode
        lanes first (one token each), then each prefilling slot's next
        chunk, within one largest-token-bucket budget.  Slices start at
        multiples of the shapes' alignment (the Pallas kernel's
        one-sequence-per-block invariant; 1 on the XLA reference path).
        Returns the packed arrays plus per-lane routing windows."""
        B = self.config.max_batch_size
        ps = self.config.page_size
        steps = self._shapes.steps
        aligned = self._shapes.aligned
        budget = self._shapes.token_budget

        q_start = np.zeros((B,), np.int32)
        q_len = np.zeros((B,), np.int32)
        kv_start = np.zeros((B,), np.int32)
        last_idx = np.zeros((B,), np.int32)
        joins = np.zeros((B,), bool)
        scan_tok0 = np.full((B,), -1, np.int32)
        scan_pos0 = np.zeros((B,), np.int32)
        step0_emits = np.zeros((B,), np.int32)
        capacity = np.zeros((B,), np.int32)
        counters = np.zeros((B,), np.int32)
        adapters = np.full((B,), -1, np.int32)
        params_list = [SamplingParams() for _ in range(B)]
        tok_list: List[int] = []
        seq_list: List[int] = []
        pos_list: List[int] = []
        consume: Dict[int, tuple] = {}  # lane -> (first row, n rows)
        chunks: List[tuple] = []  # (lane, chunk len, final?)
        offset = 0
        n_decode = 0

        def place(lane: int, tokens: List[int], positions: List[int]):
            nonlocal offset, budget
            n = len(tokens)
            pad = aligned(n) - n
            tok_list.extend(tokens + [0] * pad)
            seq_list.extend([lane] * n + [-1] * pad)
            pos_list.extend(positions + [0] * pad)
            q_start[lane] = offset
            q_len[lane] = n
            last_idx[lane] = offset + n - 1
            offset += aligned(n)
            budget -= aligned(n)

        if meta is not None:
            for i, slot in enumerate(self._slots):
                if not meta["active"][i]:
                    continue
                pos = int(meta["pos"][i])
                cap = int(meta["capacity"][i])
                place(i, [int(meta["tokens"][i])], [pos])
                kv_start[i] = pos
                joins[i] = True
                scan_pos0[i] = pos + 1
                step0_emits[i] = 1
                capacity[i] = cap
                counters[i] = int(meta["counters"][i])
                adapters[i] = int(meta["adapters"][i])
                params_list[i] = slot.params
                consume[i] = (0, min(steps, cap - pos))
                n_decode += 1

        n_prefill_tokens = 0
        for i, slot in prefilling:
            pf = slot.prefilling
            req = pf["req"]
            seq, done = pf["seq"], pf["done"]
            total = len(seq)
            n = min(total - done, budget)
            if n <= 0:
                continue  # out of token budget; this lane rides next step
            place(i, list(seq[done:done + n]),
                  list(range(done, done + n)))
            kv_start[i] = done
            cap = len(slot.pages) * ps
            capacity[i] = cap
            adapters[i] = req.adapter_id
            params_list[i] = req.params
            final = done + n >= total
            if final:
                joins[i] = True
                if req.resume is not None:
                    # the ragged sample at a re-prefill boundary is
                    # discarded; the scan continues from the checkpoint's
                    # last generated token at its original position
                    gen = req.resume["generated"]
                    scan_tok0[i] = int(gen[-1])
                    scan_pos0[i] = int(req.resume["pos"])
                    counters[i] = len(gen)
                    consume[i] = (1, max(0, min(
                        steps - 1, cap - int(req.resume["pos"]))))
                else:
                    scan_pos0[i] = total
                    step0_emits[i] = 1
                    # row 0 (the first token) is emitted at seating; the
                    # consume window covers the scan tail only
                    consume[i] = (1, max(0, min(steps - 1, cap - total)))
            else:
                consume[i] = (0, 0)
            chunks.append((i, n, final))
            n_prefill_tokens += n

        # the pair this dispatch needs: the packed slices' bucket, and a
        # table as wide as the longest SEATED lane, in this dispatch or not;
        # it runs in the smallest pair the program is loaded in that holds
        # it (shapes.LoadedPairs.fit), compiling only where none does
        rows = self._page_rows(np.ones((B,), bool))
        need = (self._shapes.tokens(max(offset, 1)), self._width_of(rows))
        (tokens, width), fit = self._loaded.fit(*need)
        page_table = self._page_table(rows, width)
        with self._phases.span("sampling"):
            sampler, sampler_path = SamplingState.planned(params_list)
        # what the program takes, in the two buffers it takes it in; the
        # buffer pads the packed slices to T with rows of no lane
        tokens_buf, lanes_buf = MixedLayout(tokens, B, width).pack(dict(
            sampler,
            q_tokens=tok_list, token_seq=seq_list, token_pos=pos_list,
            q_start=q_start, q_len=q_len, kv_start=kv_start,
            last_idx=last_idx, joins=joins, scan_tok0=scan_tok0,
            scan_pos0=scan_pos0, step0_emits=step0_emits, capacity=capacity,
            counters=counters, adapters=adapters), self._next_step())
        return {
            "tokens_buf": tokens_buf,
            "lanes_buf": lanes_buf,
            "page_table": page_table,
            # the columns the launch's accounting and the tests read
            "q_len": q_len,
            "kv_start": kv_start,
            "joins": joins,
            "scan_pos0": scan_pos0,
            "capacity": capacity,
            "sampler_path": sampler_path,
            "need": need,
            "fit": fit,
            "consume": consume,
            "chunks": chunks,
            "prefill_tokens": n_prefill_tokens,
            "decode_tokens": n_decode,
        }

    def _route_mixed(self, plan: dict, chunk_np: np.ndarray,
                     dispatched_at: float) -> None:
        """Consume one mixed dispatch's [steps, B] tokens: advance chunk
        cursors, seat lanes whose prompt completed (with their first
        token), then each joining lane's scan window.  Slots evicted
        while the dispatch was in flight (drain) are observed as empty and
        their speculative tokens discarded — same contract as the legacy
        _route_chunk.  This is the ADVANCE: it changes the engine's state
        from the ids alone and leaves what the tokens owe their streams on
        _undelivered, for _step_mixed to hand over once the next dispatch
        is launched; a lane with stop strings is handed its tokens here,
        and so is every lane when nothing is left to launch."""
        now = self._clock.now()
        step_s = now - dispatched_at
        ENGINE_STEP_DURATION.labels(model_name=self._mlabel).observe(step_s)
        self.telemetry.record_step(step_s)
        if plan["chunks"] and plan["decode_tokens"] == 0:
            # prefill-chunk duration stays meaningful only for dispatches
            # that carried NO decode lanes: a fused mixed step's time is
            # dominated by the decode scan, and recording it here would
            # inflate prefill-chunk percentiles by the whole scan cost
            ENGINE_PREFILL_CHUNK_DURATION.labels(
                model_name=self._mlabel).observe(step_s)
            self.telemetry.record_prefill_chunk(step_s)
        comp = {
            "prefill_tokens": plan["prefill_tokens"],
            "decode_tokens": plan["decode_tokens"],
        }
        self.last_step_composition = comp
        g = ENGINE_STEP_BATCH_COMPOSITION
        g.labels(model_name=self._mlabel, role="prefill_tokens").set(
            comp["prefill_tokens"])
        g.labels(model_name=self._mlabel, role="decode_tokens").set(
            comp["decode_tokens"])
        for i, n, final in plan["chunks"]:
            slot = self._slots[i]
            if slot.request_id is None or slot.prefilling is None:
                continue  # evicted mid-dispatch
            pf = slot.prefilling
            req = pf["req"]
            pf["done"] += n
            tl = req.timeline
            if tl is not None:
                tl.mark_prefill_start(dispatched_at)
                tl.mark_prefill_end(now)
            if req.adapter_id < 0 and req.resume is None:
                covered = min(pf["done"], len(req.prompt_ids))
                with self._phases.span("register"):
                    self._prefix_cache.register(
                        req.prompt_ids[:covered], slot.pages,
                        start_page=pf.get("registered", 0))
                pf["registered"] = covered // self.config.page_size
            if not final:
                continue
            self._complete_prefilling(
                i, slot, req, int(chunk_np[0, i]), defer=True)
        routed = 0
        for i in sorted(plan["consume"]):
            first_row, n_rows = plan["consume"][i]
            slot = self._slots[i]
            for s in range(first_row, first_row + n_rows):
                if slot.request_id is None:
                    break  # finished (or evicted); discard speculative tail
                token = int(chunk_np[s, i])
                slot.pos += 1
                slot.generated.append(token)
                self._advance(slot, token, defer=True)
                routed += 1
        GENERATED_TOKENS.labels(model_name=self._mlabel).inc(routed)
        if routed or plan["chunks"]:
            self._note_progress()
        if not self._has_live_work():
            # the last lanes finished and nothing waits: no launch will
            # follow for the delivery to hide behind
            self._deliver()

    # ---------------- dense / speculative decode stepping ----------------

    def _plan_dense(self, meta: dict) -> dict:
        """Host inputs for one `mixed_decode` dispatch, derived from a
        _prepare_chunk meta (growth + preemption already ran there).  The
        draft table is re-seeded for dirty rows first, so every lane's
        drafter knows its prompt + everything emitted so far."""
        self._refresh_draft_table()
        return {
            "tokens": meta["tokens"],
            "pos": meta["pos"],
            "live": meta["active"],
            "capacity": meta["capacity"],
            "counters": meta["counters"],
            "adapters": meta["adapters"],
            "page_table": meta["page_table"],
            "params_list": meta["params_list"],
        }

    def _plan_dense_chained(self, prev: dict) -> Optional[dict]:
        """Plan a dispatch chained on an in-flight one: positions, tokens
        and counters come from the DEVICE carry (never fetched), so the
        host only refreshes what it owns — page capacity (grown toward
        the worst case of two in-flight dispatches) and the page table.
        No preemption while the pipeline is busy, same as the legacy
        depth-2 chain."""
        B = self.config.max_batch_size
        adv = self._max_step_advance
        kp = (self._spec_k or 0) + 1
        live = prev["live"]
        capacity = np.zeros((B,), np.int32)
        any_live = False
        for i, slot in enumerate(self._slots):
            if slot.request_id is None or not live[i]:
                continue
            if slot.pos + adv + kp > self._dense_lane_cap:
                # the in-flight dispatch may carry this lane into the
                # zone where no further (K+1)-token slice fits its hard
                # kv ceiling — drain the pipeline instead of chaining a
                # dispatch the device could only skip (the unchained
                # re-plan falls back to the mixed path for the stretch)
                return None
            # device pos after the in-flight dispatch is at most
            # slot.pos + adv; cover one more full dispatch beyond that,
            # capped at max_model_len — positions past it can never hold
            # usable tokens, and growing pages for them steals allocator
            # headroom from other lanes (same cap _prepare_chunk applies)
            grow = min(2 * adv, self.config.max_model_len - slot.pos)
            if grow > 0:
                self._ensure_pages_at(slot, slot.pos, grow)
            capacity[i] = len(slot.pages) * self.config.page_size
            any_live = True
        if not any_live:
            return None
        page_table = self._page_table_of(live)
        return {
            "tokens": prev["tokens"],  # unused (device carry chains)
            "pos": prev["pos"],
            "live": live,
            "capacity": capacity,
            "counters": prev["counters"],
            "adapters": prev["adapters"],
            "page_table": page_table,
            # the lanes are the in-flight dispatch's: so is their sampling
            # state, already on the device
            "state": prev["state"],
            "sampler_path": prev["sampler_path"],
        }

    def _dispatch_dense(self, plan: dict, chain: Optional[dict] = None):
        """Launch one mixed_decode dispatch; `chain` threads the previous
        dispatch's device (token, pos, counters) carry so the chained
        program starts exactly where the in-flight one ends — no host
        round-trip between them."""
        phases = self._phases
        plan["_dispatched_at"] = phases.mark("launch")
        if "state" not in plan:
            with phases.span("upload"):
                plan["state"], plan["sampler_path"] = self._sampling_state(
                    plan["params_list"])
        with phases.span("account"):
            n_tokens = (int(np.count_nonzero(plan["live"]))
                        * ((self._spec_k or 0) + 1))
            phases.launched(
                "mixed_decode", n_tokens, plan["page_table"].shape[1], 0,
                n_tokens, chained=chain is not None)
            self._sampler_dispatches[plan["sampler_path"]].inc()
            self._work.forward(self._shapes.steps)  # rounds of the packed step
        with phases.span("upload"):
            if chain is not None:
                tok, pos, cnt = chain["carry"]
            else:
                # committed to the same replicated spelling the program pins
                # its carry outputs to: chained and unchained dispatches must
                # share ONE jit signature (see _refresh_draft_table)
                rep = self._replicated_sharding
                tok = jax.device_put(self._upload(plan["tokens"]), rep)
                pos = jax.device_put(self._upload(plan["pos"]), rep)
                cnt = jax.device_put(self._upload(plan["counters"]), rep)
            page_table = self._upload(plan["page_table"])
            live = self._upload(plan["live"])
            capacity = self._upload(plan["capacity"])
            adapters = self._upload(plan["adapters"])
        with phases.span("call"):
            rng = jax.random.fold_in(self._base_rng, self._next_step())
            out = self._mixed_decode_fn(
                self.params, tok, pos, self.kv_pages, page_table, live,
                capacity, cnt, self._draft_table, plan["state"], rng,
                adapters)
        toks, n_emit_dev, self.kv_pages, self._draft_table, tok_o, pos_o, cnt_o = out
        return {"toks": toks, "n": n_emit_dev, "carry": (tok_o, pos_o, cnt_o)}

    async def _route_dense(self, plan: dict, chunk: dict) -> bool:
        """Consume one mixed_decode dispatch: per round, each live lane
        emits its accepted-prefix + bonus tokens (0 when the round was
        skipped for capacity).  Slots evicted while the dispatch was in
        flight are observed empty and their tokens discarded — only
        ACCEPTED, routed tokens ever reach slot.generated, so checkpoints
        (drain/preempt/hedge) can never carry an unverified draft tail.
        Returns (any lane finished, any token routed)."""
        self._phases.mark("wait")
        toks_np = await self._fetch_async(chunk["toks"])  # [rounds, B, K+1]
        n_np = await self._fetch_async(chunk["n"])  # [rounds, B]
        self._phases.resumed(self._fetch_ready_at)
        step_s = self._clock.now() - plan["_dispatched_at"]
        ENGINE_STEP_DURATION.labels(model_name=self._mlabel).observe(step_s)
        self.telemetry.record_step(step_s)
        k_drafts = self._spec_k or 0
        rounds = toks_np.shape[0]
        live = plan["live"]
        routed = 0
        drafted = 0
        accepted = 0
        finished_any = False
        for i, slot in enumerate(self._slots):
            if not live[i]:
                continue
            if slot.request_id is None:
                # evicted (cancel/preempt/drain) while the dispatch was in
                # flight: the whole lane is discarded — no stream consumed
                # its drafts, so the acceptance-rate signal skips it too
                finished_any = True
                continue
            for r in range(rounds):
                n = int(n_np[r, i])
                if n <= 0:
                    continue  # capacity-skipped round (or inactive)
                emitted = 0
                for j in range(n):
                    token = int(toks_np[r, i, j])
                    slot.pos += 1
                    slot.generated.append(token)
                    self._advance(slot, token)
                    routed += 1
                    emitted += 1
                    if slot.request_id is None:
                        break  # finished at this token; discard the tail
                # count only what the stream actually consumed: of the
                # emitted tokens, all but the round's bonus sample are
                # accepted drafts (a mid-round finish consumed drafts
                # only), keeping spec_stats an emitted-token-exact signal
                drafted += k_drafts
                accepted += min(emitted, n - 1)
                if slot.request_id is None:
                    finished_any = True
                    break
            if (slot.request_id is not None
                    and slot.pos >= self.config.max_model_len):
                self._finish(slot, "length")
                finished_any = True
        GENERATED_TOKENS.labels(model_name=self._mlabel).inc(routed)
        if k_drafts > 0:
            s = SPEC_TOKENS
            s.labels(model_name=self._mlabel, outcome="drafted").inc(drafted)
            s.labels(model_name=self._mlabel, outcome="accepted").inc(accepted)
            s.labels(model_name=self._mlabel,
                     outcome="rejected").inc(drafted - accepted)
            self.spec_stats["drafted"] += drafted
            self.spec_stats["accepted"] += accepted
            self.spec_stats["rejected"] += drafted - accepted
        comp = {
            "prefill_tokens": 0,
            # token counts, matching the mixed program's semantics: each
            # live lane contributes a (K+1)-token verify slice to the
            # packed buffer per round
            "decode_tokens": int(np.count_nonzero(live)) * (k_drafts + 1),
            "spec_accepted_tokens": accepted,
        }
        self.last_step_composition = comp
        g = ENGINE_STEP_BATCH_COMPOSITION
        for role, value in comp.items():
            g.labels(model_name=self._mlabel, role=role).set(value)
        if routed or finished_any:
            self._note_progress()
        return finished_any, routed > 0

    async def _step_dense(self, meta: dict) -> None:
        """Dense/speculative decode with the depth-2 dispatch pipeline
        restored on the mixed path: dispatch N+1 launches — chained on
        N's device (token, pos, counters) carry — before N's tokens are
        fetched, so draft+verify of step N+1 overlaps routing of step N
        and the host round-trip hides behind device compute.  Each routed
        dispatch commits its own row; phases are recorded as they occur."""
        with self._phases.span("pack"):
            plan = self._plan_dense(meta)
        chunk = self._dispatch_dense(plan)
        while True:
            self._phases.mark("plan")
            plan2 = None
            chunk2 = None
            admission_blocked = (
                not self._waiting or self._free_slot_index() is None
            )
            # a lane guaranteed to hit max_tokens inside the in-flight
            # dispatch forces a pipeline drain anyway — don't chain into
            # a dispatch that would be wholly discarded
            predictable_finish = any(
                s.request_id is not None
                and plan["live"][i]
                and len(s.generated) + self._max_step_advance
                >= s.params.max_tokens
                for i, s in enumerate(self._slots)
            )
            if (
                admission_blocked
                and not predictable_finish
                and not (self._stopped or self._draining)
            ):
                with self._phases.span("pack"):
                    plan2 = self._plan_dense_chained(plan)
            if plan2 is not None:
                chunk2 = self._dispatch_dense(plan2, chain=chunk)
                self._pipeline_busy = True
            finished_any, routed_any = await self._route_dense(plan, chunk)
            # flush streams while the chained dispatch runs on device
            self._phases.mark("yield")
            await asyncio.sleep(0)
            self._commit_dispatch()
            if chunk2 is None:
                break
            plan, chunk = plan2, chunk2
            if (finished_any or not routed_any
                    or self._stopped or self._draining or (
                        self._waiting
                        and self._free_slot_index() is not None)):
                # in-flight dispatch has stale lanes, admission can
                # proceed, or every round was capacity-skipped (the
                # lanes need host-side growth or the mixed-path ceiling
                # fallback): drain the pipeline and re-plan
                self._pipeline_busy = False
                await self._route_dense(plan, chunk)
                break
        self._pipeline_busy = False
        self._flush_deferred_frees()

    def _advance(self, slot: _Slot, token: int,
                 logprob: Optional[float] = None,
                 top_logprobs: Optional[List[tuple]] = None,
                 defer: bool = False) -> None:
        """One token: apply stop conditions; stream it.  This is the
        state's half: every finish the ids decide (EOS under min_tokens /
        ignore_eos, max_tokens) with its page frees and the lane's reset.
        The stream's half (_hand_over) runs in place (the legacy and dense
        paths, which keep their own chain), or with `defer` is left on
        _undelivered until the next dispatch is launched (_deliver).  A
        lane with stop strings is never deferred: its text decides whether
        it goes on."""
        params = slot.params
        n_gen = len(slot.generated)
        is_eos = (
            token == self.tokenizer.eos_token_id
            and not params.ignore_eos
            and n_gen > params.min_tokens
        )
        if is_eos:
            finish_reason = "stop"
        elif n_gen >= params.max_tokens:
            finish_reason = "length"
        else:
            finish_reason = None
        owed = _Delivery(slot, token, finish_reason, is_eos,
                         self._phases.serial, logprob, top_logprobs)
        if defer and not slot.stop_texts:
            self._undelivered.append(owed)
        else:
            finish_reason = self._hand_over(owed)
            self._phases.delivered("inline", 1)
        if finish_reason is not None:
            self._release(slot)
            self._wake.set()

    def _release(self, slot: _Slot) -> None:
        """A finished lane gives its pages back and is reset: the part
        `register` of whichever phase the finish falls in."""
        with self._phases.span("register"):
            self._free_pages(slot.pages)
            slot.reset()
            self._mark_penalty_dirty(self._slots.index(slot))

    def _finish(self, slot: _Slot, reason: str):
        """Close a lane's stream without a token.  The closing chunk goes
        behind whatever is still owed (the lane's own tokens may be)."""
        owed = _Delivery(slot, -1, reason, False, self._phases.serial)
        if self._undelivered:
            self._undelivered.append(owed)
        else:
            self._hand_over(owed)
            self._phases.delivered("inline", 1)
        self._release(slot)

    def _hand_over(self, owed: _Delivery) -> Optional[str]:
        """The stream's half of one token: the timeline's stamp (this
        reading of the clock, the PRODUCING dispatch's serial), the text,
        the stop strings, the output on the request's queue.  Returns the
        finish reason, which only here can come from a stop string."""
        tl = owed.timeline
        closing = owed.token < 0  # _finish: no token, the last chunk
        if tl is not None and not closing:
            first = tl.first_token_at is None
            tl.mark_token(self._clock.now(), owed.serial)
            if first and tl.dispatches_to_first_token is not None:
                ENGINE_FIRST_TOKEN_DISPATCHES.labels(
                    model_name=self._mlabel).observe(
                        tl.dispatches_to_first_token)
        detok = owed.detok
        delta = "" if closing or owed.is_eos else detok.push(owed.token)
        text = detok.text
        finish_reason = owed.finish_reason
        if finish_reason is None:
            for stop in owed.stops:
                if stop and stop in text:
                    cut = text.index(stop)
                    delta = delta[: max(0, len(delta) - (len(text) - cut))]
                    finish_reason = "stop"
                    break
        owed.queue.put_nowait(GenerationOutput(
            token_id=owed.token,
            text_delta=delta,
            finished=finish_reason is not None,
            finish_reason=finish_reason,
            num_generated=owed.n_generated,
            num_prompt_tokens=owed.n_prompt,
            cumulative_text=text,
            logprob=owed.logprob,
            top_logprobs=owed.top_logprobs,
        ))
        if finish_reason is not None:
            self._record_terminal(tl, finish_reason)
        return finish_reason

    def _deliver(self, when: str = "inline") -> None:
        """Hand over everything still owed, in the order it was produced.
        `overlapped` from the one call that follows a launch; every other
        caller is about to put something else on a stream, or has nothing
        to launch.  A record is dropped before it is handed over, so that
        one that raises is not met again by the crash handler's call."""
        owed = self._undelivered
        if not owed:
            return
        tokens = len(owed)
        with self._phases.span("deliver"):
            while owed:
                self._hand_over(owed.popleft())
        self._phases.delivered(when, tokens)

    def _deliver_overlapped(self) -> None:
        self._deliver("overlapped")

    def _next_step(self) -> int:
        self._step_counter += 1
        return self._step_counter
