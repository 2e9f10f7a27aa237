"""Engine-level data parallelism: dp independent LLMEngine replicas over
disjoint tp*sp-sized device groups, with least-loaded request routing.

Decode batches have no cross-request math, so a lockstep `data` mesh axis
would buy nothing and cost a synchronized schedule (every replica waiting on
the slowest prefill) plus per-step collectives.  Independent replicas are
the TPU-native answer and match the semantics the reference reaches through
vLLM's DP ranks (llm_inference_service_types.go:679-700 dataParallelism):
linear decode throughput, isolated failure domains, per-replica KV space.
"""

from __future__ import annotations

import asyncio
from dataclasses import replace
from typing import Any, AsyncIterator, List, Optional, Tuple

import jax
import numpy as np

from ..logging import logger
from ..models import llama
from .engine import EngineConfig, GenerationOutput, LLMEngine
from .sampling import SamplingParams
from .tokenizer import BaseTokenizer


class DataParallelEngine:
    """API-compatible with LLMEngine (start/stop/generate/...); routes each
    request to the least-loaded replica."""

    def __init__(
        self,
        model_config: llama.LlamaConfig,
        engine_config: EngineConfig,
        tokenizer: BaseTokenizer,
        params: Optional[Any] = None,
        rng_seed: int = 0,
        devices: Optional[list] = None,
        checkpoint_label: Optional[str] = None,
        lora_adapters: Optional[dict] = None,
    ):
        dp = engine_config.dp
        if dp < 2:
            raise ValueError("DataParallelEngine needs dp >= 2; use LLMEngine")
        devices = list(devices) if devices is not None else list(jax.devices())
        per_replica = engine_config.tp * engine_config.sp * engine_config.pp
        if dp * per_replica > len(devices):
            raise ValueError(
                f"dp={dp} x (tp*sp*pp)={per_replica} needs {dp * per_replica} "
                f"devices, have {len(devices)}"
            )
        self.config = engine_config
        self.model_config = model_config
        self.tokenizer = tokenizer
        # stack adapters ONCE; replicas shard the same host arrays
        lora_stacked = None
        if lora_adapters:
            from ..models import lora as lora_mod

            lora_stacked = lora_mod.stack_adapters(
                lora_adapters, model_config.n_layers, dtype=model_config.dtype
            )
        replica_cfg = replace(engine_config, dp=1)
        self.replicas: List[LLMEngine] = [
            LLMEngine(
                model_config,
                replica_cfg,
                tokenizer,
                params=params,
                rng_seed=rng_seed + g,
                devices=devices[g * per_replica : (g + 1) * per_replica],
                metrics_label=f"engine-dp{g}",
                # one weights identity shared by every dp group (NOT the
                # per-group metrics label): a checkpoint from any group
                # resumes on any other
                checkpoint_label=checkpoint_label or "engine",
                lora_stacked=lora_stacked,
            )
            for g in range(dp)
        ]
        self.cache_config = self.replicas[0].cache_config
        self.adapter_ids = self.replicas[0].adapter_ids
        self.mesh = self.replicas[0].mesh  # compat: a replica's submesh
        self._rr = 0  # round-robin cursor for equal-load tie-breaks

    # ---------------- lifecycle ----------------

    async def start(self):
        for eng in self.replicas:
            await eng.start()
        logger.info(
            "DP engine started: %d replicas x (tp=%d, sp=%d)",
            len(self.replicas), self.config.tp, self.config.sp,
        )

    async def stop(self):
        await asyncio.gather(*[eng.stop() for eng in self.replicas])

    @property
    def running(self) -> bool:
        return all(eng.running for eng in self.replicas)

    @property
    def wedged(self) -> bool:
        """Any replica wedged wedges the pod: its slice of traffic would
        hang forever, and a restart re-homes all replicas together."""
        return any(eng.wedged for eng in self.replicas)

    @property
    def draining(self) -> bool:
        return any(eng.draining for eng in self.replicas)

    @property
    def on_loop_crash(self):
        return self.replicas[0].on_loop_crash

    @on_loop_crash.setter
    def on_loop_crash(self, hook) -> None:
        """Any replica's run loop dying is fatal to the pod (same reasoning
        as `wedged`): its slice of traffic would otherwise hang."""
        for eng in self.replicas:
            eng.on_loop_crash = hook

    def scheduler_state(self, max_digests: int = 512) -> dict:
        """The pod's load in the single-engine shape the REST state handler
        and the EPP read (summed queues and pages), with every replica's
        own snapshot — its devices included — under "replicas"."""
        states = [eng.scheduler_state(max_digests) for eng in self.replicas]
        return {
            "queue_depth": sum(s["queue_depth"] for s in states),
            "inflight": sum(s["inflight"] for s in states),
            "free_pages": sum(s["free_pages"] for s in states),
            "page_size": self.config.page_size,
            "running": self.running,
            "wedged": self.wedged,
            "replicas": states,
        }

    async def drain(self, deadline=None, clock=None,
                    poll_s: float = 0.01) -> list:
        """Drain every dp group concurrently against the shared budget;
        the pod's checkpoints are the aggregate (lifecycle drain —
        docs/lifecycle.md)."""
        results = await asyncio.gather(
            *[eng.drain(deadline, clock=clock, poll_s=poll_s)
              for eng in self.replicas]
        )
        return [ckpt for per_replica in results for ckpt in per_replica]

    def resume_generation(
        self, checkpoint, request_id: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        """Re-seat a drained/preempted generation on the least-loaded dp
        group (all groups share one weights identity, so any accepts it)."""
        return self._pick().resume_generation(checkpoint, request_id=request_id)

    # ---------------- routing ----------------

    def _load(self, eng: LLMEngine) -> Tuple[int, int]:
        """(queued+active requests, -free pages): lower routes first."""
        active = sum(1 for s in eng._slots if s.request_id is not None)
        return (len(eng._waiting) + active, -eng.allocator.free_pages)

    def _pick(self) -> LLMEngine:
        """Least-loaded replica; equal loads rotate round-robin (submission
        happens before the request lands in a replica's queue — async
        generator bodies run lazily — so load alone can't separate a burst
        of simultaneous submissions)."""
        n = len(self.replicas)
        best = min(
            range(n),
            key=lambda g: (self._load(self.replicas[g]), (g - self._rr) % n),
        )
        self._rr = (best + 1) % n
        return self.replicas[best]

    # ---------------- request API (LLMEngine-compatible) ----------------

    def generate(
        self,
        prompt_ids: List[int],
        params: SamplingParams,
        request_id: Optional[str] = None,
        adapter: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        return self._pick().generate(
            prompt_ids, params, request_id=request_id, adapter=adapter
        )

    def generate_injected(
        self,
        prompt_ids: List[int],
        params: SamplingParams,
        kv_data: np.ndarray,
        first_token: int,
        request_id: Optional[str] = None,
        adapter: Optional[str] = None,
    ) -> AsyncIterator[GenerationOutput]:
        return self._pick().generate_injected(
            prompt_ids, params, kv_data, first_token, request_id=request_id,
            adapter=adapter,
        )

    async def prefill_detached(
        self, prompt_ids: List[int], params: SamplingParams,
        adapter: Optional[str] = None,
    ) -> Tuple[int, np.ndarray]:
        return await self._pick().prefill_detached(prompt_ids, params, adapter=adapter)

    def telemetry_snapshot(self) -> dict:
        """Per-group timelines/percentiles keyed by the group's metrics
        label (GET /admin/telemetry; the groups are independent engines,
        so their latency windows must not be merged into one percentile)."""
        return {
            eng._mlabel: eng.telemetry_snapshot() for eng in self.replicas
        }

    def cancel(self, request_id: str) -> None:
        for eng in self.replicas:
            eng.cancel(request_id)


def build_engine(
    model_config: llama.LlamaConfig,
    engine_config: EngineConfig,
    tokenizer: BaseTokenizer,
    params: Optional[Any] = None,
    rng_seed: int = 0,
    lora_adapters: Optional[dict] = None,
    checkpoint_label: Optional[str] = None,
):
    """LLMEngine for dp=1, DataParallelEngine for dp>1.

    checkpoint_label is the weights identity stamped into generation
    checkpoints — pass the served model's name so resume_generation can
    refuse checkpoints captured against different weights (every engine
    defaulting to the same label would make that guard vacuous)."""
    cls = DataParallelEngine if engine_config.dp > 1 else LLMEngine
    return cls(model_config, engine_config, tokenizer, params=params,
               rng_seed=rng_seed, lora_adapters=lora_adapters,
               checkpoint_label=checkpoint_label)
