"""Generative runtime: the JAX/TPU analogue of the reference
huggingfaceserver vLLM path.

`JAXGenerativeModel` implements the OpenAI model ABCs on top of
engine.LLMEngine: completions + chat (templated), streaming via async
iterators feeding SSE.

Parity: python/huggingfaceserver/huggingfaceserver/vllm/vllm_model.py:55
(VLLMModel.start_engine :83, create_completion/create_chat_completion :273);
engine roles swapped from AsyncLLM/CUDA to LLMEngine/XLA.

Entrypoint:
    python -m kserve_tpu.runtimes.generative_server \
        --model_name=llm --model_dir=/mnt/models [--tensor_parallel_size=N]
    # no checkpoint? --model_config=tiny|llama3-1b|llama3-8b --random_weights
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import AsyncIterator, List, Optional, Union

from ..engine.aot_cache import aot_cache_dir_from_env
from ..engine.types import spec_decode_k_from_env
from ..engine.watchdog import watchdog_enabled_from_env
from ..kvstore.persist import kv_persist_dir_from_env
from ..engine.engine import EngineConfig, LLMEngine
from ..engine.limits import resolve_serving
from ..engine.sampling import SamplingParams
from ..engine.tokenizer import load_tokenizer
from ..errors import InvalidInput
from ..lifecycle import (
    CHECKPOINT_HEADER,
    GenerationCheckpoint,
    GenerationPreempted,
    ReplicaDrainingError,
)
from ..logging import logger
from ..model_server import ModelServer, build_arg_parser
from ..models import llama
from ..protocol.openai.openai_model import OpenAIGenerativeModel
from ..protocol.openai.types import (
    ChatCompletion,
    ChatCompletionChoice,
    ChatCompletionChunk,
    ChatCompletionChunkChoice,
    ChatCompletionChunkDelta,
    ChatCompletionLogprob,
    ChatCompletionLogprobs,
    ChatCompletionLogprobsContent,
    ChatCompletionRequest,
    ChatCompletionResponseMessage,
    Completion,
    CompletionChoice,
    CompletionLogprobs,
    CompletionRequest,
    UsageInfo,
    random_uuid,
)

_NAMED_CONFIGS = {
    "tiny": llama.LlamaConfig.tiny,
    "llama3-1b": llama.LlamaConfig.llama3_1b,
    "llama3-8b": llama.LlamaConfig.llama3_8b,
    "qwen3-0.6b": llama.LlamaConfig.qwen3_0_6b,
    "gemma2-2b": llama.LlamaConfig.gemma2_2b,
}


class JAXGenerativeModel(OpenAIGenerativeModel):
    def __init__(
        self,
        name: str,
        model_dir: Optional[str] = None,
        model_config: Optional[llama.LlamaConfig] = None,
        engine_config: Optional[EngineConfig] = None,
        random_weights: bool = False,
        role: str = "both",  # both | prefill | decode (P/D disaggregation)
        prefill_url: Optional[str] = None,  # decode role: prefill peer base URL
        lora_adapters: Optional[dict] = None,  # name -> local adapter dir
    ):
        super().__init__(name)
        self.model_dir = model_dir
        self._model_config = model_config
        self.engine_config = engine_config or EngineConfig()
        self.random_weights = random_weights
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        if role == "decode" and not prefill_url:
            # silently serving monolithically would hide that the operator's
            # disaggregated topology is not in effect
            raise ValueError("role=decode requires --prefill_url (or $PREFILL_URL)")
        self.role = role
        self.prefill_url = prefill_url
        self.lora_adapters = lora_adapters or {}
        # adapters are addressable via the OpenAI `model` field: the
        # registry resolves these aliases back to this model and /v1/models
        # lists them (vLLM semantics)
        self.aliases = tuple(sorted(self.lora_adapters))
        self._prefill_client = None
        self.engine: Optional[LLMEngine] = None
        self.tokenizer = None

    def load(self) -> bool:
        """Resolve config/tokenizer/weights; engine starts in start_engine()
        (inside the server event loop), after which the model turns ready."""
        if self._model_config is None:
            cfg_path = os.path.join(self.model_dir or "", "config.json")
            if not os.path.exists(cfg_path):
                raise FileNotFoundError(
                    f"no config.json under {self.model_dir}; pass model_config"
                )
            self._model_config = llama.LlamaConfig.from_hf_config(cfg_path)
        # refused before any weight is read: what this model cannot be
        # served with (the engine checks again with its own copy and sizes)
        resolve_serving(
            self._model_config, dataclasses.replace(self.engine_config),
            role=self.role, lora=bool(self.lora_adapters))
        self.tokenizer = load_tokenizer(self.model_dir, self._model_config.vocab_size)
        if self.random_weights or not self.model_dir:
            self._params = None  # engine random-initializes
        else:
            # streamed load (models/llama.load_hf_weights_streamed): the
            # checkpoint — typically a warmed LocalModelCache volume —
            # streams tensor-by-tensor with quantize-on-load, so peak host
            # staging is ~one tensor instead of the whole checkpoint
            # (docs/coldstart.md)
            import time as _time

            t0 = _time.perf_counter()
            stats: dict = {}
            self._params = llama.load_hf_weights_streamed(
                self.model_dir, self._model_config,
                weight_quant=self.engine_config.weight_quant,
                stats=stats,
            )
            self._weights_load_s = _time.perf_counter() - t0
            logger.info(
                "weights streamed: %d tensors, %.1f MiB read, peak host "
                "staging %.1f MiB, %.2fs",
                stats.get("n_tensors", 0),
                stats.get("read_bytes", 0) / (1 << 20),
                stats.get("peak_host_bytes", 0) / (1 << 20),
                self._weights_load_s,
            )
        return True  # ready flips in start_engine

    async def start_engine(self):
        from ..engine.dp import build_engine

        self.engine = build_engine(
            self._model_config,
            self.engine_config,
            self.tokenizer,
            params=getattr(self, "_params", None),
            lora_adapters=self.lora_adapters or None,
            # weights identity for resumable checkpoints: the served model
            # name, so a checkpoint can only re-seat on the same model
            checkpoint_label=self.name,
        )
        self._params = None  # free the host copy
        # the checkpoint read happened in load(), before the engine
        # existed; fold it into the weights phase AND the ready total
        # (via startup_external_s) BEFORE start() exports the
        # engine_startup_seconds observations — otherwise ready would
        # read smaller than the weights phase it contains
        load_s = getattr(self, "_weights_load_s", 0.0)
        if load_s and hasattr(self.engine, "startup_phases"):
            self.engine.startup_phases["weights"] = (
                self.engine.startup_phases.get("weights", 0.0) + load_s)
            self.engine.startup_external_s += load_s
        await self.engine.start()
        self.ready = True
        logger.info("generative model %s ready", self.name)

    def stop(self, escalate: bool = False):
        import asyncio

        try:
            loop = asyncio.get_event_loop()
        except RuntimeError:
            return
        if not loop.is_running():
            return
        # keep STRONG references until each task completes: create_task
        # results are weakly held by the loop and an un-referenced shutdown
        # task can be GC'd before it runs — the drain would silently never
        # happen.  A done-callback prunes finished tasks so repeated stops
        # don't accumulate them.  `escalate` (second shutdown signal, see
        # ModelServer._make_signal_handler) only CANCELS wedged stop work
        # and returns: the normal shutdown path issues the fresh stop, and
        # creating tasks here could race an in-progress drain loop.
        self._stop_tasks = getattr(self, "_stop_tasks", [])
        if escalate:
            for task in self._stop_tasks:
                if not task.done():
                    task.cancel()
            return
        if self.engine is not None and self.engine.running:
            self._track_stop_task(loop.create_task(self.engine.stop()))
        if self._prefill_client is not None:
            self._track_stop_task(loop.create_task(self._prefill_client.close()))
            self._prefill_client = None

    def _track_stop_task(self, task) -> None:
        self._stop_tasks.append(task)
        task.add_done_callback(self._discard_stop_task)

    def _discard_stop_task(self, task) -> None:
        try:
            self._stop_tasks.remove(task)
        except ValueError:
            pass  # escalation already pruned it

    async def drain(self, deadline=None) -> list:
        """Lifecycle drain passthrough: checkpoint whatever the budget
        cannot finish (kserve_tpu/lifecycle, docs/lifecycle.md)."""
        if self.engine is None or not self.engine.running:
            return []
        return await self.engine.drain(deadline)

    async def healthy(self) -> bool:
        return self.ready and self.engine is not None and self.engine.running

    async def live(self) -> bool:
        """Wedge detection (parity: huggingfaceserver health_check.py role):
        a wedged engine must flip /v2/health/live red so the pod restarts
        instead of hanging with a healthy-looking HTTP server."""
        return self.engine is None or not self.engine.wedged

    # ---------------- helpers ----------------

    def _logprobs_k(self, req) -> Optional[int]:
        """Normalize the two OpenAI logprob dialects to one int: None = not
        requested, 0 = sampled token's logprob only, N = N top alternatives.

        Completions (legacy): ``logprobs`` is an int count.
        Chat: ``logprobs`` is a bool gate + ``top_logprobs`` int count."""
        lp = getattr(req, "logprobs", None)
        top = getattr(req, "top_logprobs", None)
        if isinstance(lp, bool):  # chat dialect
            if top is not None and not lp:
                raise InvalidInput("top_logprobs requires logprobs=true")
            if not lp:
                return None
            k = top or 0
        elif isinstance(lp, int):  # completions dialect (0 is a valid ask)
            k = lp
        else:
            if top is not None:  # {"logprobs": null, "top_logprobs": N}
                raise InvalidInput("top_logprobs requires logprobs=true")
            return None
        max_k = (
            self.engine.config.max_logprobs
            if self.engine is not None else 20
        )
        if not 0 <= k <= max_k:
            raise InvalidInput(f"logprobs must be between 0 and {max_k}")
        if self.role == "decode" and self.prefill_url:
            # the P/D wire format carries (kv, first_token) only
            raise InvalidInput(
                "logprobs is not supported with prefill/decode disaggregation"
            )
        return k

    def _sampling_from(self, req, max_len_default: int = 16) -> SamplingParams:
        logprobs_k = self._logprobs_k(req)
        max_tokens = (
            getattr(req, "max_completion_tokens", None)
            or getattr(req, "max_tokens", None)
            or max_len_default
        )
        stop = req.stop
        if isinstance(stop, str):
            stop = [stop]
        return SamplingParams(
            temperature=req.temperature if req.temperature is not None else 1.0,
            top_p=req.top_p if req.top_p is not None else 1.0,
            top_k=req.top_k or 0,
            min_p=req.min_p or 0.0,
            repetition_penalty=getattr(req, "repetition_penalty", None) or 1.0,
            frequency_penalty=getattr(req, "frequency_penalty", None) or 0.0,
            presence_penalty=getattr(req, "presence_penalty", None) or 0.0,
            max_tokens=max_tokens,
            min_tokens=req.min_tokens or 0,
            ignore_eos=bool(req.ignore_eos),
            stop=stop,
            seed=req.seed,
            logprobs=logprobs_k,
        )

    def _encode_prompt(self, prompt: Union[str, List[int], List[str]]) -> List[List[int]]:
        if isinstance(prompt, str):
            return [self.tokenizer.encode(prompt)]
        if isinstance(prompt, list):
            if not prompt:
                raise InvalidInput("empty prompt")
            if isinstance(prompt[0], int):
                return [list(prompt)]
            if isinstance(prompt[0], str):
                return [self.tokenizer.encode(p) for p in prompt]
            if isinstance(prompt[0], list):
                return [list(p) for p in prompt]
        raise InvalidInput(f"unsupported prompt type {type(prompt).__name__}")

    # ---------------- completions ----------------

    async def create_completion(
        self, request: CompletionRequest, raw_request=None, context=None
    ):
        ckpt = self._checkpoint_from_context(context)
        if ckpt is not None:
            # preemption-safe resume: a drained replica handed the client
            # (or the EPP) this checkpoint; continue decoding from it —
            # prompt ids, sampling params and progress all come from the
            # checkpoint, not the (re-sent) request body.  A checkpoint is
            # ONE generation: multi-choice requests never receive one
            # (_raise_gathered), so carrying one here is a client error.
            if max(request.n or 1, 1) > 1 or (
                isinstance(request.prompt, list)
                and len(request.prompt) > 1
                # str elements and list-of-token-id elements are both
                # multi-prompt forms; a flat list of ints is ONE prompt
                and isinstance(request.prompt[0], (str, list))
            ):
                raise InvalidInput(
                    "checkpoint resume supports a single prompt with n=1"
                )
            # the checkpoint carries tokens but not the prefix's logprob
            # entries, so a non-streaming body cannot honor a logprobs
            # request faithfully — silently returning logprobs=null would
            # break clients that index it.  (Streaming resumes are fine:
            # the prefix deltas already delivered their logprobs before
            # the preemption.)
            if not request.stream and self._logprobs_k(request) is not None:
                raise InvalidInput(
                    "checkpoint resume cannot reconstruct logprobs for the "
                    "checkpointed prefix in a non-streaming response; "
                    "retry the request without the checkpoint"
                )
            source = self._resume_source(ckpt)
            if request.stream:
                return self._stream_completion(
                    request, list(ckpt.prompt_ids), ckpt.sampling_params(),
                    source=source,
                )
            return await self._resumed_completion(request, ckpt, source)
        prompts = self._encode_prompt(request.prompt)
        params = self._sampling_from(request, max_len_default=16)
        adapter = self._adapter_for(request)
        if request.stream:
            if len(prompts) > 1 or request.n > 1:
                raise InvalidInput("streaming supports a single prompt with n=1")
            return self._stream_completion(request, prompts[0], params, adapter)
        import asyncio

        runs = [
            prompt_ids for prompt_ids in prompts for _ in range(max(request.n, 1))
        ]
        # concurrent submission: the engine batches all of them in one pass
        results = await asyncio.gather(
            *[self._run_one(p, params, adapter) for p in runs],
            return_exceptions=True,
        )
        results = self._raise_gathered(results)
        choices = []
        usage = UsageInfo()
        for idx, (prompt_ids, (text, n_gen, finish, entries)) in enumerate(
            zip(runs, results)
        ):
            lp = (
                self._completion_logprobs(entries, params.logprobs)
                if entries is not None else None
            )
            choices.append(
                CompletionChoice(
                    index=idx, text=text, finish_reason=finish, logprobs=lp
                )
            )
            usage.prompt_tokens += len(prompt_ids)
            usage.completion_tokens += n_gen
        usage.total_tokens = usage.prompt_tokens + usage.completion_tokens
        return Completion(model=request.model, choices=choices, usage=usage)

    @staticmethod
    def _raise_gathered(results: list) -> list:
        """Surface errors from a multi-generation gather without losing
        sibling generations silently.  A lone GenerationPreempted re-raises
        as-is (503 + checkpoint: a single-choice resume is exact).  With
        MULTIPLE generations the response cannot carry per-choice
        checkpoints, so preemption degrades to a plain retryable 503 —
        the client restarts the whole request on a healthy replica, which
        loses salvaged tokens but never drops a choice from the response
        shape.  Any non-preemption error wins (it would have propagated
        first under plain gather too)."""
        errors = [r for r in results if isinstance(r, BaseException)]
        if not errors:
            return results
        for e in errors:
            if not isinstance(e, GenerationPreempted):
                raise e
        if len(results) == 1:
            raise errors[0]
        raise ReplicaDrainingError(
            "replica drained mid-request; multi-choice responses cannot "
            "carry per-choice checkpoints — retry on another replica"
        )

    def _checkpoint_from_context(self, context) -> Optional[GenerationCheckpoint]:
        """A generation checkpoint riding the request headers (the
        x-generation-checkpoint value a draining replica returned)."""
        if not context:
            return None
        return GenerationCheckpoint.from_header(context.get(CHECKPOINT_HEADER))

    def _resume_source(self, ckpt):
        """Validate + admit a wire-sourced checkpoint exactly once (the
        engine counts a resume per call).  A malformed or model-mismatched
        checkpoint is the CLIENT's error — surface it as 400 InvalidInput,
        not the last-resort 500."""
        try:
            return self.engine.resume_generation(ckpt)
        except ValueError as e:
            raise InvalidInput(f"cannot resume from checkpoint: {e}") from e

    @staticmethod
    async def _splice_resume(ckpt, source):
        """Drain a resumed generation source to completion.  Returns the
        full spliced text (checkpointed tokens + continuation), the finish
        reason, and usage accounted against the checkpoint's prompt — the
        shared core of the completion and chat resume bodies."""
        n_gen, finish, last = 0, None, None
        async for out in source:
            last, n_gen, finish = out, out.num_generated, out.finish_reason
        text = last.cumulative_text if last is not None else ""
        usage = UsageInfo(
            prompt_tokens=len(ckpt.prompt_ids),
            completion_tokens=n_gen,
            total_tokens=len(ckpt.prompt_ids) + n_gen,
        )
        return text, finish or "stop", usage

    async def _resumed_completion(self, request: CompletionRequest, ckpt, source):
        """Non-streaming resume: the response carries the FULL generation —
        the checkpointed tokens plus the continuation — so the retry is
        transparent to the caller (same body a never-preempted request
        would have returned)."""
        text, finish, usage = await self._splice_resume(ckpt, source)
        return Completion(
            model=request.model,
            choices=[CompletionChoice(index=0, text=text, finish_reason=finish)],
            usage=usage,
        )

    def _adapter_for(self, request) -> Optional[str]:
        """OpenAI `model` naming a loaded LoRA adapter selects it (vLLM
        semantics); any other value serves the base model."""
        name = getattr(request, "model", None)
        return name if name in self.lora_adapters else None

    def _generate(self, prompt_ids, params, adapter=None):
        """engine.generate with limit errors surfaced as 400s (the checks
        must run before iteration starts — async generators defer their body
        to the first __anext__)."""
        if len(prompt_ids) + params.max_tokens > self.engine.config.max_model_len:
            raise InvalidInput(
                f"prompt+max_tokens exceeds max_model_len {self.engine.config.max_model_len}"
            )
        if self.role == "decode" and self.prefill_url:
            return self._generate_disaggregated(prompt_ids, params, adapter)
        return self.engine.generate(prompt_ids, params, adapter=adapter)

    async def _generate_disaggregated(self, prompt_ids, params, adapter=None):
        """Decode role: fetch the prompt's KV from the prefill peer, then
        continue decoding locally from the transferred pages."""
        from ..protocol.pd import PrefillClient

        if self._prefill_client is None:
            self._prefill_client = PrefillClient(self.prefill_url)
        kv, first_token = await self._prefill_client.prefill(
            self.name, prompt_ids, params, adapter=adapter
        )
        async for out in self.engine.generate_injected(
            prompt_ids, params, kv, first_token, adapter=adapter
        ):
            yield out

    async def handle_prefill(self, prompt_ids, params, adapter=None):
        """Prefill role: serve one detached prefill (protocol/pd.py route)."""
        from ..protocol.pd import serialize_kv

        try:
            first_token, kv = await self.engine.prefill_detached(
                prompt_ids, params, adapter=adapter
            )
        except ValueError as e:
            raise InvalidInput(str(e)) from e
        return serialize_kv(kv, first_token)

    async def _run_one(self, prompt_ids, params, adapter=None):
        text = ""
        n_gen = 0
        finish = None
        entries = [] if params.logprobs is not None else None
        async for out in self._generate(prompt_ids, params, adapter):
            text += out.text_delta
            n_gen = out.num_generated
            finish = out.finish_reason
            if entries is not None and out.token_id >= 0:
                entries.append(
                    (out.token_id, out.text_delta, out.logprob, out.top_logprobs)
                )
        return text, n_gen, finish or "stop", entries

    # ---------------- logprob marshalling ----------------

    def _token_str(self, token_id: int) -> str:
        return self.tokenizer.decode([token_id])

    def _completion_logprobs(
        self, entries, k: int, offset0: int = 0
    ) -> CompletionLogprobs:
        """Legacy-completions logprobs block.  `entries` are engine
        (token_id, text_delta, logprob, top) tuples; the sampled token is
        folded into each top_logprobs dict (OpenAI behaviour)."""
        lp = CompletionLogprobs(top_logprobs=[] if k > 0 else None)
        offset = offset0
        for tid, delta, logprob, top in entries:
            lp.tokens.append(self._token_str(tid))
            lp.token_logprobs.append(logprob)
            lp.text_offset.append(offset)
            offset += len(delta)
            if k > 0:
                # the legacy dict format is keyed by token TEXT — byte-level
                # tokenizers can decode distinct ids to the same string, so
                # keep the best (first, list is sorted desc) on collision
                d: dict = {}
                for t, v in (top or [])[:k]:
                    d.setdefault(self._token_str(t), v)
                if logprob is not None:
                    d.setdefault(self._token_str(tid), logprob)
                lp.top_logprobs.append(d)
        return lp

    def _chat_logprobs(self, entries, k: int) -> ChatCompletionLogprobs:
        content = []
        for tid, _delta, logprob, top in entries:
            tok = self._token_str(tid)
            content.append(
                ChatCompletionLogprobsContent(
                    token=tok,
                    logprob=logprob if logprob is not None else -9999.0,
                    bytes=list(tok.encode("utf-8")),
                    top_logprobs=[
                        ChatCompletionLogprob(
                            token=self._token_str(t),
                            logprob=v,
                            bytes=list(self._token_str(t).encode("utf-8")),
                        )
                        for t, v in (top or [])[:k]
                    ],
                )
            )
        return ChatCompletionLogprobs(content=content)

    async def _stream_completion(
        self, request: CompletionRequest, prompt_ids, params, adapter=None,
        source=None,
    ) -> AsyncIterator[Completion]:
        """`source` overrides the token stream (checkpoint resume) — the
        chunks then carry only the CONTINUATION deltas, which is exactly
        what a client holding the pre-drain prefix needs to splice."""
        completion_id = random_uuid("cmpl-")
        n_gen = 0
        text_offset = 0
        if source is None:
            source = self._generate(prompt_ids, params, adapter)
        async for out in source:
            n_gen = out.num_generated
            lp = None
            if params.logprobs is not None and out.token_id >= 0:
                lp = self._completion_logprobs(
                    [(out.token_id, out.text_delta, out.logprob, out.top_logprobs)],
                    params.logprobs,
                    offset0=text_offset,
                )
            text_offset += len(out.text_delta)
            chunk = Completion(
                id=completion_id,
                model=request.model,
                choices=[
                    CompletionChoice(
                        index=0,
                        text=out.text_delta,
                        finish_reason=out.finish_reason,
                        logprobs=lp,
                    )
                ],
            )
            if request.stream_options and request.stream_options.include_usage and out.finished:
                chunk.usage = UsageInfo(
                    prompt_tokens=len(prompt_ids),
                    completion_tokens=n_gen,
                    total_tokens=len(prompt_ids) + n_gen,
                )
            yield chunk

    # ---------------- chat ----------------

    def _chat_prompt(self, request: ChatCompletionRequest) -> List[int]:
        messages = [m.model_dump(exclude_none=True) for m in request.messages]
        for m in messages:
            if isinstance(m.get("content"), list):
                m["content"] = "".join(
                    p.get("text", "") for p in m["content"] if p.get("type") == "text"
                )
        kwargs = request.chat_template_kwargs or {}
        text = self.tokenizer.apply_chat_template(
            messages, add_generation_prompt=True, **kwargs
        )
        return self.tokenizer.encode(text)

    async def create_chat_completion(
        self, request: ChatCompletionRequest, raw_request=None, context=None
    ):
        ckpt = self._checkpoint_from_context(context)
        if ckpt is not None:
            # preemption-safe resume, chat surface (see create_completion):
            # progress and sampling come from the checkpoint, stream chunks
            # carry only the continuation deltas, and the non-stream body
            # carries the full spliced message
            if max(request.n or 1, 1) > 1:
                raise InvalidInput("checkpoint resume supports n=1")
            # same prefix-logprobs constraint as create_completion
            if not request.stream and self._logprobs_k(request) is not None:
                raise InvalidInput(
                    "checkpoint resume cannot reconstruct logprobs for the "
                    "checkpointed prefix in a non-streaming response; "
                    "retry the request without the checkpoint"
                )
            source = self._resume_source(ckpt)
            if request.stream:
                return self._stream_chat(
                    request, list(ckpt.prompt_ids), ckpt.sampling_params(),
                    source=source,
                )
            return await self._resumed_chat(request, ckpt, source)
        prompt_ids = self._chat_prompt(request)
        params = self._sampling_from(request, max_len_default=256)
        adapter = self._adapter_for(request)
        if request.stream:
            if request.n > 1:
                raise InvalidInput("streaming supports n=1")
            return self._stream_chat(request, prompt_ids, params, adapter)
        import asyncio

        n = max(request.n, 1)
        results = await asyncio.gather(
            *[self._run_one(prompt_ids, params, adapter) for _ in range(n)],
            return_exceptions=True,
        )
        results = self._raise_gathered(results)
        choices = []
        usage = UsageInfo(prompt_tokens=len(prompt_ids) * n)
        for i, (text, n_gen, finish, entries) in enumerate(results):
            choices.append(
                ChatCompletionChoice(
                    index=i,
                    message=ChatCompletionResponseMessage(role="assistant", content=text),
                    finish_reason=finish,
                    logprobs=(
                        self._chat_logprobs(entries, params.logprobs)
                        if entries is not None else None
                    ),
                )
            )
            usage.completion_tokens += n_gen
        usage.total_tokens = usage.prompt_tokens + usage.completion_tokens
        return ChatCompletion(model=request.model, choices=choices, usage=usage)

    async def _resumed_chat(self, request: ChatCompletionRequest, ckpt, source):
        """Non-streaming chat resume: the full spliced message (checkpointed
        prefix + continuation), same body a never-preempted request would
        have returned."""
        text, finish, usage = await self._splice_resume(ckpt, source)
        return ChatCompletion(
            model=request.model,
            choices=[ChatCompletionChoice(
                index=0,
                message=ChatCompletionResponseMessage(
                    role="assistant", content=text),
                finish_reason=finish,
            )],
            usage=usage,
        )

    async def _stream_chat(
        self, request: ChatCompletionRequest, prompt_ids, params, adapter=None,
        source=None,
    ) -> AsyncIterator[ChatCompletionChunk]:
        """`source` overrides the token stream (checkpoint resume): chunks
        then carry only the continuation deltas — what a client holding the
        pre-drain prefix needs to splice."""
        chunk_id = random_uuid("chatcmpl-")
        yield ChatCompletionChunk(
            id=chunk_id,
            model=request.model,
            choices=[
                ChatCompletionChunkChoice(
                    index=0, delta=ChatCompletionChunkDelta(role="assistant", content="")
                )
            ],
        )
        n_gen = 0
        if source is None:
            source = self._generate(prompt_ids, params, adapter)
        async for out in source:
            n_gen = out.num_generated
            lp = None
            if params.logprobs is not None and out.token_id >= 0:
                lp = self._chat_logprobs(
                    [(out.token_id, out.text_delta, out.logprob, out.top_logprobs)],
                    params.logprobs,
                )
            chunk = ChatCompletionChunk(
                id=chunk_id,
                model=request.model,
                choices=[
                    ChatCompletionChunkChoice(
                        index=0,
                        delta=ChatCompletionChunkDelta(content=out.text_delta),
                        finish_reason=out.finish_reason,
                        logprobs=lp,
                    )
                ],
            )
            if (
                request.stream_options
                and request.stream_options.include_usage
                and out.finished
            ):
                chunk.usage = UsageInfo(
                    prompt_tokens=len(prompt_ids),
                    completion_tokens=n_gen,
                    total_tokens=len(prompt_ids) + n_gen,
                )
            yield chunk


def main(argv=None):
    from ..utils.backend import apply_platform_override

    apply_platform_override()
    parent = build_arg_parser()
    parser = argparse.ArgumentParser(parents=[parent], conflict_handler="resolve")
    parser.add_argument("--model_config", default=None, choices=sorted(_NAMED_CONFIGS))
    parser.add_argument("--random_weights", action="store_true")
    parser.add_argument("--tensor_parallel_size", "--tp", default=1, type=int)
    parser.add_argument("--data_parallel_size", "--dp", default=1, type=int)
    parser.add_argument("--sequence_parallel_size", "--sp", default=1, type=int)
    parser.add_argument(
        "--pipeline_parallel_size", "--pp", default=1, type=int,
        help="layer stages over the pipe mesh axis (composes with --tp; "
        "for models beyond one slice's HBM — within a slice prefer --tp)",
    )
    parser.add_argument(
        "--role", default="both", choices=("both", "prefill", "decode"),
        help="P/D disaggregation role; decode needs --prefill_url",
    )
    parser.add_argument(
        "--prefill_url", default=os.getenv("PREFILL_URL") or None,
        help="base URL of the prefill-role peer (decode role)",
    )
    parser.add_argument("--max_batch_size", default=8, type=int)
    parser.add_argument("--kv_pages", default=2048, type=int)
    parser.add_argument("--page_size", default=16, type=int)
    parser.add_argument("--max_model_len", default=2048, type=int)
    parser.add_argument("--max_prefill_len", default=1024, type=int)
    parser.add_argument("--kv_dtype", default="bfloat16", type=str)
    parser.add_argument("--kv_quant", default="none", choices=("none", "int8"))
    parser.add_argument(
        "--weight_quant", default="none", choices=("none", "int8"),
        help="int8 weight-only quantization (fits 8B on one v5e chip)",
    )
    parser.add_argument("--kv_offload", default="none", choices=("none", "host"))
    parser.add_argument("--kv_offload_gib", default=0.0, type=float)
    parser.add_argument(
        "--kv_offload_disk_gib", default=0.0, type=float,
        help="secondary disk tier budget (GiB) under --kv_offload_dir; "
        "entries demote host->disk per --kv_offload_policy",
    )
    parser.add_argument("--kv_offload_dir", default="/tmp/kserve-tpu-kv")
    parser.add_argument(
        "--kv_offload_policy", default="lru", choices=("lru", "arc"))
    parser.add_argument(
        "--lora_adapters", default=None,
        help="comma-separated name=/local/adapter/dir (HF PEFT format)",
    )
    parser.add_argument(
        "--aot_cache_dir", default=None,
        help="persistent AOT executable cache directory (docs/coldstart.md); "
        "defaults to $KSERVE_TPU_AOT_CACHE — a populated cache makes "
        "replica start perform zero XLA compiles",
    )
    parser.add_argument(
        "--kv_persist_dir", default=None,
        help="content-addressed persistent prefix store directory "
        "(docs/kv_hierarchy.md); defaults to $KSERVE_TPU_KV_PERSIST — a "
        "populated store makes a restarted replica serve shared-prefix "
        "traffic with cache hits from request one",
    )
    parser.add_argument(
        "--watchdog", default=None, choices=("on", "off"),
        help="gray-failure engine watchdog (docs/resilience.md): a "
        "confirmed no-progress stall flips readiness and self-drains "
        "with checkpoints instead of waiting for the client deadline "
        "or kubelet; defaults to $KSERVE_TPU_WATCHDOG (off).  Enable "
        "once a warm AOT cache keeps steady-state dispatch compile-free",
    )
    parser.add_argument(
        "--spec_decode_k", default=None, type=int,
        help="speculative decoding + dense decode packing "
        "(docs/kernels.md): K draft tokens per lane verified per round "
        "inside the dense mixed_decode program (0 = dense packing "
        "alone); defaults to $KSERVE_TPU_SPEC_DECODE_K (off).  Greedy "
        "and seeded streams stay token-identical to spec-off.  Disables "
        "the AOT executable cache until hardware-validated",
    )
    args = parser.parse_args(argv)

    model_config = _NAMED_CONFIGS[args.model_config]() if args.model_config else None
    engine_config = EngineConfig(
        max_batch_size=args.max_batch_size,
        page_size=args.page_size,
        num_pages=args.kv_pages,
        max_pages_per_seq=max(1, args.max_model_len // args.page_size),
        max_prefill_len=args.max_prefill_len,
        tp=args.tensor_parallel_size,
        dp=args.data_parallel_size,
        sp=args.sequence_parallel_size,
        pp=args.pipeline_parallel_size,
        dtype=args.kv_dtype,
        kv_quant=args.kv_quant,
        weight_quant=args.weight_quant,
        kv_offload=args.kv_offload,
        kv_offload_gib=args.kv_offload_gib,
        kv_offload_disk_gib=args.kv_offload_disk_gib,
        kv_offload_dir=args.kv_offload_dir,
        kv_offload_policy=args.kv_offload_policy,
        aot_cache_dir=args.aot_cache_dir or aot_cache_dir_from_env(),
        kv_persist_dir=args.kv_persist_dir or kv_persist_dir_from_env(),
        watchdog=(args.watchdog == "on" if args.watchdog is not None
                  else watchdog_enabled_from_env()),
        spec_decode_k=(args.spec_decode_k if args.spec_decode_k is not None
                       else spec_decode_k_from_env()),
    )
    lora_adapters = None
    if args.lora_adapters:
        lora_adapters = dict(
            pair.split("=", 1) for pair in args.lora_adapters.split(",") if pair
        )
    model = JAXGenerativeModel(
        args.model_name,
        model_dir=args.model_dir if os.path.isdir(args.model_dir) else None,
        model_config=model_config,
        engine_config=engine_config,
        random_weights=args.random_weights,
        role=args.role,
        prefill_url=args.prefill_url,
        lora_adapters=lora_adapters,
    )
    model.load()
    ModelServer(
        http_port=args.http_port,
        grpc_port=args.grpc_port,
        enable_grpc=args.enable_grpc,
    ).start([model])


if __name__ == "__main__":
    main()
