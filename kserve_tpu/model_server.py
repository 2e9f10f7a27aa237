"""Process entrypoint: wires models + DataPlane + REST/gRPC servers.

`ModelServer.start(models)` blocks serving; `start_async()` is the embeddable
form used by tests and by engine runtimes that own the event loop.

Parity: reference python/kserve/kserve/model_server.py (start :332, engine
startup :441-455, signal handling, arg parser :48-208); rebuilt on
aiohttp/grpc.aio with the same lifecycle semantics.
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import inspect
import signal
from typing import Dict, List, Optional, Union

from . import logging as ks_logging
from .errors import NoModelReady
from .lifecycle import GenerationCheckpoint, ReplicaLifecycle
from .logging import logger
from .model import BaseModel, Model
from .model_repository import ModelRepository
from .protocol.dataplane import DataPlane
from .protocol.grpc.server import GRPCServer
from .protocol.model_repository_extension import ModelRepositoryExtension
from .protocol.openai.dataplane import OpenAIDataPlane
from .protocol.rest.server import RESTServer

DEFAULT_HTTP_PORT = 8080
DEFAULT_GRPC_PORT = 8081


def build_arg_parser(parents: Optional[list] = None) -> argparse.ArgumentParser:
    """Shared CLI surface; runtimes extend via parent-parser composition the
    same way the reference runtimes do."""
    parser = argparse.ArgumentParser(
        add_help=(parents is None), parents=parents or [], conflict_handler="resolve"
    )
    parser.add_argument("--http_port", default=DEFAULT_HTTP_PORT, type=int)
    parser.add_argument("--grpc_port", default=DEFAULT_GRPC_PORT, type=int)
    parser.add_argument("--workers", default=1, type=int)
    parser.add_argument("--max_threads", default=4, type=int)
    parser.add_argument("--max_asyncio_workers", default=None, type=int)
    parser.add_argument("--enable_grpc", default=True, type=lambda x: str(x).lower() == "true")
    parser.add_argument("--enable_docs_url", default=False, type=lambda x: str(x).lower() == "true")
    parser.add_argument(
        "--enable_latency_logging", default=True, type=lambda x: str(x).lower() == "true"
    )
    parser.add_argument("--log_config_file", default=None, type=str)
    parser.add_argument("--access_log_format", default=None, type=str)
    parser.add_argument("--model_name", default="model", type=str)
    parser.add_argument("--model_dir", default="/mnt/models", type=str)
    # secure serving (parity: the reference manager/agent TLS flags,
    # pkg/tls/tls.go; certs typically ride the self-signed Secret the
    # LLMISVC reconciler provisions)
    parser.add_argument("--ssl_certfile", default=None, type=str)
    parser.add_argument("--ssl_keyfile", default=None, type=str)
    parser.add_argument("--tls_min_version", default="1.2", type=str)
    parser.add_argument("--tls_cipher_suites", default=None, type=str)
    return parser


args, _ = build_arg_parser().parse_known_args()


class ModelServer:
    def __init__(
        self,
        http_port: int = args.http_port,
        grpc_port: int = args.grpc_port,
        workers: int = args.workers,
        max_threads: int = args.max_threads,
        max_asyncio_workers: Optional[int] = args.max_asyncio_workers,
        registered_models: Optional[ModelRepository] = None,
        enable_grpc: bool = args.enable_grpc,
        enable_docs_url: bool = args.enable_docs_url,
        enable_latency_logging: bool = args.enable_latency_logging,
        access_log_format: Optional[str] = args.access_log_format,
        grace_period: int = 30,
        ssl_certfile: Optional[str] = args.ssl_certfile,
        ssl_keyfile: Optional[str] = args.ssl_keyfile,
        tls_min_version: str = args.tls_min_version,
        tls_cipher_suites: Optional[str] = args.tls_cipher_suites,
    ):
        self.http_port = http_port
        self._ssl_context = None
        if ssl_certfile and ssl_keyfile:
            from .controlplane.tls import server_ssl_context

            self._ssl_context = server_ssl_context(
                ssl_certfile, ssl_keyfile,
                min_version=tls_min_version,
                cipher_suites=tls_cipher_suites,
            )
        self.grpc_port = grpc_port
        self.workers = workers
        self.max_threads = max_threads
        self.max_asyncio_workers = max_asyncio_workers
        self.enable_grpc = enable_grpc
        self.enable_docs_url = enable_docs_url
        self.enable_latency_logging = enable_latency_logging
        self.access_log_format = access_log_format
        self.grace_period = grace_period
        self.registered_models = registered_models or ModelRepository()
        self.dataplane = OpenAIDataPlane(self.registered_models)
        self.model_repository_extension = ModelRepositoryExtension(self.registered_models)
        self._rest_server: Optional[RESTServer] = None
        self._grpc_server: Optional[GRPCServer] = None
        self._engine_tasks: List[asyncio.Task] = []
        self._grpc_task: Optional[asyncio.Task] = None
        # set (with the cause in _fatal) when an engine fails to start or
        # its run loop dies: the blocking entrypoint then shuts down and
        # raises, so the process exits non-zero instead of idling
        # live-but-never-ready
        self._stop_event = asyncio.Event()
        self._fatal: Optional[BaseException] = None
        # replica lifecycle (kserve_tpu/lifecycle — docs/lifecycle.md):
        # STARTING -> READY after start_async; SIGTERM / POST /admin/drain
        # -> DRAINING (readiness red, admission 503, in-flight gets the
        # drain budget); second signal escalates to TERMINATING
        self.lifecycle = ReplicaLifecycle()
        if not ks_logging.is_configured():
            ks_logging.configure_logging(args.log_config_file)

    # ---------- registration ----------

    def register_model(self, model: BaseModel, name: Optional[str] = None) -> None:
        if not (name or getattr(model, "name", None)):
            raise Exception("Failed to register model, model.name must be provided.")
        self.registered_models.update(model)
        logger.info("Registering model: %s", name or model.name)

    def _register_and_check_ready(self, models: Union[List[BaseModel], Dict[str, object]]):
        if isinstance(models, dict):
            for name, handle in models.items():
                self.registered_models.update_handle(name, handle)
                logger.info("Registering model handle: %s", name)
        else:
            at_least_one_ready = False
            for model in models:
                if not isinstance(model, BaseModel):
                    raise RuntimeError("Model type should be 'BaseModel'")
                self.register_model(model)
                if model.ready:
                    at_least_one_ready = True
            engine_models = [m for m in models if _has_engine(m)]
            if not at_least_one_ready and models and not engine_models:
                raise NoModelReady(models)
            return engine_models
        return []

    # ---------- lifecycle ----------

    async def start_async(self, models: List[BaseModel]) -> None:
        """Start servers inside an existing event loop (non-blocking serve)."""
        engine_models = self._register_and_check_ready(models)
        self._setup_asyncio_executor()
        for model in engine_models:
            task = asyncio.create_task(_start_engine(model))
            task.add_done_callback(
                lambda t, m=model: self._engine_started(t, m))
            self._engine_tasks.append(task)
        self._rest_server = RESTServer(
            self.dataplane,
            self.model_repository_extension,
            http_port=self.http_port,
            access_log_format=self.access_log_format,
            enable_docs_url=self.enable_docs_url,
            enable_latency_logging=self.enable_latency_logging,
            reuse_port=getattr(self, "_reuse_port", False),
            ssl_context=self._ssl_context,
            lifecycle=self.lifecycle,
            on_drain=self.drain_async,
        )
        await self._rest_server.start()
        if self.enable_grpc:
            self._grpc_server = GRPCServer(
                self.grpc_port, self.dataplane, self.model_repository_extension
            )
            self._grpc_task = asyncio.create_task(self._grpc_server.start(self.max_threads))
        self.lifecycle.mark_ready()

    def _engine_started(self, task: asyncio.Task, model) -> None:
        """Done-callback of a model's engine start: a failed start is fatal
        (a replica that cannot run its programs must not stay up), a good
        one wires the engine's stall and loop-crash hooks."""
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            self._on_fatal(exc)
            return
        self._wire_stall_hook(model)
        engine = getattr(model, "engine", None)
        if hasattr(engine, "on_loop_crash"):
            engine.on_loop_crash = self._on_fatal

    def _on_fatal(self, exc: BaseException) -> None:
        if self._fatal is None:
            self._fatal = exc
            logger.error(
                "fatal engine failure (%s: %s): shutting the server down",
                type(exc).__name__, exc)
        self._stop_event.set()

    def _wire_stall_hook(self, model) -> None:
        """Gray-failure watchdog wiring (docs/resilience.md): a confirmed
        engine stall must flip THIS replica's readiness red — the engine
        self-drains its streams internally, but only the server lifecycle
        makes the readiness probe (and with it the endpoint controller)
        see it.  Liveness stays green: checkpoints must outlive the
        stall, a kubelet kill would lose them."""
        engine = getattr(model, "engine", None)
        if engine is None or not hasattr(engine, "on_stall_confirmed"):
            return

        def on_stall(reason: str) -> None:
            logger.error(
                "engine stall confirmed (%s): flipping replica readiness "
                "(DRAINING)", reason)
            self.lifecycle.begin_drain()

        engine.on_stall_confirmed = on_stall

    async def drain_async(self) -> List[GenerationCheckpoint]:
        """Graceful drain: flip DRAINING (readiness red, liveness green,
        new inference 503s), give every engine's in-flight generations the
        drain budget, and checkpoint what the budget cannot finish.
        Idempotent — the signal handler and POST /admin/drain share one
        budget; escalation expires it in place."""
        deadline = self.lifecycle.begin_drain()
        logger.info(
            "draining replica: budget %.1fs (signal again to escalate)",
            max(deadline.remaining(), 0.0),
        )
        drains = []
        for model in self.registered_models.get_models().values():
            # model-level drain is the extension point (a wrapper can
            # aggregate several engines); plain engine models fall back to
            # engine.drain directly
            drain = getattr(model, "drain", None)
            if drain is not None:
                drains.append(drain(deadline))
                continue
            engine = getattr(model, "engine", None)
            if engine is not None and hasattr(engine, "drain"):
                drains.append(engine.drain(deadline))
        # CONCURRENT, not sequential: every engine must flip into drain
        # mode immediately — an engine drained later would keep seating new
        # work and 'length'-finishing KV-starved lanes while earlier models
        # consume the shared budget (DataParallelEngine.drain gathers its
        # replicas for the same reason)
        checkpoints: List[GenerationCheckpoint] = []
        for result in await asyncio.gather(*drains):
            checkpoints.extend(result)
        self.lifecycle.finish_drain()
        if checkpoints:
            logger.info(
                "drain complete: %d generation(s) checkpointed for resume "
                "elsewhere", len(checkpoints),
            )
        return checkpoints

    def _make_signal_handler(self, stop_event: asyncio.Event):
        """First SIGINT/SIGTERM starts the graceful drain; a SECOND signal
        escalates — it expires the drain budget in place (every engine
        drain loop observes that on its next poll) so shutdown proceeds
        immediately with the leftovers checkpointed, and cancels any stop
        task a model already has pending (a wedged engine.stop() must not
        outlive the escalation)."""

        def on_signal():
            if not stop_event.is_set():
                stop_event.set()
            else:
                logger.warning(
                    "second shutdown signal: escalating to immediate "
                    "shutdown (drain budget expired in place)"
                )
                self.lifecycle.escalate()
                for model in self.registered_models.get_models().values():
                    stop = getattr(model, "stop", None)
                    if stop is not None and (
                        "escalate" in inspect.signature(stop).parameters
                    ):
                        stop(escalate=True)

        return on_signal

    async def stop_async(self) -> None:
        for model_name in list(self.registered_models.get_models().keys()):
            try:
                self.registered_models.unload(model_name)
            except KeyError:
                pass
        for task in self._engine_tasks:
            task.cancel()
        if self._grpc_server is not None:
            await self._grpc_server.stop()
        if self._grpc_task is not None:
            self._grpc_task.cancel()
        if self._rest_server is not None:
            await self._rest_server.stop()

    def start(self, models: List[BaseModel]) -> None:
        """Blocking entrypoint.  workers > 1 serves the REST port from N
        processes sharing it via SO_REUSEPORT (parity: reference
        protocol/rest/multiprocess/server.py) — predictive serving only;
        a generative engine owns the accelerator and must stay single."""
        if self.workers > 1:
            self._start_multiprocess(models)
            return
        self._serve_blocking(models, reuse_port=False)

    def _serve_blocking(self, models: List[BaseModel], reuse_port: bool) -> None:
        self._reuse_port = reuse_port

        async def serve():
            await self.start_async(models)
            loop = asyncio.get_event_loop()
            handler = self._make_signal_handler(self._stop_event)
            for sig in (signal.SIGINT, signal.SIGTERM):
                try:
                    loop.add_signal_handler(sig, handler)
                except NotImplementedError:  # pragma: no cover (non-unix)
                    pass
            await self._stop_event.wait()
            if self._fatal is None:
                await self.drain_async()
            logger.info("Stopping servers (grace period %ss)", self.grace_period)
            await self.stop_async()

        asyncio.run(serve())
        if self._fatal is not None:
            raise RuntimeError(
                "engine failed; the server has shut down") from self._fatal

    def _child_main(self, models: List[BaseModel]) -> None:
        # one gRPC listener is enough; REST shares the port via SO_REUSEPORT
        self.enable_grpc = False
        self._serve_blocking(models, reuse_port=True)

    def _start_multiprocess(self, models: List[BaseModel]) -> None:
        if any(_has_engine(m) for m in models):
            raise ValueError(
                "--workers > 1 is for predictive serving; a generative "
                "engine owns the accelerator and cannot be forked"
            )
        import multiprocessing

        ctx = multiprocessing.get_context("fork")
        children = [
            ctx.Process(target=self._child_main, args=(models,), daemon=True)
            for _ in range(self.workers - 1)
        ]
        for child in children:
            child.start()
        logger.info(
            "REST multiprocess: %d workers sharing port %d (SO_REUSEPORT)",
            self.workers, self.http_port,
        )
        # a crashed worker must not silently degrade capacity: a monitor
        # thread respawns dead children (parity: reference multiprocess
        # server's process supervision)
        import threading

        stopping = threading.Event()

        def monitor():
            while not stopping.wait(5):
                for i, child in enumerate(children):
                    if not child.is_alive():
                        logger.error(
                            "REST worker pid=%s died (exitcode=%s); respawning",
                            child.pid, child.exitcode,
                        )
                        children[i] = ctx.Process(
                            target=self._child_main, args=(models,), daemon=True
                        )
                        children[i].start()

        threading.Thread(target=monitor, daemon=True).start()
        try:
            self._serve_blocking(models, reuse_port=True)
        finally:
            stopping.set()
            for child in children:
                child.terminate()
            for child in children:
                child.join(timeout=self.grace_period)

    def _setup_asyncio_executor(self):
        workers = self.max_asyncio_workers
        if workers is None:
            import multiprocessing

            # Mirrors the reference default: bounded small multiple of cores.
            workers = min(32, multiprocessing.cpu_count() + 4)
        loop = asyncio.get_event_loop()
        loop.set_default_executor(concurrent.futures.ThreadPoolExecutor(max_workers=workers))


def _has_engine(model: BaseModel) -> bool:
    return type(model).start_engine is not BaseModel.start_engine or (
        hasattr(model, "start_engine") and getattr(model, "_is_engine_model", False)
    )


async def _start_engine(model: BaseModel) -> None:
    try:
        result = model.start_engine()
        if asyncio.iscoroutine(result):
            await result
    except Exception:
        # a dead engine must be loud and fail readiness, not vanish into an
        # unawaited task
        logger.exception("engine startup failed for model %s", model.name)
        model.ready = False
        raise
