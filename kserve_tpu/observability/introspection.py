"""Admin introspection endpoints: rolling telemetry + on-demand profiling.

- ``GET  /admin/telemetry``  — per-model rolling TTFT/ITL/step-time
  percentiles plus recent request timelines, straight from each engine's
  bounded TimelineRecorder (no Prometheus scrape required mid-incident).
- ``POST /admin/profile``    — capture a ``jax.profiler`` trace into a
  configurable directory: ``{"action": "start"}`` ... ``{"action":
  "stop"}``, or ``{"seconds": N}`` for a start with the stop scheduled;
  ``"python": true`` turns the python tracer on.  409 while a capture is
  already running, or when there is none to stop (the profiler is a
  process-global singleton in JAX).

Both ride the always-open admin surface (resilience.is_inference_path is
False for /admin, so shedding/lifecycle gates never block an operator
mid-drain or mid-overload).
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Optional

from aiohttp import web

from ..logging import logger
from ..resilience import MONOTONIC, Clock

PROFILE_DIR_ENV = "KSERVE_TPU_PROFILE_DIR"
# typed app-config key (aiohttp 3.9 idiom): tests/operators reach the
# running ProfilerSession via app[PROFILER_KEY]
PROFILER_KEY: "web.AppKey[ProfilerSession]" = web.AppKey(
    "observability_profiler", object
)
DEFAULT_PROFILE_DIR = "/tmp/kserve-tpu-profiles"
MAX_PROFILE_SECONDS = 300.0


class ProfilerBusyError(RuntimeError):
    """A capture is already in flight, or none is there to stop (HTTP 409)."""


class ProfilerSession:
    """One-at-a-time jax.profiler capture, started and stopped on demand.

    `start` opens a capture; `stop` ends it.  A timed capture is the same
    pair with the stop scheduled on the injectable clock (tests drive the
    window without real sleeps).  `jax.profiler.stop_trace()` collects and
    writes the trace, which takes seconds: it runs in a worker thread, so
    the server keeps answering, and `active` stays true until it returns.

    The default capture leaves the python tracer off: the engine's own
    `engine.<phase>` annotations name what the host was doing, and the
    host is hardly slowed.  `python=True` gives the full python call tree,
    at that cost."""

    def __init__(self, clock: Optional[Clock] = None,
                 default_dir: Optional[str] = None):
        self._clock = clock or MONOTONIC
        self._default_dir = (
            default_dir
            or os.environ.get(PROFILE_DIR_ENV, DEFAULT_PROFILE_DIR)
        )
        self._current: Optional[dict] = None
        self._timer: Optional[asyncio.Task] = None
        self._stopping: Optional[asyncio.Future] = None

    @property
    def active(self) -> bool:
        return self._current is not None

    def status(self) -> dict:
        return {"active": self.active, "capture": self._current}

    async def start(self, out_dir: Optional[str] = None, python: bool = False,
                    seconds: Optional[float] = None) -> dict:
        if seconds is not None and not (0 < seconds <= MAX_PROFILE_SECONDS):
            raise ValueError(
                f"profile seconds must be in (0, {MAX_PROFILE_SECONDS:g}]"
            )
        if self.active:
            raise ProfilerBusyError(
                f"profile capture already running: {self._current}"
            )
        target = os.path.join(
            out_dir or self._default_dir,
            time.strftime("trace-%Y%m%d-%H%M%S", time.gmtime()),
        )
        os.makedirs(target, exist_ok=True)
        import jax.profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python else 0
        jax.profiler.start_trace(target, profiler_options=options)
        self._current = {"dir": target, "seconds": seconds, "python": python}
        if seconds is not None:
            self._timer = asyncio.get_running_loop().create_task(
                self._stop_after(seconds))
        logger.info("profiler capture started: %s (%s s, python tracer %s)",
                    target, "untimed" if seconds is None else f"{seconds:g}",
                    "on" if python else "off")
        return dict(self._current)

    async def _stop_after(self, seconds: float) -> None:
        await self._clock.sleep(seconds)
        self._timer = None  # stop() must not cancel the task it runs in
        await self.stop()

    async def stop(self) -> dict:
        """End the capture; returns once the trace is written."""
        if not self.active or self._stopping is not None:
            raise ProfilerBusyError(
                "no profile capture to stop" if not self.active
                else "the profile capture is already stopping")
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        import jax.profiler

        t0 = time.monotonic()
        self._stopping = asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)
        try:
            await self._stopping
        except RuntimeError as exc:
            # double-stop / device-side teardown race: the capture is
            # over either way, only the artifact may be partial
            logger.warning("profiler stop_trace failed: %s", exc)
        finally:
            info = dict(self._current, stop_s=time.monotonic() - t0)
            self._stopping = None
            self._current = None
        logger.info("profiler capture finished: stop_trace took %.2f s",
                    info["stop_s"])
        return info

    async def wait(self) -> None:
        """Test/shutdown helper: block until the running capture ends."""
        while self.active:
            pending = self._timer or self._stopping
            if pending is None:
                return  # untimed and nobody has asked it to stop
            await asyncio.wait([pending])


def register_observability_routes(
    app: web.Application,
    model_registry,
    profiler: Optional[ProfilerSession] = None,
) -> None:
    profiler = profiler or ProfilerSession()
    app[PROFILER_KEY] = profiler

    async def telemetry_handler(request: web.Request) -> web.Response:
        models = {}
        for name, model in model_registry.get_models().items():
            engine = getattr(model, "engine", None)
            snap = getattr(engine, "telemetry_snapshot", None)
            if callable(snap):
                models[name] = snap()
        return web.json_response({
            "models": models,
            "profiler": profiler.status(),
        })

    async def profile_handler(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except ValueError:
            body = {}
        if not isinstance(body, dict):
            body = {}
        action = body.get("action")
        if action not in (None, "start", "stop"):
            return web.json_response(
                {"error": "action must be start or stop"}, status=400
            )
        # no action: a timed capture, {"seconds": N} (default 2)
        seconds = body.get("seconds", 2.0 if action is None else None)
        try:
            seconds = None if seconds is None else float(seconds)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "seconds must be a number"}, status=400
            )
        try:
            if action == "stop":
                return web.json_response(await profiler.stop())
            info = await profiler.start(
                out_dir=body.get("dir"), python=bool(body.get("python")),
                seconds=seconds)
        except ProfilerBusyError as e:
            return web.json_response({"error": str(e)}, status=409)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        except (ImportError, RuntimeError, OSError) as e:
            # no profiler in this build / unwritable dir: the endpoint is
            # best-effort tooling, not a serving dependency
            return web.json_response({"error": str(e)}, status=501)
        return web.json_response(info, status=202)

    app.router.add_get("/admin/telemetry", telemetry_handler)
    app.router.add_post("/admin/profile", profile_handler)
