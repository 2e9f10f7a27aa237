"""A stand-in for the server child: the endpoints the harness uses, answered
in milliseconds by an aiohttp app on a thread of its own, with every request
logged.  Its counters follow a made-up engine that dispatches every 50 ms,
so that each reader finds something to read; nothing here is a measurement.

`patch_harness` puts it where `run.start_server` would start the program,
and replaces the reference child and the trace reduction, which need JAX.
"""

import asyncio
import json
import threading
import time

from aiohttp import web
from bench_paths import BENCH  # noqa: F401

from kbench import correctness, loadgen, server

PERIOD_S = 0.05
#: seconds of one made-up dispatch by phase; wait holds a lag of 2 ms
PHASES = {"admit": 0.001, "plan": 0.002, "launch": 0.003, "wait": 0.040,
          "route": 0.003, "yield": 0.001}
WAIT_LAG_S = 0.002
COLUMNS = ["serial", "launched_at", "program", "tokens", "width",
           "prefill_tokens", "decode_tokens", *PHASES, "wait_lag",
           "compiled", "chained"]
STARTUP = {"ready": 50.0, "aot_load": 38.0, "weights": 9.0, "trace": 0.0,
           "compile": 0.0}


class StandIn:
    """Start with `with StandIn() as s:`; `s.calls` is the log of
    (method, path) in arrival order, `s.profile_bodies` what was posted to
    /admin/profile."""

    def __init__(self):
        self.calls = []
        self.profile_bodies = []
        self.capturing = False
        self._t0 = time.monotonic()
        self._loop = None
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    # ---- the made-up engine ----

    def _dispatches(self) -> int:
        return int((time.monotonic() - self._t0) / PERIOD_S)

    def _metrics(self) -> str:
        n = self._dispatches()
        lines = [
            f'engine_decode_step_seconds_count{{model_name="bench"}} {n}',
            f'engine_decode_step_seconds_sum{{model_name="bench"}} {n * 0.046}',
            f'engine_prompt_tokens_total{{model_name="bench"}} {n * 40}',
            f'engine_generated_tokens_total{{model_name="bench"}} {n * 8}',
            'kv_prefix_hit_tokens_total{model_name="bench",tier="hbm"} 0',
            'engine_xla_compiles_total{program="mixed"} 12',
            'engine_xla_compile_seconds_total{program="mixed"} 600.0',
            'engine_queue_depth{model_name="bench"} 0',
            f'engine_dispatches_total{{model_name="bench",program="mixed"}} {n}',
            f'engine_first_token_dispatches_count{{model_name="bench"}} {n}',
            f'engine_first_token_dispatches_sum{{model_name="bench"}} {n * 1.5}',
        ]
        for phase, s in {**PHASES, "wait_lag": WAIT_LAG_S}.items():
            lines.append(
                'engine_dispatch_phase_seconds_total{model_name="bench",'
                f'phase="{phase}"}} {n * s}')
        for phase, s in STARTUP.items():
            labels = f'{{model_name="bench",phase="{phase}"}}'
            lines += [f"engine_startup_seconds_count{labels} 1",
                      f"engine_startup_seconds_sum{labels} {s}"]
        return "\n".join(lines) + "\n"

    def _telemetry(self) -> dict:
        n = self._dispatches()
        rows = [[i, i * PERIOD_S, "mixed", 128, 32, 40, 8, *PHASES.values(),
                 WAIT_LAG_S, 0, 0] for i in range(max(1, n - 511), n + 1)]
        return {"models": {server.MODEL_NAME: {
            "queue_wait_s": {"p50": 0.02, "n": 10},
            "now": time.monotonic() - self._t0,
            "dispatches": {"columns": COLUMNS, "rows": rows}}},
            "profiler": {"active": self.capturing}}

    # ---- handlers ----

    async def _get(self, request):
        path = request.path
        if path == "/metrics":
            return web.Response(text=self._metrics())
        if path == "/admin/telemetry":
            return web.json_response(self._telemetry())
        if path == "/v1/internal/scheduler/state":
            return web.json_response({"models": {server.MODEL_NAME: {
                "devices": [{"platform": "tpu", "kind": "TPU v5 lite",
                             "peak_bytes_in_use": 13.0e9}],
                "inflight": 0, "queue_depth": 0}}})
        return web.Response(text="ok")  # readiness

    async def _profile(self, request):
        body = await request.json()
        self.profile_bodies.append(body)
        if body.get("action") == "stop":
            self.capturing = False
            return web.json_response({"dir": "x", "stop_s": 0.0})
        self.capturing = True
        if "seconds" in body:
            asyncio.get_running_loop().call_later(
                body["seconds"], setattr, self, "capturing", False)
        return web.json_response({"dir": "x"}, status=202)

    async def _completions(self, request):
        body = await request.json()
        n = body["max_tokens"]
        if not body.get("stream"):
            return web.json_response({
                "choices": [{"text": " ".join(str(i + 1) for i in range(n))}],
                "usage": {"prompt_tokens": len(body["prompt"]),
                          "completion_tokens": n}})
        resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        try:
            for i in range(n):
                chunk = {"choices": [{"text": f" {i + 1}", "finish_reason":
                                      "length" if i == n - 1 else None}]}
                await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
                await asyncio.sleep(0.002)
            await resp.write(b"data: [DONE]\n\n")
        except ConnectionResetError:
            pass  # the generator cancels what is open when it stops
        return resp

    # ---- the thread ----

    @web.middleware
    async def _log(self, request, handler):
        self.calls.append((request.method, request.path))
        return await handler(request)

    def _run(self):
        async def serve():
            app = web.Application(middlewares=[self._log])
            app.router.add_post(loadgen.COMPLETIONS, self._completions)
            app.router.add_post("/admin/profile", self._profile)
            app.router.add_get("/{tail:.*}", self._get)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            self.port = site._server.sockets[0].getsockname()[1]
            self._stop = asyncio.Event()
            self._loop = asyncio.get_running_loop()
            self._ready.set()
            await self._stop.wait()
            await runner.cleanup()

        asyncio.run(serve())

    def __enter__(self):
        self._thread.start()
        assert self._ready.wait(10.0)
        return self

    def __exit__(self, *exc):
        self._loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(10.0)


class StandInServer(server.Server):
    """What `run.start_server` returns, without the child."""

    def __init__(self, standin: StandIn):
        self.base_url = f"http://127.0.0.1:{standin.port}"

    def wait_ready(self) -> float:
        return 0.0

    def stop(self) -> int:
        return 0

    def log_tail(self, lines: int = 40) -> str:
        return ""


class NoReference:
    """In the reference child's place: the probes' answer is not checked."""

    cached = True

    def __init__(self, *args, **kwargs):
        pass

    def result(self, timeout_s: float = 0.0) -> dict:
        return {"max_gap": 0.0, "argmax_match_share": 1.0}

    def kill(self) -> None:
        pass


REDUCED = {"busy_s": 3.2, "window_s": 4.0,
           "opcode_s": {"sort": 1.2, "custom-call": 0.4, "fusion": 1.6},
           "op_s": {"sort_f32_48_151936_": 1.2},
           "device_ops": [["sort_f32_48_151936_", 1.2]],
           "idle_gaps": [["engine.yield", 0.4], ["engine.route", 0.2]]}


def patch_harness(monkeypatch, bench_run, standin: StandIn, cache_dir: str):
    monkeypatch.setattr(
        bench_run, "start_server", lambda *a, **k: StandInServer(standin))
    monkeypatch.setattr(bench_run, "cache_root", lambda: cache_dir)
    monkeypatch.setattr(bench_run, "reduce_trace", lambda *a: dict(REDUCED))
    monkeypatch.setattr(correctness, "ReferenceCheck", NoReference)


def small_plan(bench_run, cell_name: str, ramp_s: float = 0.3):
    """The cell's own plan, cut in time only: a short ramp and cool-down,
    no shape grid (the stand-in compiles nothing)."""
    from kbench import manifest

    plan = bench_run.Plan(manifest.resolve_cell(cell_name), rehearse=False)
    plan.grid = None
    plan.mix = dict(plan.mix, ramp_s=ramp_s, cooldown_s=0.3, drain_s=0.3)
    plan.mix.pop("ramp_burst", None)
    plan.scale = 0.3  # lengths: answers of some tens of tokens, 2 ms each
    if plan.open_loop:
        plan.rate = 10.0
    else:
        plan.clients = 4
    return plan
