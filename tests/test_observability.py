"""End-to-end request telemetry tests (docs/observability.md): FakeClock
timelines with exact TTFT/ITL/queue-wait histogram assertions, queue-depth
gauge staleness regressions, cross-hop traceparent propagation, engine
child spans, introspection endpoints, and the metric-cardinality gate —
zero real sleeps anywhere."""

import asyncio
from contextlib import contextmanager

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer
from prometheus_client import REGISTRY

import kserve_tpu.tracing as tracing
from kserve_tpu import ModelRepository
from kserve_tpu.engine.engine import EngineConfig, LLMEngine
from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.engine.tokenizer import ByteTokenizer
from kserve_tpu.lifecycle.checkpoint import GenerationPreempted
from kserve_tpu.metrics import (
    observe_request_timeline,
    record_breaker_transition,
    set_lifecycle_state,
)
from kserve_tpu.models.llama import LlamaConfig
from kserve_tpu.observability import (
    CPU_COLUMNS,
    DISPATCH_COLUMNS,
    PARTS,
    PAUSES,
    PHASES,
    PROFILER_KEY,
    DispatchPhases,
    ProfilerSession,
    RequestTimeline,
    TimelineRecorder,
    percentiles,
)
from kserve_tpu.observability import pauses
from kserve_tpu.protocol.model_repository_extension import ModelRepositoryExtension
from kserve_tpu.protocol.openai.dataplane import OpenAIDataPlane
from kserve_tpu.protocol.rest.server import RESTServer
from kserve_tpu.resilience import Clock, FakeClock
from kserve_tpu.tracing import TraceContext, propagate_headers, trace_scope

from conftest import async_test
from test_rest_server import DummyModel


def make_engine(clock=None, metrics_label="obs-engine", cpu_clock=None,
                **cfg_overrides):
    model_config = LlamaConfig.tiny(dtype="float32")
    cfg = dict(
        max_batch_size=4, page_size=8, num_pages=64, max_pages_per_seq=8,
        max_prefill_len=32, prefill_buckets=(16, 32), dtype="float32",
        use_pallas=False,
    )
    cfg.update(cfg_overrides)
    tokenizer = ByteTokenizer(model_config.vocab_size)
    return LLMEngine(model_config, EngineConfig(**cfg), tokenizer,
                     clock=clock, cpu_clock=cpu_clock,
                     metrics_label=metrics_label)


def hist(name, label, suffix):
    v = REGISTRY.get_sample_value(f"{name}_{suffix}", {"model_name": label})
    return v or 0.0


def gauge(name, **labels):
    return REGISTRY.get_sample_value(name, labels)


async def collect(agen):
    outs = []
    async for out in agen:
        outs.append(out)
    return outs


class RecordingSpan:
    def __init__(self, name, attributes):
        self.name = name
        self.attributes = dict(attributes or {})
        self.events = []
        self.exceptions = []
        self.status = None
        self.ended = False

    def set_attribute(self, key, value):
        self.attributes[key] = value

    def add_event(self, name, attributes=None):
        self.events.append((name, dict(attributes or {})))

    def record_exception(self, exc):
        self.exceptions.append(exc)

    def set_status(self, status):
        self.status = status

    def end(self):
        self.ended = True


class RecordingTracer:
    """Recording tracer covering both tracer API shapes the code uses:
    start_as_current_span (middleware/proxy) and start_span (engine)."""

    def __init__(self):
        self.spans = []

    @contextmanager
    def start_as_current_span(self, name, attributes=None):
        span = RecordingSpan(name, attributes)
        self.spans.append(span)
        yield span

    def start_span(self, name, attributes=None):
        span = RecordingSpan(name, attributes)
        self.spans.append(span)
        return span

    def named(self, name):
        return [s for s in self.spans if s.name == name]


@pytest.fixture
def recording_tracer():
    tracer = RecordingTracer()
    tracing.set_tracer_for_tests(tracer)
    try:
        yield tracer
    finally:
        tracing.set_tracer_for_tests(None)
        tracing._configured = False


# ---------------------------------------------------------------- timelines


class TestRequestTimeline:
    def test_scripted_timeline_exact_values(self):
        """Pure-FakeClock scripted generation: every derived latency is
        exact virtual time, no tolerance."""
        clock = FakeClock()
        tl = RequestTimeline("r1", model_name="m")
        tl.mark_received(clock.now())          # t=0
        clock.advance(0.25)
        tl.mark_admitted(clock.now())          # t=0.25
        tl.mark_prefill_start(clock.now())
        clock.advance(0.5)
        tl.mark_prefill_end(clock.now())       # t=0.75
        tl.mark_token(clock.now())             # first token at 0.75
        for _ in range(3):
            clock.advance(0.1)
            tl.mark_token(clock.now())
        tl.mark_finished(clock.now(), "stop")  # t=1.05
        assert tl.queue_wait_s == 0.25
        assert tl.ttft_s == 0.75
        assert tl.prefill_s == 0.5
        assert tl.itls == pytest.approx([0.1, 0.1, 0.1])
        assert tl.e2e_s == pytest.approx(1.05)
        assert tl.n_generated == 4
        d = tl.to_dict()
        assert d["finish_reason"] == "stop" and d["ttft_s"] == 0.75

    def test_re_admission_keeps_first_stamps(self):
        clock = FakeClock()
        tl = RequestTimeline("r1")
        tl.mark_received(0.0)
        tl.mark_admitted(1.0)
        tl.add_event(1.5, "preempt", pos=7)
        tl.mark_admitted(9.0)  # re-seat after preemption
        assert tl.queue_wait_s == 1.0  # first admission wins
        assert tl.events[0]["name"] == "preempt"

    def test_recorder_windows_and_percentiles(self):
        rec = TimelineRecorder()
        for i, reason in enumerate(["stop", "length", "preempted", "error"]):
            tl = RequestTimeline(f"r{i}")
            tl.mark_received(0.0)
            tl.mark_admitted(0.0)
            tl.mark_token(1.0 + i)
            tl.mark_finished(2.0, reason)
            rec.observe(tl)
        snap = rec.snapshot()
        # only stop/length count toward latency windows
        assert snap["counts"] == {
            "finished": 2, "preempted": 1, "aborted": 1, "decode_steps": 0,
        }
        assert snap["ttft_s"]["n"] == 2
        assert len(snap["recent"]) == 4  # ring keeps everything for debugging

    def test_percentiles_nearest_rank(self):
        p = percentiles([0.1 * i for i in range(1, 11)])
        assert p["n"] == 10
        assert p["p50"] == pytest.approx(0.6)
        assert p["p99"] == pytest.approx(1.0)
        assert p["max"] == pytest.approx(1.0)
        assert percentiles([]) == {"n": 0}


# ------------------------------------------------- engine FakeClock chaos


class TestEngineTelemetryFakeClock:
    @async_test
    async def test_exact_ttft_itl_queue_wait_histograms(self):
        """THE acceptance test: a scripted generation under FakeClock gives
        bit-exact histogram observations — queue wait is exactly the
        virtual time the request sat queued before the engine started, and
        every decode stamp lands at the same virtual instant (ITL == 0.0
        exactly), with zero real sleeps."""
        label = "obs-exact"
        clock = FakeClock()
        engine = make_engine(clock=clock, metrics_label=label)
        params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
        task = asyncio.create_task(
            collect(engine.generate([5, 6, 7], params, request_id="scripted"))
        )
        for _ in range(3):  # let the submit reach the queue (engine not started)
            await asyncio.sleep(0)
        assert engine.queue_depth == 1
        clock.advance(2.5)  # scripted queue wait
        await engine.start()
        outs = await task
        await engine.stop()
        assert len(outs) == 4 and outs[-1].finished
        # exact histogram observations (count AND sum)
        assert hist("request_queue_wait_seconds", label, "count") == 1
        assert hist("request_queue_wait_seconds", label, "sum") == 2.5
        assert hist("request_ttft_seconds", label, "count") == 1
        assert hist("request_ttft_seconds", label, "sum") == 2.5
        # 4 tokens -> 3 inter-token gaps, all at the same virtual instant
        assert hist("request_inter_token_seconds", label, "count") == 3
        assert hist("request_inter_token_seconds", label, "sum") == 0.0
        assert hist("request_e2e_seconds", label, "count") == 1
        assert hist("request_e2e_seconds", label, "sum") == 2.5
        # rolling introspection agrees with prometheus
        snap = engine.telemetry_snapshot()
        assert snap["counts"]["finished"] == 1
        assert snap["ttft_s"]["p50"] == 2.5
        assert snap["itl_s"]["p50"] == 0.0
        assert snap["queue_wait_s"]["max"] == 2.5
        recent = snap["recent"][0]
        assert recent["request_id"] == "scripted"
        assert recent["finish_reason"] == "length"
        # decode-step/prefill-chunk series observed (virtual durations = 0)
        assert snap["counts"]["decode_steps"] >= 1
        assert hist("engine_prefill_chunk_seconds", label, "count") >= 1
        assert hist("engine_decode_step_seconds", label, "count") >= 1

    @async_test
    async def test_xla_compile_counter_counts_cache_misses(self):
        before = REGISTRY.get_sample_value(
            "engine_xla_compiles_total", {"program": "mixed"}) or 0.0
        engine = make_engine(metrics_label="obs-compile")
        await engine.start()
        params = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([1, 2, 3], params))
        first = REGISTRY.get_sample_value(
            "engine_xla_compiles_total", {"program": "mixed"})
        assert first is not None and first >= before + 1
        # steady state MUST be retrace-free: same shapes, no growth (the
        # historical donated-kv_pages settle retrace is fixed — see
        # tests/test_retrace_budget.py)
        await collect(engine.generate([4, 5, 6], params))
        await collect(engine.generate([7, 8, 9], params))
        await engine.stop()
        assert REGISTRY.get_sample_value(
            "engine_xla_compiles_total", {"program": "mixed"}) == first

    @async_test
    async def test_request_mixed_batch_ratio(self):
        """Mixed steps export per-step TOKEN composition, not just lane
        roles: while a long prompt chunk-prefills alongside a live decode
        stream, some step must report prefill_tokens > 0 AND
        decode_tokens > 0 simultaneously — the observable that proves the
        scheduler barrier is gone — and the gauges must match the
        engine's last recorded composition."""
        label = "obs-mixed-ratio"
        engine = make_engine(
            metrics_label=label, max_prefill_len=16, prefill_buckets=(16,),
            num_pages=128, max_pages_per_seq=32,
        )
        assert engine._use_mixed
        await engine.start()
        mixed_steps = []
        orig_route = engine._route_mixed

        def spy(plan, chunk_np, dispatched_at):
            out = orig_route(plan, chunk_np, dispatched_at)
            mixed_steps.append(dict(engine.last_step_composition))
            return out

        engine._route_mixed = spy
        params = SamplingParams(max_tokens=64, temperature=0.0,
                                ignore_eos=True)
        try:
            short_task = asyncio.create_task(
                collect(engine.generate([1, 2, 3], params)))
            # wait until the short request is decoding
            while not any(s.request_id is not None for s in engine._slots):
                await asyncio.sleep(0.01)
            long_prompt = [5 + (i % 200) for i in range(200)]
            await collect(engine.generate(
                long_prompt,
                SamplingParams(max_tokens=4, temperature=0.0,
                               ignore_eos=True)))
            await short_task
        finally:
            await engine.stop()
        truly_mixed = [
            c for c in mixed_steps
            if c.get("prefill_tokens", 0) > 0 and c.get("decode_tokens", 0) > 0
        ]
        assert truly_mixed, f"no mixed-composition step seen: {mixed_steps}"
        # gauges agree with the engine's last composition record
        last = mixed_steps[-1]
        assert REGISTRY.get_sample_value(
            "engine_step_batch_composition",
            {"model_name": label, "role": "prefill_tokens"},
        ) == last["prefill_tokens"]
        assert REGISTRY.get_sample_value(
            "engine_step_batch_composition",
            {"model_name": label, "role": "decode_tokens"},
        ) == last["decode_tokens"]


class TestQueueDepthGauge:
    """Satellite: the ENGINE_QUEUE_DEPTH gauge can never go stale —
    every _waiting mutation writes it unconditionally."""

    @async_test
    async def test_cancel_updates_gauge(self):
        label = "obs-gauge-cancel"
        engine = make_engine(metrics_label=label)  # never started: stays queued
        params = SamplingParams(max_tokens=2)
        t1 = asyncio.create_task(
            collect(engine.generate([1, 2], params, request_id="a")))
        t2 = asyncio.create_task(
            collect(engine.generate([3, 4], params, request_id="b")))
        for _ in range(3):
            await asyncio.sleep(0)
        assert gauge("engine_queue_depth", model_name=label) == 2
        engine.cancel("a")
        assert gauge("engine_queue_depth", model_name=label) == 1
        engine.cancel("b")
        assert gauge("engine_queue_depth", model_name=label) == 0
        t1.cancel(), t2.cancel()

    @async_test
    async def test_stop_zeroes_gauge_even_when_queue_already_empty(self):
        """The r5 bug shape: the fail-all path only zeroed the gauge when
        it flushed a non-empty queue — a stop after the queue emptied
        through another path left it stale."""
        label = "obs-gauge-stop"
        engine = make_engine(metrics_label=label)
        params = SamplingParams(max_tokens=2)
        task = asyncio.create_task(
            collect(engine.generate([1, 2], params, request_id="x")))
        for _ in range(3):
            await asyncio.sleep(0)
        assert gauge("engine_queue_depth", model_name=label) == 1
        engine.cancel("x")  # empties the queue outside the fail-all path
        await engine.stop()  # fail-all sees an EMPTY queue; gauge must be 0
        assert gauge("engine_queue_depth", model_name=label) == 0
        task.cancel()

    @async_test
    async def test_drain_checkpoints_queued_and_zeroes_gauge(self):
        label = "obs-gauge-drain"
        clock = FakeClock()
        engine = make_engine(clock=clock, metrics_label=label)
        params = SamplingParams(max_tokens=4)
        task = asyncio.create_task(
            collect(engine.generate([9, 9, 9], params, request_id="d")))
        for _ in range(3):
            await asyncio.sleep(0)
        ckpts = await engine.drain(clock=clock)
        assert len(ckpts) == 1
        with pytest.raises(GenerationPreempted):
            await task
        assert gauge("engine_queue_depth", model_name=label) == 0
        # the preempted timeline landed in the ring, not the latency windows
        snap = engine.telemetry_snapshot()
        assert snap["counts"]["preempted"] == 1
        assert snap["ttft_s"] == {"n": 0}


# ---------------------------------------------------------------- metrics


class TestMetricsHelpers:
    def test_set_lifecycle_state_one_hot(self):
        for state in ("STARTING", "READY", "DRAINING", "TERMINATING"):
            set_lifecycle_state(state)
            values = {
                s: gauge("replica_lifecycle_state", state=s)
                for s in ("STARTING", "READY", "DRAINING", "TERMINATING")
            }
            assert values[state] == 1.0
            assert sum(values.values()) == 1.0  # exactly one hot

    def test_record_breaker_transition_state_label_only(self):
        before = REGISTRY.get_sample_value(
            "resilience_breaker_transitions_total", {"state": "open"}) or 0.0
        record_breaker_transition("10.0.0.1:8080", "open")
        after = REGISTRY.get_sample_value(
            "resilience_breaker_transitions_total", {"state": "open"})
        assert after == before + 1
        # the backend identity must NOT have become a label
        assert REGISTRY.get_sample_value(
            "resilience_breaker_transitions_total",
            {"state": "open", "backend": "10.0.0.1:8080"}) is None

    @async_test
    async def test_live_scrape_exposes_ttft_itl_series(self):
        clock = FakeClock()
        tl = RequestTimeline("scrape-req", model_name="scrape-model")
        tl.mark_received(clock.now())
        clock.advance(0.2)
        tl.mark_admitted(clock.now())
        tl.mark_token(clock.now())
        clock.advance(0.05)
        tl.mark_token(clock.now())
        tl.mark_finished(clock.now(), "stop")
        observe_request_timeline("scrape-model", tl)

        repo = ModelRepository()
        repo.update(DummyModel())
        server = RESTServer(OpenAIDataPlane(repo), ModelRepositoryExtension(repo))
        async with TestClient(TestServer(server.create_application())) as client:
            res = await client.get("/metrics")
            assert res.status == 200
            text = await res.text()
        from prometheus_client.parser import text_string_to_metric_families

        families = {f.name: f for f in text_string_to_metric_families(text)}
        assert "request_ttft_seconds" in families
        assert "request_inter_token_seconds" in families
        ttft_count = [
            s for s in families["request_ttft_seconds"].samples
            if s.name.endswith("_count")
            and s.labels.get("model_name") == "scrape-model"
        ]
        assert ttft_count and ttft_count[0].value == 1
        itl_sum = [
            s for s in families["request_inter_token_seconds"].samples
            if s.name.endswith("_sum")
            and s.labels.get("model_name") == "scrape-model"
        ]
        assert itl_sum and itl_sum[0].value == pytest.approx(0.05)


# ------------------------------------------------------------ introspection


class _StubEngine:
    def __init__(self):
        self.telemetry = TimelineRecorder()

    def telemetry_snapshot(self):
        snap = self.telemetry.snapshot()
        snap["queue_depth"] = 0
        return snap


class _GateClock(Clock):
    """sleep() blocks until the test releases the gate — deterministic
    'capture in progress' window with zero real sleeps."""

    def __init__(self):
        self.gate = asyncio.Event()

    async def sleep(self, seconds: float) -> None:
        await self.gate.wait()


class TestIntrospectionEndpoints:
    def _server(self, profiler=None):
        repo = ModelRepository()
        model = DummyModel()
        model.engine = _StubEngine()
        tl = RequestTimeline("t-1", model_name="dummy")
        tl.mark_received(0.0)
        tl.mark_admitted(0.5)
        tl.mark_token(1.0)
        tl.mark_finished(1.5, "stop")
        model.engine.telemetry.observe(tl)
        repo.update(model)
        return RESTServer(
            OpenAIDataPlane(repo), ModelRepositoryExtension(repo),
            profiler=profiler,
        )

    @async_test
    async def test_admin_telemetry_reports_percentiles_and_recent(self):
        server = self._server()
        async with TestClient(TestServer(server.create_application())) as client:
            res = await client.get("/admin/telemetry")
            assert res.status == 200
            body = await res.json()
        dummy = body["models"]["dummy"]
        assert dummy["counts"]["finished"] == 1
        assert dummy["ttft_s"]["p50"] == 1.0
        assert dummy["recent"][0]["request_id"] == "t-1"
        assert body["profiler"]["active"] is False

    @async_test
    async def test_admin_profile_capture_and_409_while_running(self, tmp_path):
        clock = _GateClock()
        server = self._server(profiler=ProfilerSession(clock=clock))
        app = server.create_application()
        async with TestClient(TestServer(app)) as client:
            res = await client.post(
                "/admin/profile",
                json={"seconds": 30, "dir": str(tmp_path)},
            )
            if res.status == 501:
                pytest.skip("jax.profiler unavailable in this build")
            assert res.status == 202
            info = await res.json()
            assert info["dir"].startswith(str(tmp_path))
            # second capture while running: 409, not a corrupted trace
            res2 = await client.post("/admin/profile", json={"seconds": 1})
            assert res2.status == 409
            # telemetry endpoint reports the active capture
            tele = await (await client.get("/admin/telemetry")).json()
            assert tele["profiler"]["active"] is True
            clock.gate.set()
            await app[PROFILER_KEY].wait()
            res3 = await client.post(
                "/admin/profile", json={"seconds": 0.01, "dir": str(tmp_path)}
            )
            assert res3.status == 202
            clock.gate.set()
            await app[PROFILER_KEY].wait()

    @async_test
    async def test_admin_profile_start_stop_and_409(self, tmp_path, monkeypatch):
        """start / stop by action; 409 for a second start, for a stop with
        nothing to stop and for a second stop; while a slow `stop_trace`
        runs (in a worker thread) the server goes on answering and the
        capture counts as active."""
        import threading

        import jax.profiler

        started, release = [], threading.Event()
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda target, profiler_options=None: started.append(
                (target, profiler_options.python_tracer_level)))
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: release.wait(30.0))
        server = self._server(profiler=ProfilerSession())
        app = server.create_application()
        async with TestClient(TestServer(app)) as client:
            res = await client.post("/admin/profile", json={"action": "stop"})
            assert res.status == 409
            res = await client.post(
                "/admin/profile", json={"action": "start", "dir": str(tmp_path)})
            assert res.status == 202
            info = await res.json()
            assert info["seconds"] is None and info["python"] is False
            assert started == [(info["dir"], 0)]  # the python tracer is off
            res = await client.post("/admin/profile", json={"action": "start"})
            assert res.status == 409
            stopping = asyncio.ensure_future(
                client.post("/admin/profile", json={"action": "stop"}))
            for _ in range(200):  # until stop_trace is under way
                if app[PROFILER_KEY]._stopping is not None:
                    break
                await asyncio.sleep(0.005)
            assert not stopping.done()
            t0 = asyncio.get_running_loop().time()
            tele = await (await client.get("/admin/telemetry")).json()
            assert asyncio.get_running_loop().time() - t0 < 1.0
            assert tele["profiler"]["active"] is True
            res = await client.post("/admin/profile", json={"action": "stop"})
            assert res.status == 409  # it is stopping already
            release.set()
            res = await stopping
            assert res.status == 200 and "stop_s" in await res.json()
            tele = await (await client.get("/admin/telemetry")).json()
            assert tele["profiler"]["active"] is False
            res = await client.post(
                "/admin/profile", json={"action": "start", "python": True})
            assert res.status == 202 and started[-1][1] == 1
            assert (await client.post(
                "/admin/profile", json={"action": "stop"})).status == 200
            res = await client.post("/admin/profile", json={"action": "pause"})
            assert res.status == 400

    @async_test
    async def test_admin_profile_rejects_bad_seconds(self):
        server = self._server(profiler=ProfilerSession(clock=_GateClock()))
        async with TestClient(TestServer(server.create_application())) as client:
            res = await client.post("/admin/profile", json={"seconds": -1})
            assert res.status == 400
            res = await client.post("/admin/profile", json={"seconds": "zzz"})
            assert res.status == 400


# ------------------------------------------------------- dispatch phases


class _TickClock(Clock):
    """Every reading is one second later than the last: each stamp of the
    engine's loop is told apart, and sums come out as whole numbers."""

    def __init__(self):
        self._now = 0.0

    def now(self) -> float:
        self._now += 1.0
        return self._now


def _row(row):
    return dict(zip(DISPATCH_COLUMNS, row))


class TestDispatchPhases:
    def test_phase_sums_wait_lag_and_dropped_iterations(self):
        clock = FakeClock()
        phases = DispatchPhases(clock)
        script = (("admit", 1.0), ("plan", 2.0), ("launch", 3.0), ("wait", 10.0))
        for phase, seconds in script:
            phases.mark(phase)
            if phase == "launch":
                phases.launched("mixed", 32, 8, 24, 8, compiled=True)
            clock.advance(seconds)
        phases.resumed(clock.now() - 2.5)  # on the host 2.5 s before the loop
        clock.advance(0.5)
        phases.mark("yield")
        clock.advance(4.0)
        assert phases.serial == 1
        row = _row(phases.commit())
        assert row == {
            "serial": 1, "launched_at": 3.0, "program": "mixed", "tokens": 32,
            "width": 8, "need_tokens": 32, "need_width": 8,
            "prefill_tokens": 24, "decode_tokens": 8,
            "admit": 1.0, "plan": 2.0, "launch": 3.0, "wait": 10.0,
            "route": 0.5, "yield": 4.0, "wait_lag": 2.5, "compiled": 1,
            "chained": 0, "deliver": 0.0, "overlapped": 0, "inline": 0,
            **dict.fromkeys(PARTS, 0.0), **dict.fromkeys(CPU_COLUMNS, 0.0),
            **dict.fromkeys(PAUSES, 0.0), "kv_page_kernel": 0,
            "kv_row_scatter": 0, "uploads": 0}
        assert sum(row[p] for p in PHASES) == clock.now()
        assert phases.serial == 2
        # an iteration that launched nothing is dropped, and so is idle time
        phases.mark("admit")
        clock.advance(7.0)
        assert phases.commit() is None
        phases.pause()
        clock.advance(100.0)
        phases.mark("admit")
        clock.advance(1.0)
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        row = _row(phases.commit())
        assert (row["serial"], row["admit"], row["launch"]) == (2, 1.0, 0.0)

    @pytest.mark.parametrize("need, row_need", [
        (None, (64, 16)), ((64, 16), (64, 16)), ((16, 8), (16, 8))])
    def test_a_row_holds_the_pair_needed_beside_the_pair_run_in(
            self, need, row_need):
        phases = DispatchPhases(FakeClock())
        phases.mark("launch")
        phases.launched("mixed", 64, 16, 0, 2, need=need)
        row = _row(phases.commit())
        assert (row["tokens"], row["width"]) == (64, 16)
        assert (row["need_tokens"], row["need_width"]) == row_need
        assert (row["prefill_tokens"], row["decode_tokens"]) == (0, 2)

    def test_chained_launches_commit_oldest_first(self):
        clock = FakeClock()
        phases = DispatchPhases(clock)
        phases.mark("launch")
        phases.launched("mixed_decode", 4, 8, 0, 4)
        clock.advance(1.0)
        phases.mark("launch")
        phases.launched("mixed_decode", 4, 8, 0, 4, chained=True)
        first, second = _row(phases.commit()), _row(phases.commit())
        assert (first["serial"], first["chained"], first["launched_at"]) == (1, 0, 0.0)
        assert (second["serial"], second["chained"], second["launched_at"]) == (2, 1, 1.0)
        assert phases.commit() is None

    def test_annotations_open_and_close_once_per_phase(self):
        opened, closed = [], []

        class Span:
            def __init__(self, name, **kwargs):
                opened.append((name, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                closed.append(1)

        phases = DispatchPhases(FakeClock(), annotate=Span)
        for phase in PHASES:
            phases.mark(phase)
        phases.commit()
        assert opened == [("engine." + p, {"dispatch": 1}) for p in PHASES]
        assert len(closed) == len(PHASES)

    def test_ring_is_bounded(self):
        rec = TimelineRecorder()
        for i in range(600):
            rec.record_dispatch([i])
        snap = rec.snapshot(now=12.5)
        assert len(snap["dispatches"]["rows"]) == 512
        assert snap["dispatches"]["rows"][-1] == [599]
        assert snap["dispatches"]["columns"] == list(DISPATCH_COLUMNS)
        assert snap["now"] == 12.5

    @async_test
    async def test_engine_phases_tile_the_launch_to_launch_interval(self):
        """Under a clock that ticks at every reading, what lies between
        two consecutive launches is, to the tick, the first dispatch's
        launch, wait, route and yield and the second's admit and plan; the
        counters hold the ring's sums; a request notes the dispatch that
        admitted it and the one that gave its first token."""
        label = "obs-phases"
        engine = make_engine(clock=_TickClock(), metrics_label=label)
        await engine.start()
        params = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
        await asyncio.gather(
            collect(engine.generate(list(range(1, 40)), params)),
            collect(engine.generate([4, 5, 6], params)))
        snap = engine.telemetry_snapshot()
        await engine.stop()
        rows = [_row(r) for r in snap["dispatches"]["rows"]]
        assert len(rows) >= 4
        assert [r["serial"] for r in rows] == list(range(1, len(rows) + 1))
        assert {r["program"] for r in rows} == {"mixed"}
        for a, b in zip(rows, rows[1:]):
            between = (a["launch"] + a["wait"] + a["route"] + a["yield"]
                       + b["admit"] + b["plan"])
            assert b["launched_at"] - a["launched_at"] == between
        assert all(0.0 <= r["wait_lag"] <= r["wait"] for r in rows)
        assert rows[0]["prefill_tokens"] > 0 and rows[-1]["decode_tokens"] > 0
        assert snap["now"] > rows[-1]["launched_at"]

        def counter(name, **labels):
            return REGISTRY.get_sample_value(
                name, {"model_name": label, **labels}) or 0.0

        assert counter("engine_dispatches_total", program="mixed") == len(rows)
        for phase in (*PHASES, "wait_lag"):
            assert counter("engine_dispatch_phase_seconds_total",
                           phase=phase) == sum(r[phase] for r in rows)
        # both are admitted before the first dispatch, whose 32-token budget
        # the long prompt (39 tokens) fills alone: its second chunk and the
        # short prompt ride the second, which gives both their first token
        spans = [(t["admit_dispatch"], t["first_token_dispatch"])
                 for t in snap["recent"]]
        assert spans == [(1, 2), (1, 2)]
        assert counter("engine_first_token_dispatches_count") == 2
        assert counter("engine_first_token_dispatches_sum") == 4

    @async_test
    async def test_delivery_behind_the_launch_lands_in_wait(self):
        """Under the ticking clock: a dispatch's tokens are handed over
        after the next launch, inside that iteration's `wait`, and noted
        beside the phases (`deliver`, `overlapped`, `inline`); the six
        phases still
        tile the period; the last dispatch hands over in `route`; the
        counters hold the rows' sums; a token's stamp carries the serial
        of the dispatch that produced it, whenever it was handed over."""
        label = "obs-deliver"
        engine = make_engine(clock=_TickClock(), metrics_label=label,
                             steps_per_sync=4)
        stamps = []
        hand_over = engine._hand_over

        def spy(owed):
            stamps.append((owed.serial, engine._phases.serial,
                           engine._phases._phase))
            return hand_over(owed)

        engine._hand_over = spy
        await engine.start()
        params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
        prompt = list(range(1, 9))
        await asyncio.gather(collect(engine.generate(prompt, params)),
                             collect(engine.generate(prompt[::-1], params)))
        await engine.stop()  # the last row is committed behind the yield
        snap = engine.telemetry_snapshot()
        rows = [_row(r) for r in snap["dispatches"]["rows"]]
        # a row says when ITS iteration's tokens were handed over (the
        # last: 8 behind the launch, 8 in place), and the counters are
        # fed from the rows
        assert [(r["overlapped"], r["inline"]) for r in rows] == [
            (0, 0), (8, 0), (8, 8)]
        for a, b in zip(rows, rows[1:]):
            between = (a["launch"] + a["wait"] + a["route"] + a["yield"]
                       + b["admit"] + b["plan"])
            assert b["launched_at"] - a["launched_at"] == between
        first, second, last = rows
        assert first["deliver"] == 0.0
        assert 0.0 < second["deliver"] < second["wait"]
        # nothing is handed over in the second iteration's route: it is
        # the advance alone, and no turn of the loop follows it
        assert second["route"] < last["route"] and second["yield"] == 1.0
        assert last["deliver"] > second["deliver"]  # 8 behind the launch + 8 in place
        # produced by dispatch n: handed over in iteration n + 1's wait,
        # the last dispatch's in its own route
        assert stamps == (
            [(1, 2, "wait")] * 8 + [(2, 3, "wait")] * 8 + [(3, 3, "route")] * 8)
        assert [(t["admit_dispatch"], t["first_token_dispatch"])
                for t in snap["recent"]] == [(1, 1), (1, 1)]

        def counter(name, **labels):
            return REGISTRY.get_sample_value(
                name, {"model_name": label, **labels}) or 0.0

        assert counter("engine_dispatch_part_seconds_total",
                       part="deliver") == sum(r["deliver"] for r in rows)
        assert counter("engine_dispatch_deliveries_total", when="overlapped") == 16
        assert counter("engine_dispatch_deliveries_total", when="inline") == 8
        for phase in (*PHASES, "wait_lag"):
            assert counter("engine_dispatch_phase_seconds_total",
                           phase=phase) == sum(r[phase] for r in rows)

    @pytest.mark.parametrize("token_s, wait, wait_lag", [
        (0.125, 1.0, 0.0),  # 4 tokens handed over in half a step
        (0.5, 2.0, 1.0),  # in two steps: the result waits a step for the loop
    ])
    @async_test
    async def test_a_delivery_that_outlasts_the_device_shows_as_wait_lag(
            self, token_s, wait, wait_lag):
        """The device takes 1 s a dispatch on the engine's clock and the
        fetch is with its worker BEFORE the delivery begins: a delivery
        shorter than the step costs the period nothing, a longer one shows
        as `wait_lag` (the result was on the host, the loop was not)."""
        clock = FakeClock()
        engine = make_engine(clock=clock, metrics_label="obs-deliver-lag",
                             steps_per_sync=4)

        class StepFetcher:
            """engine.types._DeadlineFetcher's duck: the worker has the
            result 1 s after the fetch was handed to it."""

            def fetch(self, fn, timeout_s):
                return fn()

            async def fetch_async(self, fn, timeout_s, meanwhile=None):
                ready_at = clock.now() + 1.0
                if meanwhile is not None:
                    meanwhile()
                await asyncio.sleep(0)
                clock.advance(max(0.0, ready_at - clock.now()))
                out = fn()
                engine._fetch_ready_at = ready_at
                return out

            def close(self):
                pass

        engine._fetcher.close()
        engine._fetcher = StepFetcher()
        hand_over = engine._hand_over

        def slow(owed):
            clock.advance(token_s)
            return hand_over(owed)

        engine._hand_over = slow
        await engine.start()
        params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
        await collect(engine.generate(list(range(1, 9)), params))
        await engine.stop()  # the last row is committed behind the yield
        snap = engine.telemetry_snapshot()
        rows = [_row(r) for r in snap["dispatches"]["rows"]]
        assert [(r["wait"], r["wait_lag"]) for r in rows] == [
            (1.0, 0.0), (wait, wait_lag), (wait, wait_lag)]
        assert [r["deliver"] for r in rows] == [
            0.0, 4 * token_s, 8 * token_s]
        # the last dispatch's own tokens, in place: that is `route`
        assert [r["route"] for r in rows] == [0.0, 0.0, 4 * token_s]

    def test_compile_seconds_counted_on_a_forced_retrace(self):
        import jax
        import jax.numpy as jnp

        from kserve_tpu.engine.compiled import _CompileCounting

        def seconds():
            return REGISTRY.get_sample_value(
                "engine_xla_compile_seconds_total",
                {"program": "obs-retrace"}) or 0.0

        fn = _CompileCounting("obs-retrace", jax.jit(lambda x: x * 2 + 1))
        fn(jnp.ones((4,)))
        first = seconds()
        assert fn.compiles == 1 and first > 0.0
        fn(jnp.ones((4,)))  # same shape: a cache hit costs nothing
        assert fn.compiles == 1 and seconds() == first
        fn(jnp.ones((8,)))  # another shape: a retrace, timed again
        assert fn.compiles == 2 and seconds() > first

    @async_test
    async def test_cpu_capture_holds_the_engines_phases(self, tmp_path):
        """A capture of a tiny engine on the CPU: the host plane holds the
        engine's own spans, on the profiler's clock."""
        from jax.profiler import ProfileData

        engine = make_engine(metrics_label="obs-capture")
        await engine.start()
        params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([4, 5, 6], params))  # compile first
        session = ProfilerSession(default_dir=str(tmp_path))
        info = await session.start()
        assert info["python"] is False and session.active
        await collect(engine.generate([7, 8, 9], params))
        stopped = await session.stop()
        await engine.stop()
        assert not session.active and stopped["stop_s"] >= 0.0
        (path,) = list(tmp_path.rglob("*.xplane.pb"))
        names = {e.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for e in line.events}
        assert {"engine." + p for p in PHASES} <= names
        assert {"engine." + p for p in PARTS} <= names  # nested in them


# ------------------------------------------- parts, CPU seconds, pauses


class _HalfCpu:
    """A CPU clock for tests: the thread is busy half of every second of
    the engine's clock (read without ticking it)."""

    def __init__(self, clock):
        self._clock = clock

    def __call__(self) -> float:
        return 0.5 * self._clock._now


def _counter(label):
    def counter(name, **labels):
        return REGISTRY.get_sample_value(
            name, {"model_name": label, **labels}) or 0.0
    return counter


def _process_counter(name, **labels):
    return REGISTRY.get_sample_value(name, labels) or 0.0


class TestDispatchParts:
    def test_a_part_is_timed_less_the_parts_inside_it(self):
        clock = FakeClock()
        cpu = [0.0]
        phases = DispatchPhases(clock, cpu_clock=lambda: cpu[0])

        def spend(wall, busy):
            clock.advance(wall)
            cpu[0] += busy

        phases.mark("plan")
        spend(0.25, 0.25)  # between the stamp and the first part
        with phases.span("prepare"):
            spend(1.0, 1.0)
            with phases.span("sampling"):
                spend(2.0, 1.5)
            spend(0.5, 0.5)
        with phases.span("pack"):
            spend(3.0, 3.0)
            with phases.span("sampling"):
                spend(1.0, 1.0)
        phases.mark("launch")
        with phases.span("upload"):
            spend(2.0, 0.5)  # blocked on the runtime for 1.5 s
        with phases.span("call"):
            spend(1.0, 1.0)
        with phases.span("account"):
            phases.launched("mixed", 32, 8, 24, 8)
            spend(0.5, 0.5)
        phases.mark("wait")
        with phases.span("deliver"):
            spend(4.0, 4.0)
        spend(6.0, 1.0)  # the loop's turns while the device runs
        phases.mark("route")
        with phases.span("register"):
            spend(0.125, 0.125)
        row = _row(phases.commit())
        assert {part: row[part] for part in PARTS} == {
            "prepare": 1.5, "sampling": 3.0, "pack": 3.0, "upload": 2.0,
            "call": 1.0, "account": 0.5, "deliver": 4.0, "register": 0.125}
        assert row["prepare"] + row["sampling"] + row["pack"] == row["plan"] - 0.25
        assert row["upload"] + row["call"] + row["account"] == row["launch"]
        assert row["deliver"] <= row["wait"] and row["register"] <= row["route"]
        assert {column: row[column] for column in CPU_COLUMNS} == {
            "cpu_admit": 0.0, "cpu_plan": 7.25, "cpu_launch": 2.0,
            "cpu_wait": 5.0, "cpu_route": 0.125, "cpu_yield": 0.0}
        assert all(row["cpu_" + p] <= row[p] for p in PHASES)
        # the next iteration starts from nothing
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        row = _row(phases.commit())
        assert not any(row[column] for column in (*PARTS, *CPU_COLUMNS))

    def test_without_a_cpu_clock_the_columns_read_zero(self):
        clock = FakeClock()
        phases = DispatchPhases(clock)
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        clock.advance(3.0)
        row = _row(phases.commit())
        assert (row["launch"], row["cpu_launch"], row["cpu_wait"]) == (3.0, 0.0, 0.0)

    def test_parts_are_spans_nested_in_their_phase_with_its_serial(self):
        events = []

        class Span:
            def __init__(self, name, **kwargs):
                self.name = (name, kwargs["dispatch"])

            def __enter__(self):
                events.append(("open", *self.name))
                return self

            def __exit__(self, *exc):
                events.append(("close", *self.name))

        phases = DispatchPhases(FakeClock(), annotate=Span)
        phases.mark("plan")
        with phases.span("pack"):
            with phases.span("sampling"):
                pass
        phases.mark("launch")
        with phases.span("upload"):
            pass
        phases.launched("mixed", 16, 8, 0, 2)
        phases.commit()
        assert events == [
            ("open", "engine.plan", 1), ("open", "engine.pack", 1),
            ("open", "engine.sampling", 1), ("close", "engine.sampling", 1),
            ("close", "engine.pack", 1), ("close", "engine.plan", 1),
            ("open", "engine.launch", 1), ("open", "engine.upload", 1),
            ("close", "engine.upload", 1), ("close", "engine.launch", 1)]

    def test_two_open_launches_book_parts_to_the_row_committed_next(self):
        """The dense path: dispatch 2 is launched, chained, before dispatch
        1 is routed, so its launch's parts are in the iteration that
        commits row 1, as its `launch` phase is; row 2 holds what came
        after that commit."""
        clock = FakeClock()
        phases = DispatchPhases(clock)
        for chained in (False, True):
            phases.mark("launch")
            with phases.span("upload"):
                clock.advance(1.0)
            with phases.span("call"):
                clock.advance(2.0)
            phases.launched("mixed_decode", 4, 8, 0, 4, chained=chained)
        first = _row(phases.commit())
        phases.mark("route")
        with phases.span("register"):
            clock.advance(0.5)
        second = _row(phases.commit())
        assert (first["serial"], first["launch"], first["upload"],
                first["call"], first["register"]) == (1, 6.0, 2.0, 4.0, 0.0)
        assert (second["serial"], second["chained"], second["launch"],
                second["upload"], second["register"]) == (2, 1, 0.0, 0.0, 0.5)

    def test_kv_writes_go_to_the_row_of_the_iteration_that_launched(self):
        clock = FakeClock()
        phases = DispatchPhases(clock)
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        phases.wrote("page_kernel", 36 * 8)
        phases.wrote("row_scatter", 8)
        phases.wrote("page_kernel", 4)
        row = _row(phases.commit())
        assert (row["kv_page_kernel"], row["kv_row_scatter"]) == (292, 8)
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        row = _row(phases.commit())
        assert (row["kv_page_kernel"], row["kv_row_scatter"]) == (0, 0)

    def test_a_pause_outside_an_iteration_is_nobody_s(self):
        clock = FakeClock()
        phases = DispatchPhases(clock)
        phases.paused("gc", 1.0)  # the loop is idle
        phases.mark("launch")
        phases.paused("gc", 0.25)
        phases.paused("other_compile", 2.0)
        phases.launched("mixed", 16, 8, 0, 2)
        row = _row(phases.commit())
        assert (row["gc"], row["other_compile"]) == (0.25, 2.0)
        phases.pause()
        phases.paused("other_compile", 5.0)
        phases.mark("launch")
        phases.launched("mixed", 16, 8, 0, 2)
        assert _row(phases.commit())["other_compile"] == 0.0

    @async_test
    async def test_engine_parts_make_up_plan_and_launch(self):
        """A `mixed` iteration under the ticking clock: the three parts of
        `plan` and the three of `launch` account for their phase but for
        the stamps between them (each reading of the clock is a second),
        no part exceeds its phase, the CPU seconds of every phase are
        under its wall seconds, and the counters hold the rows' sums."""
        label = "obs-parts"
        clock = _TickClock()
        engine = make_engine(clock=clock, metrics_label=label,
                             cpu_clock=_HalfCpu(clock))
        await engine.start()
        params = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
        await asyncio.gather(
            collect(engine.generate(list(range(1, 40)), params)),
            collect(engine.generate([4, 5, 6], params)))
        await engine.stop()
        rows = [_row(r) for r in engine.telemetry_snapshot()["dispatches"]["rows"]]
        assert len(rows) >= 4
        for r in rows:
            # plan | prepare | pack | launch: three stamps apart
            assert r["plan"] - (r["prepare"] + r["sampling"] + r["pack"]) == 3.0
            # launch | upload | call | account | wait: four
            assert r["launch"] - (r["upload"] + r["call"] + r["account"]) == 4.0
            assert r["deliver"] <= r["wait"] + r["route"]
            assert r["register"] <= r["route"]
            assert all(r["cpu_" + p] == 0.5 * r[p] for p in PHASES)
        # the sampling state's columns are built once an iteration, by
        # `_plan_ragged` (PR 45: `_prepare_chunk` builds none), and reach
        # the device inside the lanes' buffer: three transfers a dispatch
        assert all(r["sampling"] == 1.0 and r["uploads"] == 3 for r in rows)
        assert rows[-1]["register"] > 0.0  # the finishes give their pages back
        counter = _counter(label)
        for part in PARTS:
            assert counter("engine_dispatch_part_seconds_total",
                           part=part) == sum(r[part] for r in rows)
        for phase in PHASES:
            cpu = counter("engine_dispatch_phase_cpu_seconds_total", phase=phase)
            assert cpu == 0.5 * sum(r[phase] for r in rows)
            assert cpu <= counter("engine_dispatch_phase_seconds_total",
                                  phase=phase)

    @async_test
    async def test_the_dense_path_books_parts_as_it_books_phases(self):
        """Two dispatches in flight at once (`mixed_decode`, chained): every
        second of a launch's parts is in the row whose `launch` holds it."""
        engine = make_engine(clock=_TickClock(), metrics_label="obs-parts-dense",
                             spec_decode_k=0)
        await engine.start()
        params = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([4, 5, 6], params))
        await engine.stop()
        rows = [_row(r) for r in engine.telemetry_snapshot()["dispatches"]["rows"]]
        dense = [r for r in rows if r["program"] == "mixed_decode"]
        assert len(dense) >= 2 and any(r["chained"] for r in dense)
        for r in rows:
            launch = r["upload"] + r["call"] + r["account"]
            assert (launch > 0.0) == (r["launch"] > 0.0)
            assert launch <= r["launch"]
            assert r["prepare"] + r["sampling"] + r["pack"] <= r["plan"]

    @async_test
    async def test_real_clocks_cpu_stays_under_wall(self):
        engine = make_engine(metrics_label="obs-parts-real")
        await engine.start()
        params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([4, 5, 6], params))
        await engine.stop()
        rows = [_row(r) for r in engine.telemetry_snapshot()["dispatches"]["rows"]]
        assert rows and all(sum(r[c] for c in CPU_COLUMNS) > 0.0 for r in rows)
        # thread_time and monotonic are two clocks: a microsecond of slack
        assert all(r["cpu_" + p] <= r[p] + 1e-4 for r in rows for p in PHASES)


class TestProcessPauses:
    @async_test
    async def test_compiles_by_origin_the_row_and_the_listener_s_life(self):
        """One of the engine's programs compiling is the engine's own and
        no `other` compile; a helper jitted on the loop's thread in the
        middle of an iteration is one, lands in that iteration's row and
        nowhere else, and is logged once; the listener and the collector's
        callback leave with the last engine."""
        import gc

        import jax
        import jax.numpy as jnp
        from jax._src import monitoring

        watched_before = list(pauses._watchers)
        engine = make_engine(metrics_label="obs-pauses")
        seen = _process_counter("engine_other_compile_seconds_total")
        await engine.start()
        assert pauses._on_gc in gc.callbacks
        assert pauses._on_event_duration in monitoring.get_event_duration_listeners()
        plan_ragged, calls = engine._plan_ragged, []

        def compile_a_helper(meta, prefilling):
            if len(calls) == 1:  # inside the second iteration's `plan`
                jax.jit(lambda x: x * 3 + len(calls))(jnp.ones((7,)))
            calls.append(engine._phases.serial)
            return plan_ragged(meta, prefilling)

        engine._plan_ragged = compile_a_helper
        params = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([4, 5, 6], params))
        await engine.stop()
        rows = [_row(r) for r in engine.telemetry_snapshot()["dispatches"]["rows"]]
        hit = [r for r in rows if r["other_compile"] > 0.0]
        assert calls[1] in [r["serial"] for r in hit]
        # the engine's own programs compiled in these iterations too
        # (`compiled`), and are nobody's `other_compile`
        assert any(r["compiled"] and not r["other_compile"] for r in rows)
        assert (_process_counter("engine_other_compile_seconds_total") - seen
                >= sum(r["other_compile"] for r in hit) > 0.0)
        assert all(r["other_compile"] <= r["plan"] + r["launch"] for r in rows
                   if r["serial"] == calls[1])
        assert engine._warned_other_compile
        assert pauses._ref(engine._paused) not in pauses._watchers
        assert pauses._watchers == watched_before
        if not watched_before:
            assert pauses._on_gc not in gc.callbacks
            assert (pauses._on_event_duration
                    not in monitoring.get_event_duration_listeners())

    def test_a_counted_program_s_miss_is_its_own_and_a_bare_jit_other(self):
        import jax
        import jax.numpy as jnp

        from kserve_tpu.engine.compiled import _CompileCounting

        told = []

        def watcher(pause, seconds, what):
            if pause != "gc":  # the collector runs when it likes
                told.append((pause, what))

        pauses.watch(watcher)
        try:
            def other():
                return _process_counter("engine_other_compile_seconds_total")

            x = jnp.ones((3,))  # made before the count: `ones` compiles too
            told.clear()
            before = other()
            fn = _CompileCounting(
                "obs-origin", jax.jit(lambda x: x * 5 + 2))
            fn(x)
            assert fn.compiles == 1 and other() == before and told == []

            def obs_bare_helper(x):
                return x * 7 + 3

            jax.jit(obs_bare_helper)(x)
            assert other() > before and fn.compiles == 1
            assert told == [("other_compile", "jit(obs_bare_helper)")]
        finally:
            pauses.unwatch(watcher)
        assert pauses._ref(watcher) not in pauses._watchers

    def test_a_watcher_that_is_gone_is_not_held_nor_told(self):
        """An engine that never reaches the end of its `stop()` is not
        kept alive by the process's list, and the listener and the
        collector's callback leave once nobody is left to tell."""
        import gc

        class Engine:
            def __init__(self):
                self.told = []

            def paused(self, pause, seconds, what=""):
                self.told.append(pause)

        watched_before = list(pauses._watchers)
        engine = Engine()
        pauses.watch(engine.paused)
        pauses.watch(engine.paused)  # once is enough
        assert len(pauses._watchers) == len(watched_before) + 1
        gc.collect()
        assert "gc" in engine.told
        del engine
        gc.collect()
        pauses._tell("gc", 0.0)  # nobody there, nothing raised

        def late(pause, seconds, what):
            pass

        pauses.watch(late)
        pauses.unwatch(late)
        assert pauses._watchers == watched_before
        if not watched_before:
            assert pauses._on_gc not in gc.callbacks

    @async_test
    async def test_a_forced_collection_fills_the_row_s_gc(self):
        import gc

        engine = make_engine(metrics_label="obs-gc")
        await engine.start()
        plan_ragged, calls = engine._plan_ragged, []

        def collect_garbage(meta, prefilling):
            if len(calls) == 1:
                gc.collect()  # generation 2, inside the second iteration
            calls.append(engine._phases.serial)
            return plan_ragged(meta, prefilling)

        engine._plan_ragged = collect_garbage
        before = _process_counter("engine_gc_pause_seconds_total", generation="2")
        params = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
        await collect(engine.generate([4, 5, 6], params))
        await engine.stop()
        rows = [_row(r) for r in engine.telemetry_snapshot()["dispatches"]["rows"]]
        (row,) = [r for r in rows if r["serial"] == calls[1]]
        assert 0.0 < row["gc"] <= row["plan"]
        assert _process_counter(
            "engine_gc_pause_seconds_total", generation="2") >= before + row["gc"]

    def test_a_collection_of_generation_0_is_not_timed(self):
        import gc

        told = []

        def watcher(pause, seconds, what):
            told.append(pause)

        pauses.watch(watcher)
        gc.disable()  # only the collections made up here
        try:
            for generation, so_far in ((0, []), (1, ["gc"]), (2, ["gc", "gc"])):
                pauses._on_gc("start", {"generation": generation})
                pauses._on_gc("stop", {"generation": generation})
                assert told == so_far
        finally:
            gc.enable()
            pauses.unwatch(watcher)


# ------------------------------------------------------- trace propagation


class TestTraceContext:
    def test_parse_roundtrip_and_child(self):
        ctx = TraceContext.new_root()
        parsed = TraceContext.parse(ctx.to_header())
        assert parsed == ctx
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id

    def test_parse_rejects_malformed(self):
        assert TraceContext.parse(None) is None
        assert TraceContext.parse("") is None
        assert TraceContext.parse("00-zz-bad-01") is None
        assert TraceContext.parse("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
        assert TraceContext.parse("garbage") is None

    def test_propagate_headers_single_code_path(self):
        root = TraceContext.new_root()
        headers = {}
        with trace_scope(root):
            child = propagate_headers(headers)
        assert headers["traceparent"] == child.to_header()
        assert child.trace_id == root.trace_id
        # first hop with no bound context mints a root
        headers2 = {}
        minted = propagate_headers(headers2)
        assert TraceContext.parse(headers2["traceparent"]) == minted


class TestCrossHopTracing:
    @async_test
    async def test_epp_proxy_and_replica_form_one_linked_trace(
        self, recording_tracer
    ):
        """EPP proxy span and the replica's request span must share one
        trace id — the proxy injects a child traceparent, the replica's
        context middleware adopts it."""
        import aiohttp

        from kserve_tpu.scheduler.epp import EPPServer
        from kserve_tpu.scheduler.picker import EndpointPicker

        repo = ModelRepository()
        repo.update(DummyModel())
        replica = RESTServer(OpenAIDataPlane(repo), ModelRepositoryExtension(repo))
        replica_runner = web.AppRunner(replica.create_application())
        await replica_runner.setup()
        site = web.TCPSite(replica_runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        replica_url = f"http://127.0.0.1:{port}"

        picker = EndpointPicker([replica_url])
        epp = EPPServer(picker)
        epp_runner = web.AppRunner(epp.create_application())
        await epp_runner.setup()
        epp_site = web.TCPSite(epp_runner, "127.0.0.1", 0)
        await epp_site.start()
        epp_port = epp_site._server.sockets[0].getsockname()[1]
        try:
            caller = TraceContext.new_root()
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    f"http://127.0.0.1:{epp_port}/v1/models/dummy:predict",
                    json={"instances": [[1, 2]]},
                    headers={"traceparent": caller.to_header()},
                ) as resp:
                    assert resp.status == 200
            proxy_spans = recording_tracer.named("epp.proxy")
            replica_spans = recording_tracer.named(
                "POST /v1/models/{model_name}:predict")
            assert proxy_spans and replica_spans
            # one linked trace: caller -> EPP -> replica share the trace id
            assert proxy_spans[0].attributes["trace_id"] == caller.trace_id
            assert replica_spans[0].attributes["trace_id"] == caller.trace_id
            assert replica_spans[0].attributes["http.status_code"] == 200
        finally:
            await epp_runner.cleanup()
            await replica_runner.cleanup()

    @async_test
    async def test_engine_child_spans_carry_request_trace(self, recording_tracer):
        """Engine-internal queue/prefill/decode spans join the request's
        trace: the timeline captures the bound TraceContext at submit and
        the engine emits spans tagged with its trace id."""
        clock = FakeClock()
        engine = make_engine(clock=clock, metrics_label="obs-spans")
        await engine.start()
        ctx = TraceContext.new_root()
        params = SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True)
        with trace_scope(ctx):
            agen = engine.generate([1, 2, 3], params, request_id="span-req")
        outs = await collect(agen)
        await engine.stop()
        assert outs[-1].finished
        for name in ("engine.queue", "engine.prefill", "engine.decode"):
            spans = recording_tracer.named(name)
            assert spans, f"missing {name} span"
            assert spans[0].attributes["trace_id"] == ctx.trace_id
            assert spans[0].attributes["kserve.request_id"] == "span-req"
            assert spans[0].ended
        decode = recording_tracer.named("engine.decode")[0]
        assert decode.attributes["tokens"] == 3
        assert decode.attributes["finish_reason"] == "length"

    @async_test
    async def test_full_chain_epp_replica_engine_one_trace(
        self, recording_tracer
    ):
        """The acceptance shape end to end: caller -> EPP proxy -> engine-
        backed replica -> engine internals, every span on ONE trace id."""
        import aiohttp

        from kserve_tpu.models.llama import LlamaConfig as LC
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel
        from kserve_tpu.scheduler.epp import EPPServer
        from kserve_tpu.scheduler.picker import EndpointPicker

        model = JAXGenerativeModel(
            "tinyllm",
            model_config=LC.tiny(dtype="float32"),
            engine_config=EngineConfig(
                max_batch_size=2, page_size=8, num_pages=64,
                max_pages_per_seq=8, max_prefill_len=32,
                prefill_buckets=(16, 32), dtype="float32", use_pallas=False,
            ),
            random_weights=True,
        )
        model.load()
        await model.start_engine()
        repo = ModelRepository()
        repo.update(model)
        replica = RESTServer(OpenAIDataPlane(repo), ModelRepositoryExtension(repo))
        replica_runner = web.AppRunner(replica.create_application())
        await replica_runner.setup()
        site = web.TCPSite(replica_runner, "127.0.0.1", 0)
        await site.start()
        replica_url = (
            f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"
        )
        epp = EPPServer(EndpointPicker([replica_url]))
        epp_runner = web.AppRunner(epp.create_application())
        await epp_runner.setup()
        epp_site = web.TCPSite(epp_runner, "127.0.0.1", 0)
        await epp_site.start()
        epp_port = epp_site._server.sockets[0].getsockname()[1]
        try:
            caller = TraceContext.new_root()
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    f"http://127.0.0.1:{epp_port}/openai/v1/completions",
                    json={"model": "tinyllm", "prompt": "hi",
                          "max_tokens": 3, "ignore_eos": True},
                    headers={"traceparent": caller.to_header()},
                ) as resp:
                    assert resp.status == 200
            by_name = {
                name: recording_tracer.named(name)
                for name in ("epp.proxy", "engine.queue",
                             "engine.prefill", "engine.decode")
            }
            for name, spans in by_name.items():
                assert spans, f"missing {name} span"
                assert spans[0].attributes["trace_id"] == caller.trace_id, name
            replica_spans = [
                s for s in recording_tracer.spans
                if s.name.startswith("POST /openai")
            ]
            assert replica_spans
            assert replica_spans[0].attributes["trace_id"] == caller.trace_id
        finally:
            await model.engine.stop()
            await epp_runner.cleanup()
            await replica_runner.cleanup()

    @async_test
    async def test_rest_client_forwards_traceparent_on_retries(self):
        """Satellite: the InferenceRESTClient carries traceparent on every
        retry attempt (same trace, fresh span id), alongside the existing
        deadline/checkpoint headers, through one propagation code path."""
        import httpx

        from kserve_tpu.inference_client import InferenceRESTClient, RESTConfig

        seen = []

        def handler(request: httpx.Request) -> httpx.Response:
            seen.append(dict(request.headers))
            if len(seen) == 1:
                return httpx.Response(503, headers={"Retry-After": "0"})
            return httpx.Response(200, json={"predictions": [[2]]})

        client = InferenceRESTClient(RESTConfig(
            transport=httpx.MockTransport(handler),
            clock=FakeClock(),
        ))
        root = TraceContext.new_root()
        with trace_scope(root):
            result = await client.infer(
                "http://replica", {"instances": [[1]]}, model_name="m"
            )
        await client.close()
        assert result == {"predictions": [[2]]}
        assert len(seen) == 2
        ctxs = [TraceContext.parse(h.get("traceparent")) for h in seen]
        assert all(c is not None for c in ctxs)
        assert ctxs[0].trace_id == root.trace_id  # one trace across retries
        assert ctxs[1].trace_id == root.trace_id
        assert ctxs[0].span_id != ctxs[1].span_id  # fresh hop per attempt


# ------------------------------------------------------- cardinality gate


class TestMetricsCardinalityGate:
    def test_flags_unbounded_labels(self):
        from kserve_tpu.analysis.metrics_cardinality import scan_source

        bad = (
            "from prometheus_client import Counter\n"
            "C = Counter('x_total', 'doc', ['backend'])\n"
            "D = Counter('y_total', 'doc', labelnames=['request_id'])\n"
        )
        findings = scan_source(bad, "bad.py")
        assert len(findings) == 2
        assert "backend" in findings[0][2]
        assert "request_id" in findings[1][2]

    def test_flags_computed_label_lists(self):
        from kserve_tpu.analysis.metrics_cardinality import scan_source

        bad = (
            "from prometheus_client import Gauge\n"
            "labels = make_labels()\n"
            "G = Gauge('x', 'doc', labels)\n"
        )
        findings = scan_source(bad, "bad.py")
        assert len(findings) == 1 and "literal" in findings[0][2]

    def test_bounded_labels_pass(self):
        from kserve_tpu.analysis.metrics_cardinality import scan_source

        good = (
            "from prometheus_client import Histogram\n"
            "H = Histogram('x_seconds', 'doc', ['model_name', 'state'])\n"
            "N = Histogram('y_seconds', 'doc')\n"
        )
        assert scan_source(good, "good.py") == []

    def test_tree_is_clean(self):
        """The policy metrics.py documents holds across kserve_tpu/ — the
        same invocation scripts/lint.sh runs in CI."""
        import os

        from kserve_tpu.analysis.metrics_cardinality import scan_paths

        root = os.path.join(os.path.dirname(__file__), "..", "kserve_tpu")
        assert list(scan_paths([root])) == []
