"""Lifecycle layer tests (kserve_tpu/lifecycle — docs/lifecycle.md):
the replica state machine, portable generation checkpoints, the REST
admission/readiness gate + /admin/drain, second-signal escalation, engine
stop/drain stream guarantees, and the control-plane preStop synthesis.

All clocks are FakeClocks; nothing here sleeps for real."""

import asyncio
from types import SimpleNamespace

import pytest
from aiohttp.test_utils import TestClient, TestServer

from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.lifecycle import (
    CHECKPOINT_HEADER,
    CHECKPOINT_HEADER_SAFE_BYTES,
    DRAINING,
    READY,
    STARTING,
    TERMINATING,
    GenerationCheckpoint,
    GenerationPreempted,
    ReplicaDrainingError,
    ReplicaLifecycle,
    drain_grace_from_env,
)
from kserve_tpu.resilience import FakeClock

from conftest import async_test, hist_count


# ---------------- state machine ----------------


class TestStateMachine:
    def test_happy_path_transitions(self):
        transitions = []
        lc = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=10.0,
                              on_transition=transitions.append)
        assert lc.state == STARTING
        assert lc.accepting and not lc.ready
        lc.mark_ready()
        assert lc.state == READY and lc.ready and lc.accepting
        deadline = lc.begin_drain()
        assert lc.state == DRAINING
        # readiness red, admission closed, drain budget running
        assert not lc.ready and not lc.accepting
        assert deadline.remaining() == pytest.approx(10.0)
        lc.finish_drain()
        assert lc.state == TERMINATING
        assert transitions == [READY, DRAINING, TERMINATING]

    def test_transitions_forward_only(self):
        lc = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=5.0)
        lc.mark_ready()
        lc.begin_drain()
        lc.mark_ready()  # backwards: ignored
        assert lc.state == DRAINING

    def test_begin_drain_idempotent_shares_budget(self):
        clock = FakeClock()
        lc = ReplicaLifecycle(clock=clock, drain_grace_s=10.0)
        lc.mark_ready()
        first = lc.begin_drain()
        clock.advance(4.0)
        second = lc.begin_drain()  # SIGTERM after /admin/drain: same budget
        assert second is first
        assert second.remaining() == pytest.approx(6.0)

    def test_escalate_expires_budget_in_place(self):
        clock = FakeClock()
        lc = ReplicaLifecycle(clock=clock, drain_grace_s=30.0)
        lc.mark_ready()
        deadline = lc.begin_drain()
        assert not deadline.expired
        lc.escalate()  # second SIGTERM
        # the SAME deadline object every drain loop polls is now dead
        assert deadline.expired
        assert lc.state == TERMINATING

    def test_grace_from_env(self):
        assert drain_grace_from_env({"KSERVE_TPU_DRAIN_GRACE": "12.5"}) == 12.5
        assert drain_grace_from_env({}) == 30.0
        assert drain_grace_from_env({"KSERVE_TPU_DRAIN_GRACE": "soon"}) == 30.0
        # float() parses these without raising, but an infinite/negative
        # budget is a drain that never checkpoints (kubelet SIGKILLs it)
        assert drain_grace_from_env({"KSERVE_TPU_DRAIN_GRACE": "inf"}) == 30.0
        assert drain_grace_from_env({"KSERVE_TPU_DRAIN_GRACE": "nan"}) == 30.0
        assert drain_grace_from_env({"KSERVE_TPU_DRAIN_GRACE": "-5"}) == 30.0

    def test_state_gauge_one_hot(self):
        from kserve_tpu.metrics import LIFECYCLE_STATE

        lc = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=1.0)
        lc.mark_ready()
        lc.begin_drain()
        values = {
            s: LIFECYCLE_STATE.labels(state=s)._value.get()
            for s in (STARTING, READY, DRAINING, TERMINATING)
        }
        assert values == {STARTING: 0, READY: 0, DRAINING: 1, TERMINATING: 0}

    def test_drain_duration_observed(self):
        from kserve_tpu.metrics import DRAIN_DURATION

        clock = FakeClock()
        lc = ReplicaLifecycle(clock=clock, drain_grace_s=30.0)
        lc.mark_ready()
        before = hist_count(DRAIN_DURATION)
        lc.begin_drain()
        clock.advance(3.0)
        lc.finish_drain()
        lc.finish_drain()  # idempotent: one observation per drain
        assert hist_count(DRAIN_DURATION) == before + 1


# ---------------- checkpoints ----------------


class TestCheckpoint:
    def make(self, **kw):
        from kserve_tpu.resilience import Deadline

        clock = FakeClock()
        deadline = Deadline.after(7.0, clock)
        clock.advance(2.0)
        args = dict(
            request_id="req-1",
            prompt_ids=[1, 2, 3],
            generated=[4, 5],
            params=SamplingParams(max_tokens=9, temperature=0.0, seed=42,
                                  stop=["x"]),
            adapter=None,
            model_name="llm",
            deadline=deadline,
            reason="drain",
        )
        args.update(kw)
        return GenerationCheckpoint.capture(**args)

    def test_capture_and_round_trips(self):
        ckpt = self.make()
        assert ckpt.tokens_salvaged == 2
        assert ckpt.deadline_remaining_s == pytest.approx(5.0)
        for other in (
            GenerationCheckpoint.from_dict(ckpt.to_dict()),
            GenerationCheckpoint.from_json(ckpt.to_json()),
            GenerationCheckpoint.from_header(ckpt.to_header()),
        ):
            assert other.to_dict() == ckpt.to_dict()

    def test_sampling_params_reconstruct(self):
        params = self.make().sampling_params()
        assert params == SamplingParams(max_tokens=9, temperature=0.0,
                                        seed=42, stop=["x"])

    def test_malformed_header_is_none(self):
        assert GenerationCheckpoint.from_header(None) is None
        assert GenerationCheckpoint.from_header("") is None
        assert GenerationCheckpoint.from_header("not base64 json!") is None

    def test_unknown_keys_tolerated(self):
        # a newer replica's checkpoint must resume on an older one
        data = self.make().to_dict()
        data["future_field"] = {"x": 1}
        assert GenerationCheckpoint.from_dict(data).request_id == "req-1"

    def test_preempted_exception_carries_checkpoint(self):
        ckpt = self.make()
        exc = GenerationPreempted(ckpt)
        assert exc.checkpoint is ckpt
        assert "req-1" in str(exc) and "2 decoded tokens" in str(exc)

    def test_validate_wire_schema_pins_sampling_params(self):
        """checkpoint.py hardcodes the SamplingParams wire schema (it must
        not import jax via sampling.py); this pin makes schema drift fail
        loudly instead of silently dropping a new sampling field."""
        import dataclasses

        covered = (
            set(GenerationCheckpoint._SAMPLING_FLOATS)
            | set(GenerationCheckpoint._SAMPLING_INTS)
            | set(GenerationCheckpoint._SAMPLING_OPT_INTS)
            | {"ignore_eos", "stop"}
        )
        assert covered == {f.name for f in dataclasses.fields(SamplingParams)}

    def test_validate_normalizes_and_returns_self(self):
        data = self.make().to_dict()
        data["prompt_ids"] = [True, 2, 3]  # bools are valid indices
        data["sampling"]["temperature"] = 1  # int -> float
        ckpt = GenerationCheckpoint.from_dict(data)
        assert ckpt.validate(vocab_size=300) is ckpt
        assert ckpt.prompt_ids == [1, 2, 3]
        assert ckpt.sampling["temperature"] == 1.0
        assert isinstance(ckpt.sampling["temperature"], float)
        # validated sampling still reconstructs real SamplingParams
        assert ckpt.sampling_params().max_tokens == 9

    def test_validate_rejects_bad_token_ids(self):
        base = self.make().to_dict()
        for bad in ([1.5, 2], ["7", 2], [None]):
            ckpt = GenerationCheckpoint.from_dict({**base, "generated": bad})
            with pytest.raises(ValueError, match="integer token ids"):
                ckpt.validate()
        empty = GenerationCheckpoint.from_dict({**base, "prompt_ids": []})
        with pytest.raises(ValueError, match="empty prompt_ids"):
            empty.validate()
        oov = GenerationCheckpoint.from_dict({**base, "generated": [4, 999]})
        with pytest.raises(ValueError, match=r"outside\s+vocab"):
            oov.validate(vocab_size=300)
        oov.validate()  # no vocab bound known: ids pass

    def test_validate_rejects_bad_sampling_values(self):
        base = self.make().to_dict()
        for sampling in (
            "not a dict",
            {"temperature": "hot"},
            {"top_k": 1.5},
            {"seed": "lucky"},
            {"stop": "x"},  # must be a LIST of strings
            {"stop": [1, 2]},
        ):
            ckpt = GenerationCheckpoint.from_dict({**base, "sampling": sampling})
            with pytest.raises(ValueError, match="invalid checkpoint"):
                ckpt.validate()

    def test_validate_bounds_sampling_ints_to_int32(self):
        # sampling ints reach jnp.asarray(..., jnp.int32) in the shared run
        # loop, where an out-of-range Python int raises OverflowError and
        # kills every in-flight generation — reject at the wire instead
        base = self.make().to_dict()
        for sampling in (
            {"seed": 2 ** 63},
            {"top_k": 2 ** 31},
            {"max_tokens": -(2 ** 31) - 1},
        ):
            ckpt = GenerationCheckpoint.from_dict({**base, "sampling": sampling})
            with pytest.raises(ValueError, match="outside int32 range"):
                ckpt.validate()
        edge = GenerationCheckpoint.from_dict(
            {**base, "sampling": {"seed": 2 ** 31 - 1, "max_tokens": 9}})
        assert edge.validate().sampling["seed"] == 2 ** 31 - 1

    def test_validate_drops_unknown_sampling_keys(self):
        # a newer replica's checkpoint (extra sampling knob) must resume
        # here mid-rollout instead of failing SamplingParams(**sampling)
        data = self.make().to_dict()
        data["sampling"]["future_knob"] = 3
        ckpt = GenerationCheckpoint.from_dict(data).validate()
        assert "future_knob" not in ckpt.sampling
        assert ckpt.sampling_params() == SamplingParams(
            max_tokens=9, temperature=0.0, seed=42, stop=["x"])


# ---------------- SSE: no second response after headers ----------------


class TestStreamErrorContainment:
    """An unexpected exception from a streaming source AFTER the SSE
    response has started must end the stream with a final error event —
    re-raising would have the error middleware write a SECOND response
    into the already-chunked wire, corrupting it mid-flight (observed
    live: an over-budget max_tokens surfacing lazily at first iteration
    broke the client's chunked parser instead of reporting the error)."""

    @async_test
    async def test_mid_stream_exception_becomes_final_event(self):
        import json

        from aiohttp import web

        from kserve_tpu.protocol.openai.endpoints import _stream_sse

        async def source():
            yield "first"
            raise ValueError("prompt+max_tokens exceeds max_model_len 64")

        async def handler(request):
            return await _stream_sse(request, source())

        app = web.Application()
        app.router.add_get("/stream", handler)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/stream")
            assert resp.status == 200
            body = (await resp.read()).decode()
        finally:
            await client.close()
        events = [e for e in body.split("\n\n") if e.startswith("data:")]
        assert events[0] == "data: first"
        err = json.loads(events[-1][len("data:"):])
        assert err["error"]["type"] == "internal_error"
        assert "max_model_len" in err["error"]["message"]
        # no [DONE]: truncation stays detectable to splice-aware clients
        assert "[DONE]" not in body


# ---------------- REST surface: admission gate + /admin/drain ----------------


def make_lifecycle_client(lifecycle, on_drain=None):
    from kserve_tpu.model import Model
    from kserve_tpu.model_repository import ModelRepository
    from kserve_tpu.protocol.model_repository_extension import (
        ModelRepositoryExtension,
    )
    from kserve_tpu.protocol.openai.dataplane import OpenAIDataPlane
    from kserve_tpu.protocol.rest.server import RESTServer

    class EngineBackedModel(Model):
        def __init__(self):
            super().__init__("dummy")
            self.ready = True
            self.engine = SimpleNamespace(queue_depth=0)

        async def predict(self, payload, headers=None, response_headers=None):
            return {"predictions": payload["instances"]}

    repo = ModelRepository()
    model = EngineBackedModel()
    repo.update(model)
    server = RESTServer(
        OpenAIDataPlane(repo), ModelRepositoryExtension(repo),
        lifecycle=lifecycle, on_drain=on_drain,
    )
    return TestClient(TestServer(server.create_application())), model


class TestLifecycleHTTP:
    @async_test
    async def test_draining_rejects_inference_readiness_red_liveness_green(self):
        lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=10.0)
        lifecycle.mark_ready()
        client, _ = make_lifecycle_client(lifecycle)
        async with client:
            ok = await client.post("/v1/models/dummy:predict",
                                   json={"instances": [[1]]})
            assert ok.status == 200
            assert (await client.get("/v2/health/ready")).status == 200
            lifecycle.begin_drain()
            # new inference refused with a retry hint + the state
            res = await client.post("/v1/models/dummy:predict",
                                    json={"instances": [[1]]})
            assert res.status == 503
            assert res.headers["Retry-After"] == "1"
            assert (await res.json())["lifecycle"] == DRAINING
            # readiness red (endpoint set drops this replica)...
            ready = await client.get("/v2/health/ready")
            assert ready.status == 503
            assert (await ready.json())["lifecycle"] == DRAINING
            # ...while liveness and observability stay green (kubelet must
            # not kill the drain; the operator must be able to watch it)
            assert (await client.get("/")).status == 200
            assert (await client.get("/metrics")).status == 200
            admin = await client.post("/v2/repository/models/dummy/unload")
            assert admin.status != 503

    @async_test
    async def test_checkpoint_header_omitted_when_oversized(self):
        """A preempted generation's 503 carries the checkpoint in both the
        response header (convenience) and the body — but the header only
        while it fits CHECKPOINT_HEADER_SAFE_BYTES: stock intermediaries
        (httpx/h11, default aiohttp sessions) refuse larger header lines,
        which would crash the very client the checkpoint is meant to
        save.  The body always has it."""
        lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=20.0)
        lifecycle.mark_ready()
        client, model = make_lifecycle_client(lifecycle)
        small = GenerationCheckpoint(request_id="small-1", prompt_ids=[1],
                                     generated=[2], sampling={})
        big = GenerationCheckpoint(request_id="big-1",
                                   prompt_ids=list(range(10_000)),
                                   generated=[], sampling={})
        assert len(big.to_header()) > CHECKPOINT_HEADER_SAFE_BYTES
        current = {}

        async def preempt(payload, headers=None, response_headers=None):
            raise GenerationPreempted(current["ckpt"])

        model.predict = preempt
        async with client:
            current["ckpt"] = small
            res = await client.post("/v1/models/dummy:predict",
                                    json={"instances": [[1]]})
            assert res.status == 503
            assert res.headers.get(CHECKPOINT_HEADER) == small.to_header()
            assert (await res.json())["checkpoint"]["request_id"] == "small-1"
            current["ckpt"] = big
            res = await client.post("/v1/models/dummy:predict",
                                    json={"instances": [[1]]})
            assert res.status == 503
            assert CHECKPOINT_HEADER not in res.headers
            assert (await res.json())["checkpoint"]["request_id"] == "big-1"

    @async_test
    async def test_starting_replica_not_ready(self):
        lifecycle = ReplicaLifecycle(clock=FakeClock())
        client, _ = make_lifecycle_client(lifecycle)
        async with client:
            assert (await client.get("/v2/health/ready")).status == 503
            lifecycle.mark_ready()
            assert (await client.get("/v2/health/ready")).status == 200

    @async_test
    async def test_admin_drain_endpoint_triggers_callback_once(self):
        lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=20.0)
        lifecycle.mark_ready()
        drains = []

        async def on_drain():
            drains.append(lifecycle.begin_drain())
            lifecycle.finish_drain()

        client, _ = make_lifecycle_client(lifecycle, on_drain=on_drain)
        async with client:
            res = await client.post("/admin/drain")
            assert res.status == 200
            body = await res.json()
            assert body["lifecycle"] == DRAINING
            assert body["drain_remaining_s"] == pytest.approx(20.0)
            await asyncio.sleep(0)  # let the drain task run
            # a second POST (preStop + operator) does not restart the drain
            res2 = await client.post("/admin/drain")
            assert res2.status == 200
            assert len(drains) == 1
            assert lifecycle.state == TERMINATING

    @async_test
    async def test_admin_drain_answers_get_for_kubelet_prestop(self):
        """kubelet lifecycle httpGet handlers issue GET — the synthesized
        preStop hook (controlplane ensure_drain_lifecycle, which carries
        ?source=prestop) must start a drain, not 405."""
        lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=20.0)
        lifecycle.mark_ready()
        drains = []

        async def on_drain():
            drains.append(lifecycle.begin_drain())

        client, _ = make_lifecycle_client(lifecycle, on_drain=on_drain)
        async with client:
            res = await client.get("/admin/drain?source=prestop")
            assert res.status == 200
            assert (await res.json())["lifecycle"] == DRAINING
            await asyncio.sleep(0)
            assert len(drains) == 1

    @async_test
    async def test_bare_get_admin_drain_is_read_only(self):
        """The state machine is forward-only, so a stray GET (scanner,
        browser prefetch, misaimed probe) must NOT retire a healthy
        replica — it reads the drain status instead."""
        lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=20.0)
        lifecycle.mark_ready()
        drains = []

        async def on_drain():
            drains.append(lifecycle.begin_drain())

        client, _ = make_lifecycle_client(lifecycle, on_drain=on_drain)
        async with client:
            res = await client.get("/admin/drain")
            assert res.status == 200
            body = await res.json()
            assert body["lifecycle"] == READY
            assert body["drain_remaining_s"] is None
            assert drains == []
            assert lifecycle.state == READY  # still serving


# ---------------- engine: stop/drain stream guarantees ----------------


class TestEngineStopAndDrain:
    @async_test
    async def test_stop_fails_queued_unseated_requests_promptly(self):
        """ISSUE 5 satellite: a request still waiting for a slot when the
        engine stops mid-drain must receive an error on its stream queue —
        not hang its consumer forever."""
        from test_engine import make_engine

        engine = make_engine()  # never started: requests stay queued

        async def consume():
            async for _ in engine.generate([1, 2, 3], SamplingParams(max_tokens=4)):
                pass

        tasks = [asyncio.create_task(consume()) for _ in range(3)]
        for _ in range(5):
            await asyncio.sleep(0)
        assert engine.queue_depth == 3
        await engine.stop()
        for task in tasks:
            with pytest.raises(RuntimeError, match="engine stopped"):
                await asyncio.wait_for(task, timeout=1.0)
        assert engine.queue_depth == 0

    @async_test
    async def test_stopped_engine_refuses_new_work_synchronously(self):
        from test_engine import make_engine

        engine = make_engine()
        await engine.stop()
        with pytest.raises(ReplicaDrainingError):
            engine.generate([1, 2], SamplingParams(max_tokens=2))

    @async_test
    async def test_drain_checkpoints_queued_requests(self):
        """Queued-but-unseated requests are checkpointed immediately at
        drain start (prompt-only: resume elsewhere is a fresh prefill)."""
        from test_engine import make_engine

        engine = make_engine()  # never started: request stays queued
        caught = {}

        async def consume():
            try:
                async for _ in engine.generate(
                    [7, 8, 9], SamplingParams(max_tokens=4), request_id="q1"
                ):
                    pass
            except GenerationPreempted as exc:
                caught["ckpt"] = exc.checkpoint

        task = asyncio.create_task(consume())
        for _ in range(5):
            await asyncio.sleep(0)
        clock = FakeClock()
        checkpoints = await engine.drain(clock=clock)
        await asyncio.wait_for(task, timeout=1.0)
        assert [c.request_id for c in checkpoints] == ["q1"]
        assert caught["ckpt"].prompt_ids == [7, 8, 9]
        assert caught["ckpt"].generated == []  # nothing decoded yet
        assert engine.queue_depth == 0
        await engine.stop()

    @async_test
    async def test_crashed_prefill_fails_in_admission_requests(self):
        """A request _admit_batch has popped from the queue but not yet
        seated (its prefill crashed) must receive the error on its stream —
        the crash handler previously failed only _waiting and seated slots,
        stranding in-admission requests forever (found live: the broken
        pp-on-this-jax prefill hung its consumer instead of erroring)."""
        from test_engine import make_engine

        engine = make_engine()
        await engine.start()

        def boom(*a, **k):
            raise RuntimeError("injected prefill crash")

        engine._prefill_fn = boom
        engine._prefill_lp_fn = boom
        engine._mixed_fn = boom  # the unified path admits via mixed
        try:
            with pytest.raises(RuntimeError, match="injected prefill crash"):
                await asyncio.wait_for(
                    engine.generate(
                        [1, 2, 3], SamplingParams(max_tokens=4)
                    ).__anext__(),
                    timeout=2.0,
                )
            assert engine._admitting == []
            # every page admission allocated for the doomed batch came back
            assert engine.allocator.free_pages == engine.config.num_pages - 1
        finally:
            await engine.stop()


# ---------------- engine: resume admission is strict ----------------


class TestResumeAdmission:
    """Checkpoints arrive in client-supplied headers: resume_generation
    must reject untrusted input synchronously (to THIS caller) instead of
    admitting it into the shared run loop."""

    def test_resume_rejects_model_mismatch(self):
        from test_engine import make_engine

        engine = make_engine()
        ckpt = GenerationCheckpoint(
            request_id="r1", prompt_ids=[1, 2], generated=[3],
            sampling={"max_tokens": 4}, model_name="other-weights")
        with pytest.raises(ValueError, match="identical weights"):
            engine.resume_generation(ckpt)
        assert engine.resume_count == 0

    def test_resume_validates_wire_checkpoint_synchronously(self):
        from test_engine import make_engine

        engine = make_engine()
        bad = GenerationCheckpoint(
            request_id="r2", prompt_ids=[1, "x"], generated=[],
            sampling={"max_tokens": 4})
        with pytest.raises(ValueError, match="integer token ids"):
            engine.resume_generation(bad)
        oov = GenerationCheckpoint(
            request_id="r3",
            prompt_ids=[1, engine.model_config.vocab_size],
            generated=[], sampling={"max_tokens": 4})
        with pytest.raises(ValueError, match=r"outside\s+vocab"):
            engine.resume_generation(oov)
        assert engine.resume_count == 0

    def test_resume_rejects_overfull_checkpoint(self):
        """generated >= max_tokens means there is nothing left to decode —
        and because max_tokens is the TOTAL budget, this bound (with the
        prompt+max_tokens <= max_model_len check) is what keeps a crafted
        checkpoint's prompt+generated from overflowing allocation inside
        the shared run loop instead of failing this caller with a 400."""
        from test_engine import make_engine

        engine = make_engine()
        full = GenerationCheckpoint(
            request_id="r4", prompt_ids=[1, 2],
            generated=list(range(1, 9)), sampling={"max_tokens": 8})
        with pytest.raises(ValueError, match="nothing left to resume"):
            engine.resume_generation(full)
        overfull = GenerationCheckpoint(
            request_id="r5", prompt_ids=[1, 2],
            generated=[1] * 1999, sampling={"max_tokens": 4})
        with pytest.raises(ValueError, match="nothing left to resume"):
            engine.resume_generation(overfull)
        assert engine.resume_count == 0

    @async_test
    async def test_enqueue_after_drain_rejected_not_stranded(self):
        """A request that passed sync admission BEFORE a drain but reaches
        its first __anext__ (the actual enqueue) AFTER the drain's final
        flush must get ReplicaDrainingError — appending to _waiting then
        would strand the stream forever (no later flush runs)."""
        from test_engine import make_engine

        engine = make_engine()
        gen = engine.generate([1, 2, 3], SamplingParams(max_tokens=4))
        engine._draining = True  # drain lands before the first iteration
        with pytest.raises(ReplicaDrainingError):
            await gen.__anext__()
        assert engine._waiting == []

    @async_test
    async def test_duplicate_checkpoint_resumes_do_not_collide(self):
        """The SAME checkpoint replayed twice (client retry + EPP re-send
        is exactly the storm this feature serves) must run as two
        independent generations: the engine uniquifies its internal id,
        otherwise the first finisher's cancel() tears down every slot
        matching checkpoint.request_id — silently evicting the live
        sibling and hanging its stream forever."""
        import json

        from test_engine import make_engine

        # one decode step per chunk: the replays must genuinely interleave
        # across loop iterations (with the default 8-step chunks a 5-token
        # continuation finishes inside one chunk and never overlaps)
        engine = make_engine(steps_per_sync=1)
        await engine.start()
        try:
            wire = json.dumps(GenerationCheckpoint(
                request_id="dup", prompt_ids=[1, 2, 3], generated=[5],
                sampling={"max_tokens": 6, "temperature": 0.0,
                          "ignore_eos": True}).to_dict())

            def resume():
                return engine.resume_generation(
                    GenerationCheckpoint.from_dict(json.loads(wire)))

            async def drain(gen, acc):
                async for out in gen:
                    acc.append(out.token_id)

            # stagger the replays so the first finishes while the second is
            # still decoding — that is when the finisher's finally-cancel
            # would tear down the sibling's slot under a shared id
            a_tokens, b_tokens = [], []
            gen_a = resume()
            a_tokens.append((await gen_a.__anext__()).token_id)
            await asyncio.wait_for(
                asyncio.gather(drain(gen_a, a_tokens), drain(resume(), b_tokens)),
                timeout=5.0)
            # both streams ran to completion (5 = max_tokens - salvaged),
            # and greedy decoding makes them byte-identical
            assert len(a_tokens) == 5
            assert b_tokens == a_tokens
            assert engine.resume_count == 2
        finally:
            await engine.stop()

    def test_build_engine_threads_checkpoint_label(self):
        """The served model's name must become the checkpoint weights
        identity — with every engine defaulting to the same label, the
        resume model-mismatch guard would be vacuous."""
        from kserve_tpu.engine.dp import build_engine
        from kserve_tpu.engine.engine import EngineConfig
        from kserve_tpu.engine.tokenizer import ByteTokenizer
        from kserve_tpu.models.llama import LlamaConfig

        mc = LlamaConfig.tiny(dtype="float32")
        engine = build_engine(
            mc,
            EngineConfig(max_batch_size=2, page_size=8, num_pages=32,
                         max_pages_per_seq=4, max_prefill_len=16,
                         prefill_buckets=(16,), dtype="float32",
                         use_pallas=False),
            ByteTokenizer(mc.vocab_size),
            checkpoint_label="prod-llm",
        )
        assert engine._ckpt_label == "prod-llm"
        ckpt = GenerationCheckpoint(
            request_id="r", prompt_ids=[1], generated=[],
            sampling={"max_tokens": 4}, model_name="other-llm")
        with pytest.raises(ValueError, match="identical weights"):
            engine.resume_generation(ckpt)


class TestMultiChoicePreemption:
    """Multi-generation requests cannot carry per-choice checkpoints: a
    drain mid-gather must degrade to a plain retryable 503 without losing
    choices from the response shape, and a checkpoint attached to a
    multi-choice request is a 400."""

    def _preempted(self):
        ckpt = GenerationCheckpoint(
            request_id="r", prompt_ids=[1], generated=[2],
            sampling={"max_tokens": 4}, reason="drain")
        return GenerationPreempted(ckpt)

    def test_single_run_reraises_with_checkpoint(self):
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        pre = self._preempted()
        with pytest.raises(GenerationPreempted) as exc:
            JAXGenerativeModel._raise_gathered([pre])
        assert exc.value.checkpoint.request_id == "r"

    def test_multi_run_degrades_to_retryable_503(self):
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        with pytest.raises(ReplicaDrainingError):
            JAXGenerativeModel._raise_gathered(
                [("text", 1, "stop", None), self._preempted()])

    def test_non_preemption_error_wins(self):
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        with pytest.raises(RuntimeError, match="boom"):
            JAXGenerativeModel._raise_gathered(
                [self._preempted(), RuntimeError("boom")])

    def test_clean_results_pass_through(self):
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        assert JAXGenerativeModel._raise_gathered([1, 2]) == [1, 2]

    @async_test
    async def test_resume_with_multi_choice_request_is_400(self):
        from kserve_tpu.errors import InvalidInput
        from kserve_tpu.protocol.openai.types import CompletionRequest
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel("llm", model_config=None,
                                   random_weights=True)
        ckpt = GenerationCheckpoint(
            request_id="r", prompt_ids=[1], generated=[2],
            sampling={"max_tokens": 4})
        req = CompletionRequest(model="llm", prompt="x", n=2)
        with pytest.raises(InvalidInput, match="single prompt with n=1"):
            await model.create_completion(
                req, context={CHECKPOINT_HEADER: ckpt.to_header()})
        # multi-prompt via a list of token-id lists must trip the same
        # guard (a flat list of ints is ONE prompt and must not)
        req = CompletionRequest(model="llm", prompt=[[1, 2], [3, 4]], n=1)
        with pytest.raises(InvalidInput, match="single prompt with n=1"):
            await model.create_completion(
                req, context={CHECKPOINT_HEADER: ckpt.to_header()})

    @async_test
    async def test_non_stream_resume_with_logprobs_is_400(self):
        """The checkpoint carries tokens but not the prefix's logprob
        entries — a non-streaming resume cannot honor a logprobs request
        faithfully, and silently returning logprobs=null would break
        clients that index it.  Explicit 400 on both OpenAI surfaces."""
        from kserve_tpu.errors import InvalidInput
        from kserve_tpu.protocol.openai.types import (
            ChatCompletionRequest,
            CompletionRequest,
        )
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel("llm", model_config=None,
                                   random_weights=True)
        ckpt = GenerationCheckpoint(
            request_id="r", prompt_ids=[1], generated=[2],
            sampling={"max_tokens": 4, "logprobs": 2})
        req = CompletionRequest(model="llm", prompt="x", logprobs=2)
        with pytest.raises(InvalidInput, match="cannot reconstruct logprobs"):
            await model.create_completion(
                req, context={CHECKPOINT_HEADER: ckpt.to_header()})
        chat = ChatCompletionRequest(
            model="llm", messages=[{"role": "user", "content": "x"}],
            logprobs=True, top_logprobs=2)
        with pytest.raises(InvalidInput, match="cannot reconstruct logprobs"):
            await model.create_chat_completion(
                chat, context={CHECKPOINT_HEADER: ckpt.to_header()})


# ---------------- generative server: shutdown task references ----------------


class TestGenerativeServerStopTasks:
    @async_test
    async def test_stop_holds_strong_ref_and_prunes_on_completion(self):
        """ISSUE 5 satellite: the engine shutdown task must be strongly
        referenced (the loop holds tasks weakly — an un-referenced task can
        be GC'd before it runs and the drain silently never happens) and
        pruned once it completes so repeated stops don't accumulate."""
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel("llm", model_config=None, random_weights=True)
        release = asyncio.Event()
        stopped = asyncio.Event()

        async def engine_stop():
            await release.wait()
            stopped.set()

        model.engine = SimpleNamespace(running=True, stop=engine_stop)
        model.stop()
        assert len(model._stop_tasks) == 1  # strong reference held
        release.set()
        await asyncio.wait_for(stopped.wait(), timeout=1.0)
        await asyncio.sleep(0)  # let the done-callback run
        assert model._stop_tasks == []  # pruned, not accumulated

    @async_test
    async def test_escalate_cancels_pending_stop_without_new_tasks(self):
        """Second-signal escalation must cancel a wedged stop task and must
        NOT spawn fresh stop work (that could race the in-progress drain —
        the normal shutdown path owns issuing the stop)."""
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel("llm", model_config=None, random_weights=True)

        async def wedged_stop():
            await asyncio.Event().wait()  # never returns

        model.engine = SimpleNamespace(running=True, stop=wedged_stop)
        model.stop()
        (task,) = model._stop_tasks
        model.stop(escalate=True)
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(task, timeout=1.0)
        assert model._stop_tasks == []  # cancelled task pruned, none spawned


# ---------------- model server: signals + drain orchestration ----------------


class TestModelServerLifecycle:
    def make_server(self):
        from kserve_tpu.model_server import ModelServer

        server = ModelServer(enable_grpc=False)
        server.lifecycle = ReplicaLifecycle(clock=FakeClock(), drain_grace_s=10.0)
        return server

    @async_test
    async def test_second_signal_escalates(self):
        """ISSUE 5 satellite: the second SIGINT/SIGTERM must escalate to
        immediate shutdown (expired drain budget), not re-set the same
        stop event as a no-op."""
        server = self.make_server()
        server.lifecycle.mark_ready()
        stop_event = asyncio.Event()
        handler = server._make_signal_handler(stop_event)
        handler()  # first signal: graceful drain begins
        assert stop_event.is_set()
        deadline = server.lifecycle.begin_drain()
        assert not deadline.expired
        handler()  # second signal: escalate
        assert deadline.expired
        assert server.lifecycle.state == TERMINATING

    @async_test
    async def test_escalation_fans_out_to_models_that_understand_it(self):
        """The second signal passes escalate=True to models whose stop()
        accepts it (cancelling their wedged shutdown work) and skips base
        models whose stop() has no such parameter."""
        server = self.make_server()
        server.lifecycle.mark_ready()
        calls = []

        class EscalatableModel:
            def stop(self, escalate=False):
                calls.append(escalate)

        class PlainModel:
            def stop(self):
                calls.append("plain")

        server.registered_models.update_handle("a", EscalatableModel())
        server.registered_models.update_handle("b", PlainModel())
        handler = server._make_signal_handler(asyncio.Event())
        handler()  # first: drain
        handler()  # second: escalate
        assert calls == [True]  # only the escalatable model, escalate=True

    @async_test
    async def test_drain_async_prefers_model_level_drain(self):
        """A model exposing its own drain() (e.g. a wrapper aggregating
        several engines) owns the checkpointing; the engine fallback must
        not run a second drain on the same engine."""
        server = self.make_server()
        server.lifecycle.mark_ready()
        engine_drains = []

        class FakeEngine:
            async def drain(self, deadline):
                engine_drains.append(deadline)
                return ["engine-ckpt"]

        class DrainingModel:
            engine = FakeEngine()

            async def drain(self, deadline):
                return ["model-ckpt"]

        server.registered_models.update_handle("llm", DrainingModel())
        checkpoints = await server.drain_async()
        assert checkpoints == ["model-ckpt"]
        assert engine_drains == []  # engine fallback skipped

    @async_test
    async def test_drain_async_drains_models_concurrently(self):
        """Every engine must flip into drain mode immediately: a
        sequentially-drained second model would keep seating new work (and
        'length'-finishing KV-starved lanes) while the first consumes the
        shared budget."""
        server = self.make_server()
        server.lifecycle.mark_ready()
        started, release = [], asyncio.Event()

        def make_model(name):
            class Model:
                async def drain(self, deadline):
                    started.append(name)
                    await release.wait()
                    return [f"{name}-ckpt"]
            return Model()

        server.registered_models.update_handle("a", make_model("a"))
        server.registered_models.update_handle("b", make_model("b"))
        task = asyncio.ensure_future(server.drain_async())
        for _ in range(5):  # ticks: drain_async body, then the gather fan-out
            await asyncio.sleep(0)
            if len(started) == 2:
                break
        assert sorted(started) == ["a", "b"]  # both flipped BEFORE either ends
        release.set()
        checkpoints = await asyncio.wait_for(task, timeout=1.0)
        assert sorted(checkpoints) == ["a-ckpt", "b-ckpt"]

    @async_test
    async def test_drain_async_drains_engines_and_records_duration(self):
        server = self.make_server()
        server.lifecycle.mark_ready()
        drained = []

        class FakeEngine:
            async def drain(self, deadline):
                drained.append(deadline)
                return ["ckpt"]

        model = SimpleNamespace(engine=FakeEngine(), name="llm")
        server.registered_models.update_handle("llm", model)
        checkpoints = await server.drain_async()
        assert checkpoints == ["ckpt"]
        # engines got the lifecycle's budget, and the drain settled
        assert drained == [server.lifecycle.drain_deadline]
        assert server.lifecycle.state == TERMINATING


# ---------------- control plane: preStop + grace synthesis ----------------


class TestControlPlaneDrain:
    def test_ensure_drain_lifecycle(self):
        from kserve_tpu.controlplane.objects import ensure_drain_lifecycle

        container = {"name": "main", "ports": [{"containerPort": 9000}]}
        ensure_drain_lifecycle(container, 30.0)
        pre_stop = container["lifecycle"]["preStop"]["httpGet"]
        assert pre_stop == {"path": "/admin/drain?source=prestop", "port": 9000}
        env = {e["name"]: e["value"] for e in container["env"]}
        assert env["KSERVE_TPU_DRAIN_GRACE"] == "30"
        # idempotent: re-applying must not duplicate the env entry
        ensure_drain_lifecycle(container, 30.0)
        assert len(container["env"]) == 1

    def test_user_provided_prestop_wins(self):
        from kserve_tpu.controlplane.objects import ensure_drain_lifecycle

        container = {
            "name": "main",
            "lifecycle": {"preStop": {"exec": {"command": ["/bye"]}}},
        }
        ensure_drain_lifecycle(container, 30.0)
        assert container["lifecycle"]["preStop"] == {
            "exec": {"command": ["/bye"]}
        }

    def test_llmisvc_workload_synthesizes_drain_wiring(self):
        """The reconciled decode workload carries the preStop drain hook,
        the KSERVE_TPU_DRAIN_GRACE env, and a terminationGracePeriodSeconds
        that covers the drain budget plus shutdown margin — kubelet never
        SIGKILLs a generation still inside its budget."""
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import (
            DRAIN_GRACE_S,
            DRAIN_SHUTDOWN_MARGIN_S,
            LLMISVCReconciler,
        )

        llm = LLMInferenceService.model_validate({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "llama", "namespace": "default"},
            "spec": {
                "model": {"uri": "hf://meta-llama/Llama-3.2-1B", "name": "llama"},
                "workload": {"replicas": 1, "parallelism": {"tensor": 4}},
            },
        })
        reconciler = LLMISVCReconciler()
        spec = reconciler._merge_presets(llm)
        objects = reconciler._workload(
            llm, spec.workload, "decode", str(llm.spec.model.uri))
        deployment = next(o for o in objects if o["kind"] == "Deployment")
        pod = deployment["spec"]["template"]["spec"]
        assert pod["terminationGracePeriodSeconds"] == int(
            DRAIN_GRACE_S + DRAIN_SHUTDOWN_MARGIN_S
        )
        main = next(c for c in pod["containers"] if c["name"] == "main")
        port = main["ports"][0]["containerPort"]
        assert main["lifecycle"]["preStop"]["httpGet"] == {
            "path": "/admin/drain?source=prestop", "port": port,
        }
        env = {e["name"]: e["value"] for e in main["env"]}
        assert env["KSERVE_TPU_DRAIN_GRACE"] == f"{DRAIN_GRACE_S:g}"

    def test_user_drain_grace_env_extends_termination_grace(self):
        """A pod-template KSERVE_TPU_DRAIN_GRACE override wins inside
        ensure_drain_lifecycle, so terminationGracePeriodSeconds must be
        derived from the EFFECTIVE value — otherwise kubelet SIGKILLs at
        default-grace+margin while the runtime is still granting the
        user's longer budget."""
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import (
            DRAIN_SHUTDOWN_MARGIN_S,
            LLMISVCReconciler,
        )

        llm = LLMInferenceService.model_validate({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "llama", "namespace": "default"},
            "spec": {
                "model": {"uri": "hf://meta-llama/Llama-3.2-1B", "name": "llama"},
                "workload": {
                    "replicas": 1,
                    "template": {"containers": [{
                        "name": "main",
                        "env": [{"name": "KSERVE_TPU_DRAIN_GRACE",
                                 "value": "300"}],
                    }]},
                },
            },
        })
        reconciler = LLMISVCReconciler()
        spec = reconciler._merge_presets(llm)
        objects = reconciler._workload(
            llm, spec.workload, "decode", str(llm.spec.model.uri))
        deployment = next(o for o in objects if o["kind"] == "Deployment")
        pod = deployment["spec"]["template"]["spec"]
        main = next(c for c in pod["containers"] if c["name"] == "main")
        env = {e["name"]: e["value"] for e in main["env"]}
        assert env["KSERVE_TPU_DRAIN_GRACE"] == "300"
        assert pod["terminationGracePeriodSeconds"] == int(
            300 + DRAIN_SHUTDOWN_MARGIN_S
        )

    def test_non_finite_drain_grace_env_keeps_default(self):
        """float('inf') parses without raising, so it slips past the
        garbage guard — but int(inf + margin) would crash the reconcile
        loop, and the runtime (drain_grace_from_env) falls back to the
        default for non-finite values anyway: the synthesized grace period
        must track what the runtime will actually grant."""
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import (
            DRAIN_GRACE_S,
            DRAIN_SHUTDOWN_MARGIN_S,
            LLMISVCReconciler,
        )

        llm = LLMInferenceService.model_validate({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "llama", "namespace": "default"},
            "spec": {
                "model": {"uri": "hf://meta-llama/Llama-3.2-1B", "name": "llama"},
                "workload": {
                    "replicas": 1,
                    "template": {"containers": [{
                        "name": "main",
                        "env": [{"name": "KSERVE_TPU_DRAIN_GRACE",
                                 "value": "inf"}],
                    }]},
                },
            },
        })
        reconciler = LLMISVCReconciler()
        spec = reconciler._merge_presets(llm)
        objects = reconciler._workload(
            llm, spec.workload, "decode", str(llm.spec.model.uri))
        deployment = next(o for o in objects if o["kind"] == "Deployment")
        pod = deployment["spec"]["template"]["spec"]
        assert pod["terminationGracePeriodSeconds"] == int(
            DRAIN_GRACE_S + DRAIN_SHUTDOWN_MARGIN_S
        )


class TestControlPlaneAOTCache:
    """The llmisvc reconciler wires the persistent AOT executable cache
    (docs/coldstart.md): a node-local hostPath mounted into the main
    container with KSERVE_TPU_AOT_CACHE pointing at it, so replica
    restarts on the same node start with zero XLA compiles."""

    def _reconcile(self, template=None):
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import LLMISVCReconciler

        workload = {"replicas": 1}
        if template is not None:
            workload["template"] = template
        llm = LLMInferenceService.model_validate({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "llama", "namespace": "default"},
            "spec": {
                "model": {"uri": "hf://meta-llama/Llama-3.2-1B",
                          "name": "llama"},
                "workload": workload,
            },
        })
        reconciler = LLMISVCReconciler()
        spec = reconciler._merge_presets(llm)
        objects = reconciler._workload(
            llm, spec.workload, "decode", str(llm.spec.model.uri))
        deployment = next(o for o in objects if o["kind"] == "Deployment")
        return deployment["spec"]["template"]["spec"]

    def test_workload_mounts_node_local_aot_cache(self):
        from kserve_tpu.controlplane.objects import (
            AOT_CACHE_HOST_PATH,
            AOT_CACHE_MOUNT_PATH,
            AOT_CACHE_VOLUME,
        )

        pod = self._reconcile()
        main = next(c for c in pod["containers"] if c["name"] == "main")
        env = {e["name"]: e.get("value") for e in main["env"]}
        assert env["KSERVE_TPU_AOT_CACHE"] == AOT_CACHE_MOUNT_PATH
        mount = next(m for m in main["volumeMounts"]
                     if m["name"] == AOT_CACHE_VOLUME)
        assert mount["mountPath"] == AOT_CACHE_MOUNT_PATH
        volume = next(v for v in pod["volumes"]
                      if v["name"] == AOT_CACHE_VOLUME)
        assert volume["hostPath"] == {
            "path": AOT_CACHE_HOST_PATH, "type": "DirectoryOrCreate",
        }

    def test_user_aot_cache_env_wins(self):
        """An operator pointing KSERVE_TPU_AOT_CACHE at their own warmed
        PVC mount must not get the hostPath volume stacked on top."""
        from kserve_tpu.controlplane.objects import AOT_CACHE_VOLUME

        pod = self._reconcile(template={"containers": [{
            "name": "main",
            "env": [{"name": "KSERVE_TPU_AOT_CACHE",
                     "value": "/mnt/warmed-cache"}],
        }]})
        main = next(c for c in pod["containers"] if c["name"] == "main")
        env = {e["name"]: e.get("value") for e in main["env"]}
        assert env["KSERVE_TPU_AOT_CACHE"] == "/mnt/warmed-cache"
        assert not any(m.get("name") == AOT_CACHE_VOLUME
                       for m in main.get("volumeMounts", []))
        assert not any(v.get("name") == AOT_CACHE_VOLUME
                       for v in pod.get("volumes", []))


class TestControlPlaneKVPersist:
    """kvCacheOffloading.persistentPrefixCache (docs/kv_hierarchy.md): the
    persistent prefix store rides the SAME node-local hostPath as the AOT
    executable cache — one mount, two persistence layers, and the env
    KSERVE_TPU_KV_PERSIST points the runtime at its subdir."""

    def _reconcile(self, kv=None):
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import LLMISVCReconciler

        workload = {"replicas": 1}
        if kv is not None:
            workload["kvCacheOffloading"] = kv
        llm = LLMInferenceService.model_validate({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "llama", "namespace": "default"},
            "spec": {
                "model": {"uri": "hf://meta-llama/Llama-3.2-1B",
                          "name": "llama"},
                "workload": workload,
            },
        })
        reconciler = LLMISVCReconciler()
        spec = reconciler._merge_presets(llm)
        objects = reconciler._workload(
            llm, spec.workload, "decode", str(llm.spec.model.uri))
        deployment = next(o for o in objects if o["kind"] == "Deployment")
        return deployment["spec"]["template"]["spec"]

    def _main_env(self, pod):
        main = next(c for c in pod["containers"] if c["name"] == "main")
        return main, {e["name"]: e.get("value") for e in main["env"]}

    def test_enabled_spec_sets_env_on_aot_mount(self):
        from kserve_tpu.controlplane.objects import (
            AOT_CACHE_VOLUME,
            KV_PERSIST_DEFAULT_PATH,
        )

        pod = self._reconcile(kv={
            "persistentPrefixCache": {"enabled": True},
        })
        main, env = self._main_env(pod)
        assert env["KSERVE_TPU_KV_PERSIST"] == KV_PERSIST_DEFAULT_PATH
        # the prefix dir lives under the AOT cache mount — no second volume
        assert any(m.get("name") == AOT_CACHE_VOLUME
                   for m in main["volumeMounts"])
        # independent of host offload: no --kv_offload args synthesized
        assert not any(a.startswith("--kv_offload") for a in main["args"])

    def test_custom_path_and_user_env_win(self):
        pod = self._reconcile(kv={
            "enabled": True, "hostMemoryGi": 4,
            "persistentPrefixCache": {"enabled": True,
                                      "path": "/mnt/warm/kv"},
        })
        _, env = self._main_env(pod)
        assert env["KSERVE_TPU_KV_PERSIST"] == "/mnt/warm/kv"

    def test_disabled_or_absent_leaves_no_env(self):
        for kv in (None, {"enabled": True, "hostMemoryGi": 4},
                   {"persistentPrefixCache": {"enabled": False}}):
            _, env = self._main_env(self._reconcile(kv=kv))
            assert "KSERVE_TPU_KV_PERSIST" not in env, kv

    def test_crd_schema_carries_persistent_prefix_cache(self):
        from kserve_tpu.controlplane.crdgen import crd_manifest

        manifest = crd_manifest("LLMInferenceService")
        schema = manifest["spec"]["versions"][0]["schema"]["openAPIV3Schema"]
        kv = (schema["properties"]["spec"]["properties"]["workload"]
              ["properties"]["kvCacheOffloading"]["properties"])
        assert "persistentPrefixCache" in kv
        assert set(kv["persistentPrefixCache"]["properties"]) == {
            "enabled", "path"}


# ---------------- event-loop responsiveness during device fetch ----------------


class TestFetchLoopResponsiveness:
    """A drain (or a readiness probe, or /admin/drain itself) can only land
    mid-generation if the event loop keeps serving WHILE a decode chunk
    computes.  The decode hot loop therefore awaits its device fetches
    (engine._fetch_async -> _DeadlineFetcher.fetch_async) instead of
    sitting in a threading wait on the loop thread."""

    @async_test
    async def test_fetch_async_keeps_event_loop_serving(self):
        import threading

        from kserve_tpu.engine.types import _DeadlineFetcher

        fetcher = _DeadlineFetcher()
        gate = threading.Event()
        # backstop: with a regression to a blocking wait this test would
        # otherwise hang the suite (the loop could never run gate.set())
        backstop = threading.Timer(10.0, gate.set)
        backstop.start()
        try:
            def compute():  # the "device": returns only when released
                assert gate.wait(15.0)
                return 42

            task = asyncio.create_task(
                fetcher.fetch_async(compute, timeout_s=20.0))
            # the fetch is in flight on the worker thread; the loop must
            # still be running OTHER coroutines — these turns only execute
            # promptly if fetch_async yielded
            for _ in range(20):
                await asyncio.sleep(0)
            assert not task.done()
            gate.set()  # release the device
            assert await task == 42
        finally:
            backstop.cancel()
            fetcher.close()

    @pytest.mark.parametrize("raises", [False, True])
    @async_test
    async def test_fetch_async_runs_meanwhile_beside_the_worker(self, raises):
        """`meanwhile` (the mixed step's deferred delivery) runs on the
        caller's thread once the worker has the thunk and before the
        result is awaited, with no task started for it; one that raises
        is the caller's error and leaves the worker serving."""
        import threading

        from kserve_tpu.engine.types import _DeadlineFetcher

        fetcher = _DeadlineFetcher()
        started, release = threading.Event(), threading.Event()
        log = []

        def compute():
            started.set()
            assert release.wait(15.0)
            log.append("computed")
            return 42

        def meanwhile():
            assert started.wait(15.0)  # the worker is already at it
            assert threading.current_thread() is threading.main_thread()
            log.append("meanwhile")
            release.set()
            if raises:
                raise ValueError("a delivery failed")

        try:
            tasks = len(asyncio.all_tasks())
            fetching = fetcher.fetch_async(compute, 20.0, meanwhile)
            if raises:
                with pytest.raises(ValueError):
                    await fetching
            else:
                assert await fetching == 42
            assert len(asyncio.all_tasks()) == tasks
            assert log[0] == "meanwhile"
            assert await fetcher.fetch_async(lambda: 7, 20.0) == 7
            assert log == ["meanwhile", "computed"]
        finally:
            release.set()
            fetcher.close()

    @async_test
    async def test_fetch_async_timeout_maps_to_wedge_contract(self):
        import threading

        from kserve_tpu.engine.types import _DeadlineFetcher

        fetcher = _DeadlineFetcher()
        hang = threading.Event()
        try:
            with pytest.raises(TimeoutError):
                await fetcher.fetch_async(
                    lambda: hang.wait(5.0), timeout_s=0.02)
        finally:
            hang.set()  # unstick the worker so close() is clean
            fetcher.close()
