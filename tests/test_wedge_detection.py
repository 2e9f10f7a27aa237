"""Engine wedge detection (VERDICT round-2 #7): a device fetch that blows
the step deadline marks the engine wedged; /v2/health/live goes red so the
pod restarts instead of hanging behind a healthy-looking HTTP server.

Parity role: huggingfaceserver/health_check.py (the reference's serving
liveness for stuck accelerator runtimes)."""

import asyncio
import time

import pytest

from kserve_tpu.engine.engine import EngineWedgedError
from kserve_tpu.engine.sampling import SamplingParams

from conftest import async_test
from test_engine import make_engine


class _BlockingChunk:
    """A fake device result whose host fetch never completes (what a
    wedged device looks like from np.asarray)."""

    def __array__(self, dtype=None, copy=None):
        # this sleep IS the simulated wedge (a host fetch that never
        # returns); the engine's watchdog must fire around it
        time.sleep(3600)  # jaxlint: disable=blocking-async

    def __getitem__(self, item):
        return self


class TestFetchDeadline:
    def test_fetch_timeout_marks_wedged(self):
        engine = make_engine(step_deadline_s=0.3)
        assert not engine.wedged
        with pytest.raises(EngineWedgedError):
            engine._fetch(_BlockingChunk())
        assert engine.wedged

    def test_normal_fetch_passes_through(self):
        import numpy as np

        engine = make_engine(step_deadline_s=5.0)
        out = engine._fetch([1, 2, 3])
        assert isinstance(out, np.ndarray)
        assert not engine.wedged


class TestWedgedLiveness:
    @async_test
    async def test_blocked_decode_fails_request_and_liveness(self):
        """End to end through the running engine loop: a decode chunk whose
        fetch hangs -> the awaiting request fails, the engine reports
        wedged, the dataplane reports non-alive, the v2 endpoint 503s."""
        engine = make_engine(step_deadline_s=0.5)
        await engine.start()
        # wedge the DEVICE path only: dispatch returns a result whose
        # host fetch never completes
        engine._decode_fn = lambda *a, **k: (_BlockingChunk(),
                                             engine.kv_pages)
        engine._mixed_fn = lambda *a, **k: (_BlockingChunk(),
                                            engine.kv_pages)

        params = SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True)
        with pytest.raises(Exception) as err:
            async for _ in engine.generate([5, 6, 7], params):
                pass
        assert "wedged" in str(err.value).lower() or isinstance(
            err.value, EngineWedgedError)
        assert engine.wedged

        # liveness chain: model -> dataplane -> REST endpoint
        from kserve_tpu.model_repository import ModelRepository
        from kserve_tpu.protocol.dataplane import DataPlane
        from kserve_tpu.protocol.rest.v2_endpoints import V2Endpoints
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel.__new__(JAXGenerativeModel)
        model.name = "wedgy"
        model.ready = True
        model.engine = engine
        repo = ModelRepository()
        repo.update(model)
        dataplane = DataPlane(repo)
        assert (await dataplane.live())["status"] == "wedged"
        endpoints = V2Endpoints(dataplane, None)
        resp = await endpoints.live(None)
        assert resp.status == 503
        await engine.stop()

    @async_test
    async def test_healthy_engine_is_live(self):
        engine = make_engine(step_deadline_s=30.0)
        await engine.start()
        params = SamplingParams(max_tokens=2, temperature=0.0,
                                ignore_eos=True)
        outs = []
        async for out in engine.generate([5, 6, 7], params):
            outs.append(out)
        assert outs and not engine.wedged

        from kserve_tpu.model_repository import ModelRepository
        from kserve_tpu.protocol.dataplane import DataPlane
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel.__new__(JAXGenerativeModel)
        model.name = "fine"
        model.ready = True
        model.engine = engine
        repo = ModelRepository()
        repo.update(model)
        dataplane = DataPlane(repo)
        assert (await dataplane.live())["status"] == "alive"
        await engine.stop()


class TestDataParallelWedge:
    def test_dp_engine_aggregates_wedged(self):
        """dp>1 serves through DataParallelEngine — its liveness must
        aggregate replica wedge state (a missing property would 500 every
        probe and restart-loop a healthy pod)."""
        from kserve_tpu.engine.dp import DataParallelEngine, build_engine
        from kserve_tpu.engine.tokenizer import ByteTokenizer

        from test_dp_engine import make_config, model_config

        engine = build_engine(model_config(), make_config(dp=2),
                              ByteTokenizer(512))
        assert isinstance(engine, DataParallelEngine)
        assert not engine.wedged
        engine.replicas[1]._wedged = True
        assert engine.wedged
