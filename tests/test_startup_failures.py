"""Start-up must fail loudly: a program the backend refuses to compile, or a
platform that is not there, ends the process — it does not leave a live,
never-ready server, a hung `engine.start()`, or a replica quietly serving
from the CPU."""

import asyncio
import os
import signal
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import pytest

from kserve_tpu.engine.sampling import SamplingParams

from conftest import HAS_TPU, async_test
from test_engine import make_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CompileRefused(RuntimeError):
    pass


def _refuse(*_args, **_kwargs):
    raise CompileRefused("INVALID_ARGUMENT: No such compile option")


class TestEngineStartFailure:
    @async_test
    async def test_start_raises_when_a_program_does_not_compile(self):
        """Warm-up drives the real loop; the refused compile kills the loop,
        and start() must surface that instead of parking the NEXT warm-up
        bucket on a loop that is gone (the tier-1 hang on jax 0.9.0)."""
        engine = make_engine(aot_warmup=True)
        engine._mixed_fn = _refuse
        with pytest.raises(CompileRefused):
            await asyncio.wait_for(engine.start(), timeout=60)
        assert not engine.running
        await engine.stop()

    @async_test
    async def test_dead_loop_refuses_new_work(self):
        """After the loop dies on a first dispatch, a new request must be
        refused at submit — queued, it would wait forever."""
        engine = make_engine()
        await engine.start()
        crashed = []
        engine.on_loop_crash = crashed.append
        engine._mixed_fn = _refuse
        params = SamplingParams(max_tokens=4, temperature=0.0)
        with pytest.raises(CompileRefused):
            async for _ in engine.generate([1, 2, 3], params):
                pass
        assert len(crashed) == 1 and isinstance(crashed[0], CompileRefused)
        with pytest.raises(RuntimeError, match="engine loop crashed"):
            engine.generate([1, 2, 3], params)
        await engine.stop()


class _Alarm:
    """Fail the test instead of hanging the suite if main() never returns."""

    def __init__(self, seconds: int):
        self.seconds = seconds

    def __enter__(self):
        def on_alarm(_sig, _frame):
            raise TimeoutError("generative_server.main did not exit")

        self._old = signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(self.seconds)

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _server_argv(port: int) -> list:
    return [
        "--model_config=tiny", "--random_weights", "--model_name=m",
        f"--http_port={port}", "--enable_grpc=false", "--max_batch_size=4",
        "--kv_pages=64", "--page_size=8", "--max_model_len=64",
        "--max_prefill_len=32", "--kv_dtype=float32",
    ]


@pytest.fixture
def refused_compiles(monkeypatch):
    """Every engine built while this is active gets a `mixed` program that
    the 'backend' refuses to compile."""
    import dataclasses

    from kserve_tpu.engine import compiled

    real = compiled.build_compiled

    def build(*args, **kwargs):
        return dataclasses.replace(real(*args, **kwargs), mixed=_refuse)

    monkeypatch.setattr(compiled, "build_compiled", build)


class TestServerExitsNonZero:
    def test_failed_engine_start_ends_main(self, refused_compiles,
                                           monkeypatch, tmp_path):
        """Warm-up on (an AOT cache dir is set): the engine never starts,
        and main() must raise — exit code 1 — not idle never-ready."""
        from kserve_tpu.runtimes import generative_server

        monkeypatch.setenv("KSERVE_TPU_AOT_CACHE", str(tmp_path))
        with _Alarm(120), pytest.raises(RuntimeError, match="engine failed"):
            generative_server.main(_server_argv(_free_port()))

    def test_first_dispatch_failure_ends_main(self, refused_compiles):
        """No warm-up: the server turns ready, the first request hits the
        refused compile, the loop dies — and the process must end."""
        from kserve_tpu.runtimes import generative_server

        port = _free_port()
        statuses = []

        pause = threading.Event()

        def client():
            url = f"http://127.0.0.1:{port}"
            for _ in range(200):
                try:
                    urllib.request.urlopen(
                        f"{url}/v2/models/m/ready", timeout=2).close()
                    break
                except (urllib.error.URLError, OSError):
                    pause.wait(0.1)  # not listening yet
            req = urllib.request.Request(
                f"{url}/openai/v1/completions",
                data=b'{"model": "m", "prompt": [5, 6, 7], "max_tokens": 4}',
                headers={"content-type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    statuses.append(r.status)
            except urllib.error.HTTPError as e:
                statuses.append(e.code)
            except (urllib.error.URLError, OSError) as e:
                statuses.append(repr(e))

        thread = threading.Thread(target=client, daemon=True)
        thread.start()
        with _Alarm(120), pytest.raises(RuntimeError, match="engine failed"):
            generative_server.main(_server_argv(port))
        thread.join(timeout=30)
        assert not thread.is_alive()
        assert statuses and statuses[0] != 200


class TestRequestedPlatformIsHonoured:
    @pytest.mark.skipif(HAS_TPU, reason="this host has a TPU")
    def test_unavailable_platform_raises_instead_of_serving_from_cpu(self):
        proc = subprocess.run(
            [sys.executable, "-c",
             "from kserve_tpu.utils.backend import apply_platform_override\n"
             "apply_platform_override()\n"
             "import jax; print('SERVING ON', jax.default_backend())"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="tpu", PYTHONPATH=REPO))
        assert proc.returncode != 0
        assert "SERVING ON" not in proc.stdout
        assert "Unable to initialize backend 'tpu'" in proc.stderr
