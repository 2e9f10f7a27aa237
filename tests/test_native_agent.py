"""C++ agent sidecar tests: batching + passthrough against a stub backend
(subprocess-built binary; skipped when no g++)."""

import asyncio
import json
import os
import shutil
import socket
import subprocess
import time
from pathlib import Path

import httpx
import pytest
from aiohttp import web

from conftest import async_test

AGENT_DIR = Path(__file__).resolve().parent.parent / "native" / "agent"
AGENT_BIN = AGENT_DIR / "kserve-tpu-agent"


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _build_agent(bin_path, make_target=None):
    """Build (or reuse) an agent binary; one staleness/skip/make path for
    every fixture."""
    src = AGENT_DIR / "agent.cpp"
    stale = (
        not bin_path.exists()
        or src.stat().st_mtime > bin_path.stat().st_mtime
    )
    if stale:
        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain")
        cmd = ["make", "-C", str(AGENT_DIR)]
        if make_target:
            cmd.append(make_target)
        subprocess.run(cmd, check=True)
    return str(bin_path)


@pytest.fixture(scope="module")
def agent_binary():
    return _build_agent(AGENT_BIN)


class _Backend:
    """Stub model server counting predict calls."""

    def __init__(self):
        self.calls = []

    async def predict(self, request: web.Request):
        body = await request.json()
        self.calls.append(len(body["instances"]))
        return web.json_response(
            {"predictions": [sum(row) for row in body["instances"]]}
        )

    async def models(self, request):
        return web.json_response({"models": ["stub"]})

    def app(self):
        app = web.Application()
        app.router.add_post("/v1/models/stub:predict", self.predict)
        app.router.add_get("/v1/models", self.models)
        return app


@async_test
async def test_agent_batches_and_splits(agent_binary):
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", backend_port)
    await site.start()
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port), "--component_port", str(backend_port),
         "--enable-batcher", "--max-batchsize", "3", "--max-latency", "2000"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        async with httpx.AsyncClient() as client:
            health = await client.get(f"http://127.0.0.1:{agent_port}/healthz")
            assert health.status_code == 200

            # passthrough GET
            models = await client.get(f"http://127.0.0.1:{agent_port}/v1/models")
            assert models.json() == {"models": ["stub"]}

            # three concurrent single-instance predicts -> one backend call
            async def one(row):
                r = await client.post(
                    f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                    json={"instances": [row]},
                    timeout=10,
                )
                return r.json()

            results = await asyncio.gather(one([1, 2]), one([3, 4]), one([10, 20]))
        assert [r["predictions"] for r in results] == [[3], [7], [30]]
        assert backend.calls == [3]  # coalesced into a single backend call
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_agent_latency_flush(agent_binary):
    """A partial batch flushes after max-latency even without filling up."""
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port), "--component_port", str(backend_port),
         "--enable-batcher", "--max-batchsize", "100", "--max-latency", "100"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        start = time.perf_counter()
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                json={"instances": [[5, 5]]},
                timeout=10,
            )
        elapsed = time.perf_counter() - start
        assert r.json()["predictions"] == [10]
        assert elapsed < 2.0  # flushed by the 100ms timer, not stuck
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_file_sink_jsonl_batching(agent_binary, tmp_path):
    """Blob-store sink: events batch into json-lines files under file://dir
    (reference pkg/logger/store.go + marshaller_json.go roles)."""
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    log_dir = tmp_path / "payloads"
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port), "--component_port", str(backend_port),
         "--enable-logger", "--log-url", f"file://{log_dir}",
         "--log-batch-size", "4", "--log-flush-interval", "200"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        async with httpx.AsyncClient() as client:
            for i in range(2):  # 2 predicts -> 4 events (request+response)
                r = await client.post(
                    f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                    json={"instances": [[i, i]]}, timeout=10,
                )
                assert r.status_code == 200
        deadline = time.time() + 5
        files = []
        while time.time() < deadline:
            files = sorted(log_dir.glob("payloads-*.jsonl"))
            if files:
                break
            await asyncio.sleep(0.1)
        assert files, "no batch file written"
        events = [json.loads(line) for line in files[0].read_text().splitlines()]
        assert len(events) == 4
        types = {e["type"] for e in events}
        assert types == {
            "org.kubeflow.serving.inference.request",
            "org.kubeflow.serving.inference.response",
        }
        assert events[0]["data"]["instances"] == [[0, 0]]
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_sigterm_flushes_buffered_batch(agent_binary, tmp_path):
    """Graceful shutdown drains the logger: a partial batch (below
    --log-batch-size, size-only strategy so no timer flush) must be
    written on SIGTERM, not dropped (ADVICE r4: the detached worker
    discarded it and could race static destruction)."""
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    log_dir = tmp_path / "payloads"
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port), "--component_port", str(backend_port),
         "--enable-logger", "--log-url", f"file://{log_dir}",
         "--log-batch-size", "100", "--log-batch-strategy", "size"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                json={"instances": [[7, 7]]}, timeout=10,
            )
            assert r.status_code == 200
        # the 2 events sit buffered (batch of 100 never fills); SIGTERM
        # must flush them on the way out
        assert not list(log_dir.glob("payloads-*")), "batch flushed early?"
        proc.terminate()
        assert proc.wait(timeout=5) == 0
        files = sorted(log_dir.glob("payloads-*.jsonl"))
        assert files, "buffered batch dropped on SIGTERM"
        events = [json.loads(line) for line in files[0].read_text().splitlines()]
        assert len(events) == 2
        assert events[0]["data"]["instances"] == [[7, 7]]
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_file_sink_csv_marshaller(agent_binary, tmp_path):
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    log_dir = tmp_path / "csv"
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port), "--component_port", str(backend_port),
         "--enable-logger", "--log-url", f"file://{log_dir}",
         "--log-format", "csv", "--log-batch-size", "2",
         "--log-flush-interval", "200"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                json={"instances": [[5, 6]]}, timeout=10,
            )
            assert r.status_code == 200
        deadline = time.time() + 5
        files, lines = [], []
        while time.time() < deadline:
            # the file exists before the batch is written into it
            files = sorted(log_dir.glob("payloads-*.csv"))
            lines = files[0].read_text().splitlines() if files else []
            if len(lines) >= 3:
                break
            await asyncio.sleep(0.1)
        assert files
        assert lines[0] == "id,type,path,payload"
        assert len(lines) == 3  # header + request + response
        assert "request" in lines[1] and "[[5,6]]" in lines[1].replace('""', '"').replace(" ", "")
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_sse_stream_passes_through_live(agent_binary):
    """VERDICT round-3 weak #5: the OpenAI streaming path must survive the
    injected sidecar.  The backend emits SSE events with delays; the proxy
    must relay them AS THEY ARRIVE (first event observed well before the
    stream finishes), byte-identical."""
    backend_port = free_port()
    agent_port = free_port()

    async def stream(request):
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"}
        )
        await resp.prepare(request)
        for i in range(3):
            await resp.write(f"data: {{\"n\": {i}}}\n\n".encode())
            await asyncio.sleep(0.25)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/openai/v1/chat/completions", stream)
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port),
         "--component_port", str(backend_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        chunks = []
        t0 = time.perf_counter()
        first_at = None
        async with httpx.AsyncClient() as client:
            async with client.stream(
                "POST",
                f"http://127.0.0.1:{agent_port}/openai/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "stream": True},
                timeout=15,
            ) as resp:
                assert resp.status_code == 200
                assert resp.headers["content-type"] == "text/event-stream"
                async for chunk in resp.aiter_bytes():
                    if first_at is None:
                        first_at = time.perf_counter() - t0
                    chunks.append(chunk)
        total = time.perf_counter() - t0
        text = b"".join(chunks).decode()
        assert text.count("data:") == 4 and "[DONE]" in text
        # live relay: the first event arrived long before the stream ended
        assert first_at is not None and first_at < total - 0.4, (
            f"first chunk at {first_at:.2f}s of {total:.2f}s — buffered?"
        )
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_chunked_request_body_accepted(agent_binary):
    """Chunked REQUESTS (no Content-Length) de-chunk at the agent and
    re-frame upstream."""
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port),
         "--component_port", str(backend_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)

        async def gen():
            yield b'{"instances": '
            await asyncio.sleep(0.05)
            yield b"[[2, 3]]}"

        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                content=gen(),  # httpx sends Transfer-Encoding: chunked
                headers={"Content-Type": "application/json"},
                timeout=10,
            )
        assert r.status_code == 200
        assert r.json()["predictions"] == [5]
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_parquet_marshaller_roundtrip(agent_binary, tmp_path):
    """VERDICT round-3 #9: parquet files written by the sidecar round-trip
    through a real parquet reader (pyarrow)."""
    pq = pytest.importorskip("pyarrow.parquet")
    backend = _Backend()
    backend_port, agent_port = free_port(), free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    log_dir = tmp_path / "pq"
    proc = subprocess.Popen(
        [agent_binary, "--port", str(agent_port),
         "--component_port", str(backend_port),
         "--enable-logger", "--log-url", f"file://{log_dir}",
         "--log-format", "parquet", "--log-batch-size", "2",
         "--log-flush-interval", "200"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        await asyncio.sleep(0.3)
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                json={"instances": [[7, 8]]}, timeout=10,
            )
            assert r.status_code == 200
        deadline = time.time() + 5
        files = []
        while time.time() < deadline and not files:
            files = sorted(log_dir.glob("payloads-*.parquet"))
            await asyncio.sleep(0.1)
        assert files
        table = pq.read_table(files[0]).to_pydict()
        assert table["type"] == ["request", "response"]
        assert table["id"] == [0, 1]
        assert json.loads(table["payload"][0]) == {"instances": [[7, 8]]}
        assert json.loads(table["payload"][1]) == {"predictions": [15]}
    finally:
        proc.terminate()
        await runner.cleanup()


@async_test
async def test_batch_strategies(agent_binary, tmp_path):
    """immediate: one file per event.  size: no flush until the batch
    fills, even after the interval."""
    backend = _Backend()
    backend_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()

    async def drive(strategy, batch_size, n_requests):
        agent_port = free_port()
        log_dir = tmp_path / strategy
        proc = subprocess.Popen(
            [agent_binary, "--port", str(agent_port),
             "--component_port", str(backend_port),
             "--enable-logger", "--log-url", f"file://{log_dir}",
             "--log-mode", "request",
             "--log-batch-strategy", strategy,
             "--log-batch-size", str(batch_size),
             "--log-flush-interval", "150"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        try:
            await asyncio.sleep(0.3)
            async with httpx.AsyncClient() as client:
                for _ in range(n_requests):
                    await client.post(
                        f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                        json={"instances": [[1, 1]]}, timeout=10,
                    )
            await asyncio.sleep(0.8)
            return sorted(log_dir.glob("payloads-*.jsonl"))
        finally:
            proc.terminate()

    # immediate: 3 requests -> 3 files of 1 event each
    files = await drive("immediate", 16, 3)
    assert len(files) == 3
    # size-only with batch 4: 3 requests never fill a batch -> NO file even
    # after several flush intervals
    files = await drive("size", 4, 3)
    assert files == []
    # timed: a partial batch flushes on the interval
    files = await drive("timed", 100, 2)
    assert len(files) >= 1
    await runner.cleanup()


@pytest.fixture
def agent_binary_tsan():
    """ThreadSanitizer build (SURVEY §5 race-detection row)."""
    return _build_agent(AGENT_DIR / "kserve-tpu-agent-tsan",
                        "kserve-tpu-agent-tsan")


@pytest.mark.slow
@async_test
async def test_tsan_concurrent_load_and_shutdown(agent_binary_tsan, tmp_path):
    """Drive the TSAN build with concurrent batched traffic while the
    logger buffers, then SIGTERM mid-flight: any data race between the
    connection threads, batcher, logger worker, and the shutdown path
    makes ThreadSanitizer report and exit non-zero (TSAN_OPTIONS
    exitcode)."""
    backend = _Backend()
    backend_port = free_port()
    agent_port = free_port()
    runner = web.AppRunner(backend.app())
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", backend_port).start()
    log_dir = tmp_path / "payloads"
    out_path = tmp_path / "tsan-out.txt"
    # opening the subprocess's output sink before spawn; one-shot test setup
    out_file = open(out_path, "wb")  # jaxlint: disable=blocking-async
    proc = subprocess.Popen(
        [agent_binary_tsan, "--port", str(agent_port),
         "--component_port", str(backend_port),
         "--enable-batcher", "--max-batchsize", "4", "--max-latency", "20",
         "--enable-logger", "--log-url", f"file://{log_dir}",
         "--log-batch-size", "8", "--log-flush-interval", "50"],
        # a file, not a PIPE: a sanitizer report storm past the pipe
        # buffer would block agent threads mid-write and mask the race
        # behind a wait() timeout
        stdout=out_file, stderr=subprocess.STDOUT,
        env={**os.environ, "TSAN_OPTIONS": "exitcode=66 halt_on_error=0"},
    )
    try:
        await asyncio.sleep(0.6)  # tsan startup is slower
        async with httpx.AsyncClient() as client:
            async def one(i):
                r = await client.post(
                    f"http://127.0.0.1:{agent_port}/v1/models/stub:predict",
                    json={"instances": [[i]]}, timeout=30,
                )
                assert r.status_code == 200
            # concurrent fan-in exercises batcher cv + logger queue
            for _ in range(4):
                await asyncio.gather(*[one(i) for i in range(16)])
        proc.terminate()  # drain+join under tsan
        rc = proc.wait(timeout=20)
        out = out_path.read_text(errors="replace")
        assert "WARNING: ThreadSanitizer" not in out, out[-4000:]
        assert rc == 0, f"rc={rc}\n{out[-4000:]}"
    finally:
        if proc.poll() is None:
            proc.kill()
        out_file.close()
        await runner.cleanup()
