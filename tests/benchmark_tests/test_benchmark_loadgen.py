"""The load generator against a stand-in server: what it stamps, and what
it calls failed when it stops."""

import asyncio
import json
import time

from aiohttp import web
from bench_paths import BENCH  # noqa: F401

from kbench import loadgen, stats
from kbench.schedule import Request

STALL = 7  # a prompt that starts with this id gets no answer


async def _completions(request):
    body = await request.json()
    resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
    await resp.prepare(request)
    if body["prompt"][0] == STALL:
        await asyncio.sleep(2.0)
    for i in range(body["max_tokens"]):
        last = i == body["max_tokens"] - 1
        chunk = {"choices": [{"text": f" {i}",
                              "finish_reason": "length" if last else None}]}
        await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
        await asyncio.sleep(0.01)
    await resp.write(b"data: [DONE]\n\n")
    return resp


def _serve(drive):
    """Run `drive(base_url)` against the stand-in server."""
    async def go():
        app = web.Application()
        app.router.add_post(loadgen.COMPLETIONS, _completions)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            return await drive(f"http://127.0.0.1:{port}")
        finally:
            await runner.cleanup()

    return asyncio.run(go())


def _drive(requests, drain_s):
    return _serve(lambda url: loadgen.run_open_loop(
        url, "m", requests, {"temperature": 0.0},
        time.perf_counter() + 0.1, drain_s, 5.0))


def _request(index, due, first_id, output_len, phase="window"):
    return Request(index=index, due_s=due, prompt_len=2, output_len=output_len,
                   prompt=[first_id, 1], phase=phase)


def test_open_loop_fails_a_window_request_left_without_a_token():
    requests = [
        _request(0, 0.0, 1, 4),  # answered in full
        _request(1, 0.05, STALL, 4),  # no token by the deadline
        _request(2, 0.1, 1, 400),  # still streaming at the deadline
        _request(3, 0.2, STALL, 4, phase="cooldown"),  # not judged
    ]
    records = _drive(requests, drain_s=0.5)
    answered, starved, streaming, cooldown = records
    assert answered.done and len(answered.token_times) == 4
    assert answered.token_ids == [0, 1, 2, 3]
    assert starved.error and "no first token" in starved.error
    assert not streaming.done and streaming.error is None and streaming.token_times
    assert cooldown.error is None  # outside the window: neither judged nor failed
    tried = stats.attempted(records, 0.15)
    assert len(tried) == 3
    assert [stats.is_failed(r) for r in tried] == [False, True, False]
    assert len(stats.ttfts_ms(records, 0.15)) == 2


def test_closed_loop_carries_on_for_a_traced_runs_tail():
    """`tail_s` keeps the clients sending past the window (a traced run's
    capture follows it); what starts there is not `attempted`, and what
    arrives there is not in the window's tokens."""
    window = 0.3

    def run(tail_s):
        per_client = [[Request(index=c, due_s=None, prompt_len=2, output_len=4,
                               prompt=[8, c], client=c)] for c in range(2)]
        return _serve(lambda url: loadgen.run_closed_loop(
            url, "m", per_client, {"temperature": 0.0},
            time.perf_counter() + 0.05, window, 5.0, vocab=1000, tail_s=tail_s))

    plain, traced = run(0.0), run(0.4)
    assert max(r.sent_s for r in plain) < window
    assert max(r.sent_s for r in traced) >= window + 0.2
    late = [r for r in traced if r.sent_s >= window]
    assert late and not set(map(id, late)) & set(
        map(id, stats.attempted(traced, window)))
    assert any(t >= window for r in traced for t in r.token_times)
    assert stats.tokens_in_window(traced, window) == sum(
        sum(0.0 <= t < window for t in r.token_times) for r in traced)
