"""The load generator: one process, one thread, asyncio over aiohttp.

It drives `/openai/v1/completions` with `stream: true` and stamps every SSE
chunk that carries a token with its own clock.  Open loop: each request is
sent at its due time whether or not earlier ones have finished.  Closed
loop: each client sends its next request as its last one ends.
"""

import asyncio
import itertools
import json
import time
from typing import List, Optional

import aiohttp

from .stats import Record

COMPLETIONS = "/openai/v1/completions"


def body_for(model: str, request, sampling: dict) -> dict:
    body = {
        "model": model,
        "prompt": request.prompt,
        "max_tokens": request.output_len,
        "temperature": sampling.get("temperature", 0.0),
        "ignore_eos": True,
        "stream": True,
    }
    if "top_p" in sampling:
        body["top_p"] = sampling["top_p"]
    if sampling.get("seeded"):
        body["seed"] = request.sampling_seed
    return body


def parse_token_id(text: str) -> Optional[int]:
    """The synthetic tokenizer spells token i as the decimal string of i."""
    text = text.strip()
    return int(text) if text.isdigit() else None


async def stream_one(session: aiohttp.ClientSession, base_url: str, body: dict,
                     record: Record, t_open: float, timeout_s: float) -> Record:
    """Send one request and stamp its tokens.  `t_open` is the
    perf_counter reading at which the window opens."""
    record.sent_s = time.perf_counter() - t_open
    try:
        async with session.post(
                base_url + COMPLETIONS, json=body,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            if resp.status != 200:
                record.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return record
            async for raw in resp.content:
                now = time.perf_counter() - t_open
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    record.done = True
                    break
                chunk = json.loads(data)
                if "error" in chunk:
                    record.error = str(chunk["error"])[:200]
                    break
                for choice in chunk.get("choices") or ():
                    record.token_times.append(now)
                    record.token_ids.append(parse_token_id(choice.get("text") or ""))
                    if choice.get("finish_reason"):
                        record.finish_reason = choice["finish_reason"]
    except asyncio.CancelledError:
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        record.error = f"{type(e).__name__}: {e}"[:200]
    return record


def _record(request, phase: str) -> Record:
    return Record(index=request.index, phase=phase,
                  prompt_len=request.prompt_len, output_len=request.output_len,
                  due_s=request.due_s, client=request.client)


async def _cancel(tasks) -> None:
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def _until_done_or_abort(work, abort: asyncio.Event, deadline: float):
    """Wait for `work`, for the window hook's abort (the ramp was disturbed:
    the run starts over), or for the deadline, whichever comes first."""
    aborted = asyncio.ensure_future(abort.wait())
    await asyncio.wait(
        [work, aborted], timeout=max(0.0, deadline - time.perf_counter()),
        return_when=asyncio.FIRST_COMPLETED)
    aborted.cancel()


async def run_open_loop(base_url: str, model: str, requests, sampling: dict,
                        t_open: float, drain_s: float, timeout_s: float,
                        on_window=None) -> List[Record]:
    """Send every request at its due time.  Returns when every request due
    in the window has ended (or `drain_s` after the last due time); what
    is still open then is cancelled.  A request of the window with no token
    by then has FAILED (its record says so); one that was streaming stays
    unfinished in its record."""
    records = [_record(r, r.phase) for r in requests]
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=connector) as session:

        async def one(request, record):
            delay = t_open + request.due_s - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            await stream_one(session, base_url,
                             body_for(model, request, sampling), record,
                             t_open, timeout_s)

        tasks = [asyncio.ensure_future(one(q, r))
                 for q, r in zip(requests, records)]
        abort = asyncio.Event()
        side = None
        if on_window is not None:
            side = asyncio.ensure_future(on_window(abort))
        judged = asyncio.ensure_future(asyncio.wait(
            [t for t, r in zip(tasks, records) if r.phase == "window"]))
        last_due = max(r.due_s for r in requests)
        deadline = t_open + last_due + drain_s
        await _until_done_or_abort(judged, abort, deadline)
        await _cancel([t for t in tasks if not t.done()] + [judged])
        stopped = time.perf_counter() - t_open
        for r in records:
            # a stream cut off while its tokens arrive is judged on what it
            # got; one that had nothing yet got no answer: it has failed,
            # or the judged TTFT would be a mean over the survivors
            if (r.phase == "window" and not r.token_times
                    and r.error is None and not abort.is_set()):
                r.error = (f"no first token {stopped - r.due_s:.1f} s after "
                           "it was due, when the generator stopped")
        if side is not None:
            await side
    return records


async def run_closed_loop(base_url: str, model: str, per_client, sampling: dict,
                          t_open: float, seconds: float, timeout_s: float,
                          on_window=None, vocab: int = 0, head=(),
                          tail_s: float = 0.0) -> List[Record]:
    """Each client sends its requests one after another until the window
    has closed (and `tail_s` longer: a traced run's capture follows the
    window); what is in flight then is cancelled.  A client that runs
    out of requests starts its list again with every token id shifted by
    one, so that a repeat shares no prefix with the first pass.  `head`
    (the mix's ramp primer) is sent first, 50 ms ahead of the clients."""
    records: List[Record] = []
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=connector) as session:

        async def client(mine):
            for lap in itertools.count():
                for request in mine:
                    if time.perf_counter() - t_open >= seconds + tail_s:
                        return
                    record = _record(request, "closed")
                    records.append(record)
                    body = body_for(model, request, sampling)
                    if lap:
                        body["prompt"] = [(t + lap) % vocab for t in request.prompt]
                    await stream_one(session, base_url, body, record,
                                     t_open, timeout_s)

        tasks = []
        for request in head:
            record = _record(request, "ramp")
            record.due_s = None
            records.append(record)
            tasks.append(asyncio.ensure_future(stream_one(
                session, base_url, body_for(model, request, sampling), record,
                t_open, timeout_s)))
        if head:
            await asyncio.sleep(0.05)
        tasks += [asyncio.ensure_future(client(mine)) for mine in per_client]
        abort = asyncio.Event()
        side = None
        if on_window is not None:
            side = asyncio.ensure_future(on_window(abort))
        clients_done = asyncio.ensure_future(asyncio.wait(tasks))
        await _until_done_or_abort(
            clients_done, abort, t_open + seconds + tail_s)
        await _cancel([t for t in tasks if not t.done()] + [clients_done])
        if side is not None:
            await side
    return records
