"""Request-lifecycle timelines: the engine's per-request telemetry spine.

Every generation is stamped with a `RequestTimeline` as it moves through
the engine (received → admitted → prefill start/end → first token →
per-token → finished/checkpointed).  All stamps come from an injectable
`resilience.Clock`, so the FakeClock chaos suite can assert exact TTFT /
inter-token / queue-wait values without a single real sleep.

The `TimelineRecorder` keeps a bounded ring of finished timelines plus
rolling sample windows (TTFT, ITL, queue wait, e2e, decode-step and
prefill-chunk durations) that back `GET /admin/telemetry` — engine step
introspection without a Prometheus scrape in the loop.

`DispatchPhases` stamps the engine's loop the same way: every dispatch gets
a serial number and six consecutive phases (admit, plan, launch, wait,
route, yield) that tile one iteration of the loop, from the same clock.
One stamp feeds three outputs: a `TraceAnnotation` on the profiler's clock
while a capture runs, the `engine_dispatch_phase_seconds_total` counters,
and one row per dispatch in the recorder's bounded ring.  What a phase is
made of is timed the same way, as PARTS (`span`): handing tokens to their
streams, for one, is no phase: it runs inside one (`wait` where it follows
the launch, `route` where it is done in place) and is the part `deliver`.
A second clock gives the CPU seconds of the loop's thread by phase, and
what stops the whole process (a compile of something that is none of the
engine's programs, a pause of the collector: observability/pauses.py) is
noted on the row of the iteration it fell in (`paused`).

Derived metrics follow the serving-benchmark vocabulary of the vLLM/TGI
comparative study (PAPERS.md, arXiv:2511.17593): TTFT is first token
minus *received* (queue wait included — the client experiences it), ITL
is the gap between consecutive emitted tokens.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

# bounded per-timeline storage: events and ITL samples never grow past
# these caps even for max_model_len generations (overflow keeps aggregate
# count/sum so means stay exact)
MAX_EVENTS = 64
MAX_ITL_SAMPLES = 4096
# one row per dispatch; a 51 s benchmark window holds 80-135 of them
MAX_DISPATCHES = 512

#: the phases that tile one iteration of the engine's loop, in order
PHASES = ("admit", "plan", "launch", "wait", "route", "yield")
#: what the host's phases are made of, each timed by `DispatchPhases.span`
#: inside the phase under way: `prepare`, `sampling`, `pack` make up `plan`
#: (what precedes the packing: the legacy gate, growth, preemption, the
#: lanes' arrays, the gauges; every rebuild of the sampling
#: state; packing the ragged step), `upload`, `call`, `account` make up
#: `launch` (the plan's arrays to the device; the jitted call up to its
#: return; what the counting itself costs), `deliver` is the handing over
#: of deferred tokens (inside `wait`, or in place), `register` the finishes
#: with their page frees and the prefix cache's registration inside `route`
PARTS = ("prepare", "sampling", "pack", "upload", "call", "account",
         "deliver", "register")
#: what stops the whole process and not the loop alone, noted on the row of
#: the iteration it fell in: seconds of XLA compiles of anything but the
#: engine's own programs, and of the collector's pauses (generations 1, 2)
PAUSES = ("other_compile", "gc")
#: when a token is handed to its stream: `overlapped` with the dispatch
#: launched after the one that produced it, while that one runs, or `inline`,
#: between its own dispatch's fetch and the next launch
DELIVERIES = ("overlapped", "inline")
#: how a layer's K/V write runs (ops/attention.kv_write_path): a row's
#: `kv_<path>` is the layer-steps its launches wrote that way
KV_WRITE_PATHS = ("page_kernel", "row_scatter")
#: the columns of a dispatch row: flat, so a snapshot of the whole ring
#: serialises in about a millisecond
#: (`tokens`, `width` are the (T, W) pair the dispatch ran in, `need_*` the
#: pair it needed: they differ where it ran padded in a loaded pair;
#: `overlapped`, `inline` are the tokens this iteration handed to their
#: streams, by DELIVERIES, and `deliver` the seconds it spent handing over
#: those that had been deferred, inside whichever phase that was: the part
#: of that name; the other PARTS follow, then `cpu_<phase>`, the CPU seconds
#: of the loop's thread inside each of the iteration's PHASES, and the
#: PAUSES; then the K/V writes, by KV_WRITE_PATHS; last `uploads`, the
#: host-to-device transfers the iteration's launches made of their inputs:
#: three for a `mixed` dispatch)
_PARTS_APPENDED = tuple(part for part in PARTS if part != "deliver")
CPU_COLUMNS = tuple("cpu_" + phase for phase in PHASES)
DISPATCH_COLUMNS = (
    "serial", "launched_at", "program", "tokens", "width", "need_tokens",
    "need_width", "prefill_tokens", "decode_tokens", *PHASES, "wait_lag",
    "compiled", "chained", "deliver", *DELIVERIES, *_PARTS_APPENDED,
    *CPU_COLUMNS, *PAUSES, *("kv_" + path for path in KV_WRITE_PATHS),
    "uploads")


class RequestTimeline:
    """Clock-stamped lifecycle of one generation.  Times are whatever the
    engine's injected clock reports (monotonic seconds in production,
    virtual seconds under FakeClock); only differences are meaningful."""

    __slots__ = (
        "request_id", "model_name", "trace", "received", "admitted",
        "prefill_start", "prefill_end", "first_token_at", "finished_at",
        "finish_reason", "n_prompt_tokens", "n_generated", "itls",
        "itl_overflow_n", "itl_overflow_sum", "events", "_last_token_at",
        "recorded", "admit_dispatch", "first_token_dispatch",
    )

    def __init__(self, request_id: str, model_name: str = "",
                 trace: Any = None):
        self.request_id = request_id
        self.model_name = model_name
        # the tracing.TraceContext bound when the request entered (or None):
        # engine spans emitted from this timeline carry its trace_id so the
        # proxy → replica → engine spans form one linked trace
        self.trace = trace
        self.received: Optional[float] = None
        self.admitted: Optional[float] = None
        self.prefill_start: Optional[float] = None
        self.prefill_end: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.n_prompt_tokens = 0
        self.n_generated = 0
        self.itls: List[float] = []
        self.itl_overflow_n = 0
        self.itl_overflow_sum = 0.0
        self.events: List[dict] = []
        self._last_token_at: Optional[float] = None
        # set by the engine once this timeline has been fed to the
        # recorder/metrics — makes terminal recording idempotent across
        # overlapping teardown paths (finish vs cancel vs stop)
        self.recorded = False
        # serials of the dispatch that admitted the request and of the one
        # that gave its first token: TTFT = queue wait + (their difference
        # + 1) dispatch periods
        self.admit_dispatch: Optional[int] = None
        self.first_token_dispatch: Optional[int] = None

    # ---- stamps (first-write-wins where re-admission can re-stamp) ----

    def mark_received(self, t: float) -> None:
        if self.received is None:
            self.received = t

    def mark_admitted(self, t: float, dispatch: Optional[int] = None) -> None:
        # queue wait is measured to the FIRST admission; a preemption
        # re-seat must not shrink it retroactively
        if self.admitted is None:
            self.admitted = t
            self.admit_dispatch = dispatch

    def mark_prefill_start(self, t: float) -> None:
        if self.prefill_start is None:
            self.prefill_start = t

    def mark_prefill_end(self, t: float) -> None:
        self.prefill_end = t

    def mark_token(self, t: float, dispatch: Optional[int] = None) -> None:
        """One emitted token: the first sets TTFT, later ones append ITL."""
        self.n_generated += 1
        if self.first_token_at is None:
            self.first_token_at = t
            self.first_token_dispatch = dispatch
        elif self._last_token_at is not None:
            gap = t - self._last_token_at
            if len(self.itls) < MAX_ITL_SAMPLES:
                self.itls.append(gap)
            else:
                self.itl_overflow_n += 1
                self.itl_overflow_sum += gap
        self._last_token_at = t

    def mark_finished(self, t: float, reason: Optional[str]) -> None:
        if self.finished_at is None:
            self.finished_at = t
            self.finish_reason = reason

    def add_event(self, t: float, name: str, **detail) -> None:
        """Span-event seam: preemptions, checkpoints, resumes, errors."""
        if len(self.events) < MAX_EVENTS:
            self.events.append({"t": t, "name": name, **detail})

    # ---- derived latencies (None until both stamps exist) ----

    @staticmethod
    def _delta(a: Optional[float], b: Optional[float]) -> Optional[float]:
        if a is None or b is None:
            return None
        return b - a

    @property
    def queue_wait_s(self) -> Optional[float]:
        return self._delta(self.received, self.admitted)

    @property
    def ttft_s(self) -> Optional[float]:
        return self._delta(self.received, self.first_token_at)

    @property
    def prefill_s(self) -> Optional[float]:
        return self._delta(self.prefill_start, self.prefill_end)

    @property
    def decode_s(self) -> Optional[float]:
        return self._delta(self.first_token_at, self.finished_at)

    @property
    def e2e_s(self) -> Optional[float]:
        return self._delta(self.received, self.finished_at)

    @property
    def dispatches_to_first_token(self) -> Optional[int]:
        """Dispatches from admission to the first token, both included."""
        if self.admit_dispatch is None or self.first_token_dispatch is None:
            return None
        return self.first_token_dispatch - self.admit_dispatch + 1

    @property
    def mean_itl_s(self) -> Optional[float]:
        n = len(self.itls) + self.itl_overflow_n
        if n == 0:
            return None
        return (sum(self.itls) + self.itl_overflow_sum) / n

    def to_dict(self, max_events: int = 16) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "request_id": self.request_id,
            "model_name": self.model_name,
            "received": self.received,
            "admitted": self.admitted,
            "prefill_start": self.prefill_start,
            "prefill_end": self.prefill_end,
            "first_token_at": self.first_token_at,
            "finished_at": self.finished_at,
            "finish_reason": self.finish_reason,
            "n_prompt_tokens": self.n_prompt_tokens,
            "n_generated": self.n_generated,
            "queue_wait_s": self.queue_wait_s,
            "ttft_s": self.ttft_s,
            "prefill_s": self.prefill_s,
            "e2e_s": self.e2e_s,
            "mean_itl_s": self.mean_itl_s,
            "admit_dispatch": self.admit_dispatch,
            "first_token_dispatch": self.first_token_dispatch,
            "events": self.events[:max_events],
        }
        if self.trace is not None:
            d["trace_id"] = getattr(self.trace, "trace_id", None)
        return d


def percentiles(samples) -> Dict[str, Any]:
    """{p50,p90,p99,mean,max,n} by nearest-rank over a bounded window —
    deterministic (no interpolation) so chaos tests can assert exactly."""
    xs = sorted(samples)
    n = len(xs)
    if n == 0:
        return {"n": 0}

    def rank(q: float) -> float:
        return xs[min(n - 1, int(q * n))]

    return {
        "n": n,
        "p50": rank(0.50),
        "p90": rank(0.90),
        "p99": rank(0.99),
        "mean": sum(xs) / n,
        "max": xs[-1],
    }


class TimelineRecorder:
    """Bounded in-memory telemetry behind `GET /admin/telemetry`: a ring of
    recent finished timelines plus rolling sample windows for the latency
    series.  Pure host-side bookkeeping — never touches the device."""

    def __init__(self, max_timelines: int = 128, max_samples: int = 2048):
        self.timelines: deque = deque(maxlen=max_timelines)
        self._ttft: deque = deque(maxlen=max_samples)
        self._itl: deque = deque(maxlen=max_samples)
        self._queue_wait: deque = deque(maxlen=max_samples)
        self._e2e: deque = deque(maxlen=max_samples)
        self._step: deque = deque(maxlen=max_samples)
        self._prefill_chunk: deque = deque(maxlen=max_samples)
        self.dispatches: deque = deque(maxlen=MAX_DISPATCHES)
        self.finished_count = 0
        self.preempted_count = 0
        self.aborted_count = 0
        self.step_count = 0

    def observe(self, tl: RequestTimeline) -> None:
        """Record a timeline that reached a terminal state.  Preempted /
        cancelled / errored timelines land in the ring (operators debugging
        a drain want them) but not in the latency windows — a half
        generation's e2e is noise."""
        self.timelines.append(tl)
        if tl.finish_reason not in ("stop", "length"):
            if tl.finish_reason == "preempted":
                self.preempted_count += 1
            else:
                self.aborted_count += 1
            return
        self.finished_count += 1
        if tl.ttft_s is not None:
            self._ttft.append(tl.ttft_s)
        if tl.queue_wait_s is not None:
            self._queue_wait.append(tl.queue_wait_s)
        if tl.e2e_s is not None:
            self._e2e.append(tl.e2e_s)
        self._itl.extend(tl.itls)

    def signal_windows(self) -> Dict[str, Any]:
        """The autoscaling-relevant latency percentiles (the compact
        subset of snapshot() the EPP /state payload carries per replica —
        kserve_tpu/autoscale/signals.py ingests this shape)."""
        ttft = percentiles(self._ttft)
        itl = percentiles(self._itl)
        return {
            "ttft_p50_s": ttft.get("p50"),
            "ttft_p99_s": ttft.get("p99"),
            "itl_p99_s": itl.get("p99"),
            "finished": self.finished_count,
        }

    def record_step(self, seconds: float) -> None:
        """One decode step: a multi-token dispatch+fetch chunk."""
        self.step_count += 1
        self._step.append(seconds)

    def record_prefill_chunk(self, seconds: float) -> None:
        self._prefill_chunk.append(seconds)

    def record_dispatch(self, row: list) -> None:
        """One committed dispatch, as a flat row of DISPATCH_COLUMNS."""
        self.dispatches.append(row)

    def snapshot(self, max_recent: int = 32,
                 now: Optional[float] = None) -> Dict[str, Any]:
        # [-0:] would slice the WHOLE ring, the opposite of "none"
        recent = list(self.timelines)[-max_recent:] if max_recent > 0 else []
        return {
            "counts": {
                "finished": self.finished_count,
                "preempted": self.preempted_count,
                "aborted": self.aborted_count,
                "decode_steps": self.step_count,
            },
            "ttft_s": percentiles(self._ttft),
            "itl_s": percentiles(self._itl),
            "queue_wait_s": percentiles(self._queue_wait),
            "e2e_s": percentiles(self._e2e),
            "decode_step_s": percentiles(self._step),
            "prefill_chunk_s": percentiles(self._prefill_chunk),
            "recent": [tl.to_dict() for tl in reversed(recent)],
            # the engine clock's reading at the snapshot, so a reader can
            # cut the ring to "the last N seconds" on the ring's own clock
            "now": now,
            "dispatches": {"columns": list(DISPATCH_COLUMNS),
                           "rows": list(self.dispatches)},
        }


class _Part:
    """One timed part of a phase, for `with` (DispatchPhases.span)."""

    __slots__ = ("_phases", "_name", "_span", "_started", "_nested")

    def __init__(self, phases: "DispatchPhases", name: str):
        self._phases = phases
        self._name = name
        self._span = None
        self._nested = 0.0  # seconds of the parts opened inside this one

    def __enter__(self) -> "_Part":
        phases = self._phases
        if phases._annotate is not None:
            self._span = phases._annotate(
                "engine." + self._name, dispatch=phases.serial)
            self._span.__enter__()
        phases._open.append(self)
        self._started = phases._clock.now()
        return self

    def __exit__(self, *exc) -> None:
        phases = self._phases
        seconds = phases._clock.now() - self._started
        phases._open.pop()
        # never below zero, whatever the sums of nested readings round to
        phases._parts[self._name] += max(0.0, seconds - self._nested)
        if phases._open:
            phases._open[-1]._nested += seconds
        if self._span is not None:
            self._span.__exit__(*exc)


class DispatchPhases:
    """Stamps the phases of the engine's loop.  `mark(phase)` closes the
    phase under way at the clock's reading and opens the next; `launched`
    notes what was dispatched; `commit` closes the iteration and returns
    the row of DISPATCH_COLUMNS of the oldest dispatch not yet committed
    (the engine appends it to the recorder's ring and feeds its counters
    from it).  An iteration that dispatched nothing returns None and is
    dropped, so idle time is in no phase.  Where the dense path chains a
    dispatch on the one in flight, two launches are open at once: phases
    and parts go to the row committed next, as they occur.

    `span(part)` times what a phase is made of (PARTS): the seconds go to
    the iteration's row under the part's name, less those of a part opened
    inside it, so parts never count a second twice and never exceed their
    phase.

    `annotate` is `jax.profiler.TraceAnnotation` in the engine: each phase
    and part is then a host span `engine.<name>` on the profiler's clock
    while a capture runs, and one flag test when none does.  `cpu_clock`
    is `time.thread_time` there: the CPU seconds of the loop's thread are
    booked to the phase a `mark` closes (in `wait` they are the work the
    device's step hides; in `launch`, wall less CPU is how long the thread
    was blocked on the runtime); with none, they read 0."""

    def __init__(self, clock, annotate=None,
                 cpu_clock: Optional[Callable[[], float]] = None):
        self._clock = clock
        self._annotate = annotate
        self._cpu_clock = cpu_clock
        self.serial = 1  # of the oldest dispatch not yet committed
        self._span = None
        self._launches: deque = deque()
        self._open: List[_Part] = []
        self._reset(None)

    def _reset(self, now: Optional[float], cpu: float = 0.0) -> None:
        self._phase: Optional[str] = None
        self._since = now
        self._cpu_since = cpu
        self._seconds = dict.fromkeys(PHASES, 0.0)
        self._cpu = dict.fromkeys(PHASES, 0.0)
        self._parts = dict.fromkeys(PARTS, 0.0)
        self._uploads = 0
        self._pauses = dict.fromkeys(PAUSES, 0.0)
        self._wait_lag = 0.0
        self._handed = dict.fromkeys(DELIVERIES, 0)
        self._kv_writes = dict.fromkeys(KV_WRITE_PATHS, 0)

    def _close_phase(self) -> Tuple[float, float]:
        """Book the clocks' readings to the phase under way."""
        now = self._clock.now()
        cpu = self._cpu_clock() if self._cpu_clock is not None else 0.0
        if self._phase is not None:
            self._seconds[self._phase] += now - self._since
            self._cpu[self._phase] += cpu - self._cpu_since
        return now, cpu

    def mark(self, phase: str) -> float:
        now, cpu = self._close_phase()
        self._phase, self._since, self._cpu_since = phase, now, cpu
        if self._annotate is not None:
            self.close()
            self._span = self._annotate("engine." + phase,
                                        dispatch=self.serial)
            self._span.__enter__()
        return now

    def close(self) -> None:
        """End the host span under way (the loop is leaving)."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def launched(self, program: str, tokens: int, width: int,
                 prefill_tokens: int, decode_tokens: int,
                 compiled: bool = False, chained: bool = False,
                 need: Optional[Tuple[int, int]] = None) -> None:
        """What the `launch` phase under way dispatched.  `need` is the
        (T, W) pair the dispatch needed, where its planner may run it in
        another (the mixed step: engine/shapes.LoadedPairs)."""
        self._launches.append([
            self._since, program, tokens, width, *(need or (tokens, width)),
            prefill_tokens, decode_tokens, int(compiled), int(chained)])

    def span(self, part: str) -> _Part:
        """Times `part` (one of PARTS) of the phase under way, for `with`;
        also a host span `engine.<part>` nested in the phase's."""
        return _Part(self, part)

    def uploaded(self) -> None:
        """A launch's input went to the device: one transfer."""
        self._uploads += 1

    def delivered(self, when: str, tokens: int) -> None:
        """`tokens` were handed to their streams, `when` (one of
        DELIVERIES)."""
        self._handed[when] += tokens

    def wrote(self, path: str, layer_steps: int) -> None:
        """A launch's forward steps write `layer_steps` layers' K/V by
        `path` (one of KV_WRITE_PATHS)."""
        self._kv_writes[path] += layer_steps

    def paused(self, pause: str, seconds: float) -> None:
        """The process stood still for `seconds` (`pause`: one of PAUSES);
        outside an iteration that is nobody's row."""
        if self._phase is not None:
            self._pauses[pause] += seconds

    def resumed(self, ready_at: Optional[float]) -> None:
        """The loop took a fetched result up `now - ready_at` after the
        fetch worker had it on the host: opens `route`."""
        now = self.mark("route")
        if ready_at is not None:
            self._wait_lag += max(0.0, now - ready_at)

    def commit(self) -> Optional[list]:
        """Close the iteration.  The next one's `admit` opens at the same
        reading, so that consecutive iterations leave no time between them
        in no phase; a loop that goes idle calls `pause` instead."""
        now, cpu = self._close_phase()
        self.close()
        seconds, parts, cpus = self._seconds, self._parts, self._cpu
        wait_lag = min(self._wait_lag, seconds["wait"])
        handed, pauses, kv_writes = self._handed, self._pauses, self._kv_writes
        uploads = self._uploads
        self._reset(now, cpu)
        self._phase = "admit"
        if not self._launches:
            return None
        launched_at, *what, compiled, chained = self._launches.popleft()
        row = [self.serial, launched_at, *what,
               *(seconds[p] for p in PHASES), wait_lag, compiled, chained,
               parts["deliver"], *handed.values(),
               *(parts[p] for p in _PARTS_APPENDED),
               *cpus.values(), *pauses.values(), *kv_writes.values(), uploads]
        self.serial += 1
        return row

    def pause(self) -> None:
        """The loop waits for work: what follows is in no phase until the
        next `mark`."""
        self.close()
        self._reset(None)
