"""`--trace 2`: a `--trace 0` run up to the window's close, then a traced
tail.  Against the stand-in server (`standin.py`): what the harness sends in
each mode, what it prints, and that the untraced path is left as it was."""

import argparse
import json
import os

import pytest
from bench_paths import BENCH  # noqa: F401
from standin import StandIn, StandInServer, patch_harness, small_plan

import run as bench_run
from kbench import loadgen, manifest, server

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("qwen3-4b.chat", "qwen3-4b.decode-sat")
COMPLETION = ("POST", loadgen.COMPLETIONS)
SEED = 2**31 + 11


def measure(monkeypatch, tmp_path, capsys, cell, trace, trace_seconds=0.3):
    """One run of `cell` against the stand-in; returns (line, stand-in)."""
    monkeypatch.setattr(bench_run, "TRACE_SECONDS", trace_seconds)
    monkeypatch.setattr(bench_run, "TRACE_TAIL_S", trace_seconds + 0.2)
    monkeypatch.setattr(bench_run, "TRACED_MAX_S", 5.0)
    with StandIn() as standin:
        patch_harness(monkeypatch, bench_run, standin, str(tmp_path))
        args = argparse.Namespace(seed=SEED, seconds=1.0, trace=trace)
        code = bench_run.measure(args, small_plan(bench_run, cell), "tpu")
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line, standin


@pytest.mark.parametrize("cell", CELLS)
def test_trace_2_prints_both_kinds_of_metric_and_none_missing(
        monkeypatch, tmp_path, capsys, cell):
    line, standin = measure(monkeypatch, tmp_path, capsys, cell, 2)
    resolved = manifest.resolve_cell(cell)
    wanted = {m["name"] for m in resolved.end_to_end + resolved.per_layer}
    assert set(line["metrics"]) == wanted
    assert all(m["value"] is not None for m in line["metrics"].values())
    assert line["correct"] is True and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    assert line["breakdown"]["idle_gaps"][0][0] == "engine.yield"
    # a first start and stop, thrown away, then the capture itself
    discard = os.path.join(str(tmp_path), "profiles", cell + ".run.first")
    assert standin.profile_bodies == [
        {"action": "start", "dir": discard}, {"action": "stop"},
        {"seconds": 0.3, "dir": discard[:-len(".first")]}]
    assert not os.path.exists(discard)
    cost = line["detail"]["timings"]["traced_phase"]
    assert cost["first_start_stop_s"] < cost["ramp_s"]  # inside the ramp
    assert cost["telemetry_answered_s"] < 1.0 and cost["stop_s"] < 1.0
    # the margin every traced line on record shows: the wait's limit
    assert cost["stop_limit_s"] > bench_run.CAPTURE_WAIT_S[2]
    assert "capture_ended_at" not in cost
    assert cost["period_under_capture_ms"] == pytest.approx(50.0, rel=0.02)


@pytest.mark.parametrize("cell", CELLS)
def test_trace_0_and_1_print_what_they_printed(monkeypatch, tmp_path, capsys, cell):
    resolved = manifest.resolve_cell(cell)
    line, standin = measure(monkeypatch, tmp_path, capsys, cell, 0)
    assert set(line["metrics"]) == {m["name"] for m in resolved.end_to_end}
    assert "breakdown" not in line and standin.profile_bodies == []
    line, standin = measure(monkeypatch, tmp_path, capsys, cell, 1)
    assert set(line["metrics"]) == {m["name"] for m in resolved.per_layer}
    waited = line["detail"]["timings"]["capture_wait"]
    assert waited["stop_s"] < 1.0 < bench_run.CAPTURE_WAIT_S[1] < waited["stop_limit_s"]
    assert standin.profile_bodies == [{
        "seconds": 0.3,
        "dir": os.path.join(str(tmp_path), "profiles", cell + ".run")}]


@pytest.mark.parametrize("cell", CELLS)
def test_trace_0_sends_what_the_accepted_harness_sent(
        monkeypatch, tmp_path, capsys, cell):
    """`recorded_calls.trace0.json` is the log of (method, path), the
    completions left out, that the harness of PR 24 sent to this stand-in
    with these arguments, recorded before `--trace 2` was written.  A
    `--trace 2` run sends the same until its generator has stopped, then
    what its traced stretch needs, then the same again to the end."""
    with open(os.path.join(HERE, "recorded_calls.trace0.json")) as f:
        recorded = [tuple(c) for c in json.load(f)[cell]]
    _, standin = measure(monkeypatch, tmp_path, capsys, cell, 0)
    assert [c for c in standin.calls if c != COMPLETION] == recorded
    _, standin = measure(monkeypatch, tmp_path, capsys, cell, 2)
    sent = [c for c in standin.calls if c != COMPLETION]
    close = recorded.index(("GET", "/admin/telemetry")) + 1
    assert sent[:close] == recorded[:close]
    profile, telemetry = ("POST", "/admin/profile"), ("GET", "/admin/telemetry")
    assert sent[close:close + 5] == [
        profile, profile, telemetry, profile, telemetry]
    # then the untraced run's end, and a wait for the capture to be written
    assert [c for c in sent[close + 5:] if c != telemetry] == recorded[close:]
    assert sent[-2] == telemetry


def test_the_server_child_is_started_alike_in_every_mode(monkeypatch, tmp_path):
    """argv and environment of the server child, modes 0, 1 and 2."""
    started = []

    class Started(Exception):
        pass

    def popen(argv, env=None, **kwargs):
        started.append((
            [a for a in argv if not a.startswith("--http_port=")], dict(env)))
        raise Started

    monkeypatch.setattr(server.subprocess, "Popen", popen)
    monkeypatch.setattr(bench_run, "cache_root", lambda: str(tmp_path))
    plan = bench_run.Plan(manifest.resolve_cell("qwen3-4b.chat"), rehearse=False)
    for trace in (0, 1, 2):
        args = argparse.Namespace(seed=SEED, seconds=1.0, trace=trace)
        with pytest.raises(Started):
            bench_run.measure(args, plan, "tpu")
    assert started[0] == started[1] == started[2]
    assert "--random_weights" in started[0][0]


def as_fields(request):
    return (request.index, request.due_s, request.prompt_len,
            request.output_len, tuple(request.prompt), request.sampling_seed,
            request.phase, request.client, request.gap_s)


@pytest.mark.parametrize("cell", CELLS)
def test_trace_2_drives_the_trace_0_schedule_and_then_another(
        monkeypatch, tmp_path, capsys, cell):
    """What the load generator is handed, field for field: a `--trace 2`
    run's first drive is the `--trace 0` run's (same requests, same
    arguments: no tail, no longer cool-down), and its traced stretch is a
    second drive of the same mix under another seed, so that none of its
    prompts is in the prefix cache."""
    handed = []
    real_open, real_closed = loadgen.run_open_loop, loadgen.run_closed_loop

    async def open_loop(base_url, model, requests, sampling, t_open, drain_s,
                        timeout_s, hooks):
        handed.append(([as_fields(r) for r in requests], (drain_s,)))
        return await real_open(base_url, model, requests, sampling, t_open,
                               drain_s, timeout_s, hooks)

    async def closed_loop(base_url, model, per_client, sampling, t_open,
                          seconds, timeout_s, hooks, vocab, head, tail_s=0.0):
        handed.append(([as_fields(r) for mine in per_client for r in mine]
                       + [as_fields(r) for r in head], (seconds, tail_s)))
        return await real_closed(base_url, model, per_client, sampling, t_open,
                                 seconds, timeout_s, hooks, vocab, head, tail_s)

    monkeypatch.setattr(loadgen, "run_open_loop", open_loop)
    monkeypatch.setattr(loadgen, "run_closed_loop", closed_loop)
    measure(monkeypatch, tmp_path, capsys, cell, 0)
    (plain,) = handed
    del handed[:]
    measure(monkeypatch, tmp_path, capsys, cell, 2)
    window, traced = handed
    assert window == plain
    assert {r[4] for r in traced[0]}.isdisjoint(r[4] for r in plain[0])
    assert sorted({r[2] for r in traced[0]})[0] >= 1  # the same mix's lengths


class Clock:
    """In the place of `time` in run.py: a sleep moves it, nothing waits."""

    def __init__(self, now: float = 5000.0):
        self.now = now

    def monotonic(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds


class WritingStandIn(StandIn):
    """A stand-in that reports its capture `active` until the injected
    clock reaches `written_at`."""

    def __init__(self, clock: Clock, written_at: float):
        self.clock, self.written_at = clock, written_at
        super().__init__()

    capturing = property(lambda self: self.clock.now < self.written_at,
                         lambda self, value: None)


NEVER = float("inf")
#: (--trace, s from the launch to the capture's end, s the write takes)
CAPTURE_WAITS = {
    # eval-sat's write on a slow day; the 200 s this wait had gave up on it
    "trace_2_written_after_260_s": (2, 300.0, 260.0),
    "trace_2_never_written": (2, 300.0, NEVER),
    # a run that is already late keeps the wait it had before PR 51
    "trace_2_late_run_written_after_199_s": (2, 900.0, 199.0),
    "trace_2_very_late_run_never_written": (2, 1150.0, NEVER),
    "trace_1_written_after_260_s": (1, 300.0, 260.0),
    "trace_1_never_written": (1, 300.0, NEVER),
    "trace_1_very_late_run_written_after_119_s": (1, 1150.0, 119.0),
}


@pytest.mark.parametrize("case", CAPTURE_WAITS)
def test_the_wait_for_a_capture_follows_the_runs_clock(monkeypatch, case):
    """`wait_capture` against the stand-in server and an injected clock: it
    waits for as long as the run has left, less the reserve, and never less
    than the wait of its mode had; what it gives up with says how long it
    waited, how far into the run, and that the capture was still active."""
    trace, launched_ago, write_s = CAPTURE_WAITS[case]
    clock = Clock()
    ended_at = clock.now
    monkeypatch.setattr(bench_run, "time", clock)
    monkeypatch.setattr(bench_run, "_T_LAUNCH", ended_at - launched_ago)
    floor_s = bench_run.CAPTURE_WAIT_S[trace]
    left_s = bench_run.RUN_LIMIT_S - bench_run.STOP_RESERVE_S - launched_ago
    limit_s = max(floor_s, left_s)
    with WritingStandIn(clock, ended_at + write_s) as standin:
        server_ = StandInServer(standin)
        if write_s < limit_s:
            waited = bench_run.wait_capture(server_, ended_at, floor_s)
            assert write_s <= waited["stop_s"] < write_s + 1.0
            assert waited["stop_limit_s"] == pytest.approx(limit_s)
            assert 0.0 <= waited["telemetry_answered_s"] < 1.0
            return
        with pytest.raises(server.ServerFailure) as failure:
            bench_run.wait_capture(server_, ended_at, floor_s)
    gave_up_after = clock.now - ended_at
    assert limit_s <= gave_up_after < limit_s + 1.0
    if left_s > floor_s:  # inside the run's limit, the reserve kept
        assert launched_ago + gave_up_after < (
            bench_run.RUN_LIMIT_S - bench_run.STOP_RESERVE_S + 1.0)
    message = str(failure.value)
    assert f"{gave_up_after:.0f} s after the capture's end" in message
    assert f"{launched_ago + gave_up_after:.0f} s into a run" in message
    assert "`active`" in message and f"limit was {limit_s:.0f} s" in message


def test_a_server_that_died_under_the_write_is_told_from_a_slow_write(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(bench_run, "time", clock)
    monkeypatch.setattr(bench_run, "_T_LAUNCH", clock.now - 300.0)
    with StandIn() as standin:
        gone = StandInServer(standin)
    with pytest.raises(server.ServerFailure) as failure:
        bench_run.wait_capture(gone, clock.now, 200.0)
    assert "stopped answering" in str(failure.value)
    assert "`active`" not in str(failure.value)
