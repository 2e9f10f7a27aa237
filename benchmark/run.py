#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

A parent that never imports JAX starts the generative server as a child on
the cell's chips (seeded random weights, a model directory that holds only
the configuration's config.json and a synthetic tokenizer), waits until it
is ready, sends the correctness probes, warms the cell's program shapes,
drives the cell's traffic over /openai/v1/completions with SSE — ramp,
window of `--seconds`, cool-down — repeats the probes, stops the server and
prints one JSON line: `correct`, `attempted`, `failed`, `metrics`, `device`
(and `breakdown` with `--trace 1` or `2`).  `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics, `--trace 2` both: it
is a `--trace 0` run until the generator has stopped, followed by a short
traced stretch of the same traffic (`traced_phase`).

It measures only on a TPU: with no accelerator, or fewer chips than the
cell asks for, the server child fails at start-up and this exits non-zero
without a result.  Two other modes share the code:

    --mode rehearse   the same flow on the CPU at a tiny size; prints
                      counts only, under other names, never a metric
    --mode sweep      one server start, the cell's open loop at each of
                      --rates for --seconds: the table the knee is read from
"""

import argparse
import asyncio
import json
import os
import random
import shutil
import subprocess
import sys
import time

_T_LAUNCH = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from kbench import correctness, loadgen, manifest, schedule, stats, warmup  # noqa: E402
from kbench.server import (  # noqa: E402
    MODEL_NAME, Server, ServerFailure, cache_root, metric_sum, parse_metrics,
    write_model_dir)

CLIENT_TIMEOUT_S = 300.0
IDLE_TIMEOUT_S = 400.0
TRACE_SECONDS = 4.0
#: `--trace 1`: the capture starts as the window closes, on the same traffic
#: carried on, so that no number of the window is taken under the profiler.
#: Before PR 26 the program's capture ran the python tracer, which slows the
#: host (a dispatch took 760 ms against 430), and its `stop_trace` blocked
#: the server's loop; inside the window that left the last requests without
#: a first token (failed, so not correct).  Since PR 26 the program stops a
#: capture in a worker thread, with the python tracer off unless asked; a
#: process's FIRST `stop_trace` still takes 13-88 s (my chip runs, PR 26).
#: The traffic goes on this long past the capture's start.
TRACE_TAIL_S = TRACE_SECONDS + 1.0
#: `--trace 2`: the traced stretch's traffic is scheduled this far and
#: called off as soon as the capture is written
TRACED_MAX_S = 200.0
MAX_OPEN_LATE_S = 2.0
MAX_RAMPS = 3
#: A run is ended from outside this long after its launch (PERF.md section
#: 7, "`process_left_running`": PR 44's cold chat run ran out at it).
RUN_LIMIT_S = 1200.0
#: Kept back for what a traced run still does once its capture is written:
#: the state, the server's stop, the trace's reduction, the line.  Measured
#: in `ouro-2.6b.eval-sat`, the largest capture there is: 29.4-30.8 s from
#: the write's end to the process's exit in five runs, of which the stop
#: 5.1-5.9 s and the reduction 23.8-24.8 s (my chip runs, PR 51; PERF.md
#: section 6).  Four times that: the stop may wait STOP_TIMEOUT_S, 60 s, for
#: a server that does not go, and the reduction twice as long on a busy host.
STOP_RESERVE_S = 120.0
#: what the two waits for a capture had before they followed the run's
#: clock (PR 51); neither is ever shorter
CAPTURE_WAIT_S = {1: 120.0, 2: 200.0}


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T_LAUNCH:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Plan:
    """What one run does, resolved from the cell's three data files (and,
    in a rehearsal, their `rehearsal` groups)."""

    def __init__(self, cell: manifest.Cell, rehearse: bool):
        self.cell = cell
        self.rehearse = rehearse
        dep = cell.deployment
        self.hf_config = dict(cell.hf_config)
        self.flags = {**dep["server_flags"], **cell.pair.get("server_flags", {})}
        self.mix = dict(cell.traffic)
        self.rate = cell.pair.get("rate")
        self.clients = cell.pair.get("clients")
        self.scale = 1.0
        policy, warm = dep["engine_policy"], cell.pair.get("warm")
        if rehearse:
            tiny = cell.config["rehearsal"]
            self.hf_config.update(tiny["hf_overrides"])
            self.flags.update(tiny["server_flags"])
            policy = {**policy, **tiny["engine_policy"]}
            warm = warm and tiny["warm"]
            mix_r = self.mix.get("rehearsal", {})
            self.scale = mix_r.get("length_scale", 1.0)
            self.rate = mix_r.get("rate", self.rate)
            self.clients = mix_r.get("clients", self.clients)
            for key in ("ramp_s", "cooldown_s"):
                if key in mix_r:
                    self.mix[key] = mix_r[key]
        self.grid = warm and warmup.grid_of(policy, self.flags, warm)
        self.vocab = int(self.hf_config["vocab_size"])
        self.chips = cell.chips
        self.open_loop = self.mix["loop"] == "open"


async def _fetch(session, url):
    async with session.get(url) as resp:
        return await resp.text()


def make_window_hooks(server: Server, t_open: float, seconds: float,
                      trace: bool, profile_dir: str, out: dict,
                      shapes_before_ramp=None):
    """The coroutine that runs beside the traffic: counters at the window's
    two ends, telemetry and state at its close, then the profiler.
    If the ramp met a program shape nothing had warmed (the compile counter
    rose since `shapes_before_ramp`, or the server took long to answer at
    the opening), it calls the run off at once: the caller ramps again, now
    with that shape compiled.  The sweep passes None: its rows say what
    compiled."""
    import aiohttp

    async def hooks(abort):
        async with aiohttp.ClientSession() as session:
            await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
            out["before"] = parse_metrics(
                await _fetch(session, server.base_url + "/metrics"))
            out["opened_late_s"] = time.perf_counter() - t_open
            if shapes_before_ramp is not None and (
                    compiles(out["before"]) > shapes_before_ramp
                    or out["opened_late_s"] > MAX_OPEN_LATE_S):
                out["disturbed"] = (
                    f"{compiles(out['before']) - shapes_before_ramp:.0f} "
                    f"compiles in the ramp, the server answered "
                    f"{out['opened_late_s']:.1f} s late at the opening")
                abort.set()
                return
            await asyncio.sleep(
                max(0.0, t_open + seconds - time.perf_counter()))
            out["after"] = parse_metrics(
                await _fetch(session, server.base_url + "/metrics"))
            out["telemetry"] = json.loads(await _fetch(
                session, server.base_url + "/admin/telemetry"))
            if trace:
                async with session.post(
                        server.base_url + "/admin/profile",
                        json={"seconds": TRACE_SECONDS, "dir": profile_dir}) as r:
                    out["profile"] = (r.status, await r.json())

    return hooks


def traced_phase(server: Server, plan: Plan, seed: int, profile_dir: str) -> dict:
    """`--trace 2`, once a `--trace 0` run's numbers are taken and its
    generator has stopped: the cell's traffic again (another seed: nothing
    of the window's prompts is in the prefix cache), from the ramp's primer
    on.  During the ramp the profiler is started and stopped once into a
    directory that is thrown away (a process's first stop costs 12-74 s,
    which so falls into no number); at the ramp's end TRACE_SECONDS are
    captured (`{"seconds": N}`: the server stops the capture itself), and
    the traffic is called off as the capture ends.  Writing the trace then
    takes the server 37-79 s more (190-200+ s in `ouro-2.6b.eval-sat`), in
    a thread of its own: `wait_capture` waits that out after the probes.
    Returns what the capture cost so far: the first start and stop, how
    long the server took to answer /admin/telemetry under the capture, the
    engine's dispatch period under it, and when the capture ended."""
    import aiohttp

    mix = plan.mix
    ramp = float(mix.get("ramp_s", 0.0))
    out = {}
    t_open = time.perf_counter() + ramp + 0.25

    async def hooks(abort):
        async def post(body):
            async with session.post(
                    server.base_url + "/admin/profile", json=body) as r:
                if r.status not in (200, 202):
                    raise ServerFailure(f"/admin/profile {body} -> HTTP "
                                        f"{r.status}: {await r.text()}")
                return await r.json()

        async def telemetry():
            t = time.perf_counter()
            snap = json.loads(
                await _fetch(session, server.base_url + "/admin/telemetry"))
            return time.perf_counter() - t, snap["models"].get(MODEL_NAME, {})

        try:
            async with aiohttp.ClientSession() as session:
                t0 = time.perf_counter()
                await post({"action": "start", "dir": profile_dir + ".first"})
                await post({"action": "stop"})
                shutil.rmtree(profile_dir + ".first", ignore_errors=True)
                out["first_start_stop_s"] = time.perf_counter() - t0
                await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
                out["ramp_s"] = time.perf_counter() - t0
                answered_s, at_start = await telemetry()
                await post({"seconds": TRACE_SECONDS, "dir": profile_dir})
                await asyncio.sleep(TRACE_SECONDS)
                while_stopping_s, at_stop = await telemetry()
                out["capture_ended_at"] = time.monotonic()
                out["telemetry_answered_s"] = max(answered_s, while_stopping_s)
                ring = at_stop.get("dispatches") or {
                    "columns": ["launched_at"], "rows": []}
                at = ring["columns"].index("launched_at")
                launches = [row[at] for row in ring["rows"]
                            if row[at] >= (at_start.get("now") or 0.0)]
                out["period_under_capture_ms"] = (
                    1e3 * (launches[-1] - launches[0]) / (len(launches) - 1)
                    if len(launches) > 1 else None)
        finally:
            abort.set()

    if plan.open_loop:
        requests = schedule.open_loop_schedule(
            mix, plan.rate, TRACED_MAX_S, seed, plan.vocab, plan.scale)
        asyncio.run(loadgen.run_open_loop(
            server.base_url, MODEL_NAME, requests, mix["sampling"], t_open,
            float(mix.get("drain_s", 30.0)), CLIENT_TIMEOUT_S, hooks))
    else:
        per_client = schedule.closed_loop_schedule(
            mix, plan.clients, seed, plan.vocab, plan.scale)
        head = schedule.ramp_head(
            mix, -ramp, random.Random(seed + 1), plan.vocab, plan.scale)
        asyncio.run(loadgen.run_closed_loop(
            server.base_url, MODEL_NAME, per_client, mix["sampling"], t_open,
            TRACED_MAX_S, CLIENT_TIMEOUT_S, hooks, plan.vocab, head))
    if "capture_ended_at" not in out:
        raise ServerFailure(f"the capture was not taken: {out}")
    return out


def wait_capture(server: Server, ended_at: float, at_least_s: float) -> dict:
    """Until the profiler has written its capture, which ended at
    `ended_at` (`time.monotonic`).  How long that may take is set by what
    the run has left: to RUN_LIMIT_S after the launch less STOP_RESERVE_S,
    and `at_least_s` however late the run is.  Returns how long it took
    from the capture's end (`stop_s`), the limit it was held to
    (`stop_limit_s`) and the longest the server took to answer meanwhile."""
    limit_s = max(at_least_s,
                  _T_LAUNCH + RUN_LIMIT_S - STOP_RESERVE_S - ended_at)
    answered_s = 0.0
    while True:
        t = time.monotonic()
        try:
            # a `stop_trace` that blocks the loop that would answer (the
            # program's before PR 26) is waited out
            active = server.get_json(
                "/admin/telemetry", timeout=at_least_s)["profiler"]["active"]
        except OSError as e:
            raise ServerFailure(
                f"the server stopped answering {t - ended_at:.0f} s after "
                f"the capture's end, {t - _T_LAUNCH:.0f} s into the run, "
                f"with the capture being written: {e!r}") from None
        now = time.monotonic()
        answered_s = max(answered_s, now - t)
        if not active:
            return {"stop_s": now - ended_at, "stop_limit_s": limit_s,
                    "telemetry_answered_s": answered_s}
        if now - ended_at >= limit_s:
            raise ServerFailure(
                f"the profiler capture did not end: {now - ended_at:.0f} s "
                f"after the capture's end (the limit was {limit_s:.0f} s) and "
                f"{now - _T_LAUNCH:.0f} s into a run of {RUN_LIMIT_S:.0f} s "
                "the server still answers and reports the capture `active`: "
                "a slow write of the trace, not a server that died")
        time.sleep(0.5)


def drive(server: Server, plan: Plan, seed: int, seconds: float, trace: bool,
          profile_dir: str, rate=None, shapes_before_ramp=None) -> dict:
    """Ramp, window, cool-down.  Returns records and the window's counters;
    `disturbed` is set where the run was called off at the window's opening.
    A traced run carries the traffic on past the window, under the capture."""
    mix = plan.mix
    tail = TRACE_TAIL_S if trace else 0.0
    if trace and plan.open_loop:
        mix = dict(mix, cooldown_s=max(float(mix.get("cooldown_s", 0.0)), tail))
    ramp = float(mix.get("ramp_s", 0.0))
    side = {}
    t_open = time.perf_counter() + ramp + 0.25
    hooks = make_window_hooks(server, t_open, seconds, trace, profile_dir, side,
                              shapes_before_ramp)
    if plan.open_loop:
        requests = schedule.open_loop_schedule(
            mix, rate or plan.rate, seconds, seed, plan.vocab, plan.scale)
        records = asyncio.run(loadgen.run_open_loop(
            server.base_url, MODEL_NAME, requests, mix["sampling"], t_open,
            float(mix.get("drain_s", 30.0)), CLIENT_TIMEOUT_S, hooks))
    else:
        per_client = schedule.closed_loop_schedule(
            mix, plan.clients, seed, plan.vocab, plan.scale)
        head = schedule.ramp_head(
            mix, -ramp, random.Random(seed + 1), plan.vocab, plan.scale)
        records = asyncio.run(loadgen.run_closed_loop(
            server.base_url, MODEL_NAME, per_client, mix["sampling"], t_open,
            seconds, CLIENT_TIMEOUT_S, hooks, plan.vocab, head, tail))
    side["records"] = records
    side["t_open_launch_s"] = t_open - time.perf_counter() + (
        time.monotonic() - _T_LAUNCH)
    return side


def wait_idle(server: Server, timeout_s: float = IDLE_TIMEOUT_S) -> float:
    """Until nothing is seated or queued (cancelled streams have drained).
    As the lanes empty the engine can pass through a program shape it has
    not met; a checkout's first run then compiles here, outside the window,
    and the server answers nothing meanwhile."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        state = server.state(timeout=timeout_s)
        if not state.get("inflight") and not state.get("queue_depth"):
            return time.monotonic() - t0
        time.sleep(0.25)
    raise ServerFailure(f"server not idle {timeout_s:.0f} s after the window")


def reduce_trace(profile_dir: str, out_path: str) -> dict:
    """The xplane reduction, in a child under JAX_PLATFORMS=cpu (the parent
    never imports JAX); run after the server has exited."""
    argv = [sys.executable, os.path.join(HERE, "kbench", "xplane_reduce.py"),
            profile_dir, out_path]
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    done = subprocess.run(argv, env=env, capture_output=True, text=True,
                          timeout=600)
    if done.returncode != 0:
        raise RuntimeError(f"trace reduction failed: {done.stderr[-500:]}")
    with open(out_path) as f:
        return json.load(f)


def device_report(state: dict) -> dict:
    rows = state["devices"]
    peaks = [r["peak_bytes_in_use"] for r in rows
             if r.get("peak_bytes_in_use") is not None]
    return {
        "platform": rows[0]["platform"],
        "kind": rows[0]["kind"],
        "count": len(rows),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


def compiles(snapshot: dict) -> float:
    return metric_sum(snapshot, "engine_xla_compiles_total")


def model_dir_of(plan: Plan, cache: str) -> str:
    return os.path.join(
        cache, "model", plan.cell.config_name + ("-rehearsal" if plan.rehearse else ""))


def start_server(plan: Plan, platform: str, cache: str, tag: str) -> Server:
    model_dir = model_dir_of(plan, cache)
    write_model_dir(model_dir, plan.hf_config)
    flags = {k: (os.path.join(cache, v[len("@cache/"):])
                 if isinstance(v, str) and v.startswith("@cache/") else v)
             for k, v in dict(plan.flags, model_dir=model_dir).items()}
    n_cpu = plan.flags.get("tp", 1) if platform == "cpu" else 0
    return Server(flags, platform, cache, tag, n_cpu_devices=n_cpu)


def prepare(server: Server, plan: Plan, platform: str, reference: bool, cache: str):
    """Ready, device check, probes, reference child, shape warm-up.
    Returns (timings, probes, served, reference check or None)."""
    timings = {"ready_s": server.wait_ready()}
    log(f"server ready after {timings['ready_s']:.1f} s")
    device = device_report(server.state())
    if device["platform"] != platform or (
            platform != "cpu" and device["count"] != plan.chips):
        raise ServerFailure(
            f"the server runs on {device['count']} x {device['platform']} "
            f"({device['kind']}); the cell asks for {plan.chips} x {platform}")
    if platform != "cpu":
        manifest.load_peaks(device["kind"])  # an unknown device is an error
    t0 = time.monotonic()
    prompts = correctness.probe_prompts(
        plan.vocab, 16 if plan.rehearse else correctness.PROBE_PROMPT_LEN)
    served = correctness.run_probes(server, prompts)
    timings["probes_s"] = time.monotonic() - t0
    ref = None
    if reference:
        ref = correctness.ReferenceCheck(
            cache, model_dir_of(plan, cache), plan.hf_config,
            plan.cell.deployment["family"], prompts, served)
        log("reference: " + ("cached" if ref.cached else "child started"))
    t0 = time.monotonic()
    if plan.grid:
        n = asyncio.run(warmup.grid_warmup(
            server.base_url, MODEL_NAME, plan.grid, plan.vocab, log))
        log(f"grid warm-up: {n} waves, "
            f"{compiles(parse_metrics(server.get('/metrics'))):.0f} shapes")
    timings["grid_s"] = time.monotonic() - t0
    if ref is not None and not ref.cached:
        t0 = time.monotonic()
        ref.result()  # keep the host's cores free for the window
        timings["reference_wait_s"] = time.monotonic() - t0
    return timings, prompts, served, ref


def build_run(plan: Plan, side: dict, seconds: float, state: dict, trace,
              timings: dict, startup_metrics: dict, device: dict) -> dict:
    """What a per-layer reader is handed."""
    records = side["records"]
    return {
        "setup_s": side["t_open_launch_s"],
        "cell": plan.cell.name,
        "chips": plan.chips,
        "seconds": seconds,
        "records": records,
        "client": stats.end_to_end(records, seconds, plan.chips),
        "limits": plan.mix.get("limits"),
        "before": side["before"],
        "after": side["after"],
        "opened_late_s": side["opened_late_s"],
        "telemetry": side.get("telemetry", {}).get("models", {}).get(MODEL_NAME, {}),
        "state": state,
        "trace": trace,
        "peaks": (manifest.load_peaks(device["kind"])
                  if device["platform"] != "cpu" else None),
        "device": device,
        "hf_config": plan.hf_config,
        "flags": plan.flags,
        "timings": timings,
        "startup_metrics": startup_metrics,
    }


def judge(plan: Plan, run: dict, served_before, served_after, ref, tolerance):
    """The conjunction that is `correct`, with its reasons."""
    faults = correctness.check_streams(run["records"], plan.vocab)
    reasons = [f"{len(faults)} faulty streams, first: {faults[0]}"] if faults else []
    if served_before != served_after:
        reasons.append("greedy probes differ before and after the window")
    in_window = compiles(run["after"]) - compiles(run["before"])
    if in_window:
        reasons.append(f"{in_window:.0f} compiles inside the window")
    if run["opened_late_s"] > MAX_OPEN_LATE_S:
        # a compile blocks the server's loop: its counter then reads the
        # same at both ends although the stall lay inside the window
        reasons.append(
            f"the server took {run['opened_late_s']:.1f} s to answer at the "
            "window's opening: something stalled it (a compile?)")
    detail = {"compiles_in_window": in_window,
              "opened_late_s": run["opened_late_s"],
              # shapes first met in the ramp: each stalled it for a compile
              "ramp_compiles": compiles(run["before"])
              - run["timings"]["shapes_before_ramp"]}
    if ref is not None:
        result = ref.result()
        detail["reference_max_gap"] = result["max_gap"]
        detail["reference_argmax_match_share"] = result["argmax_match_share"]
        if result["max_gap"] > tolerance:
            reasons.append(
                f"a served token's reference logit lies {result['max_gap']:.4f} "
                f"under the reference's maximum; tolerance {tolerance}")
    return not reasons, reasons, detail


def measure(args, plan: Plan, platform: str) -> int:
    cache = cache_root()
    tag = f"{plan.cell.name}.{'rehearse' if plan.rehearse else 'run'}"
    profile_dir = os.path.join(cache, "profiles", tag)
    if args.trace:
        shutil.rmtree(profile_dir, ignore_errors=True)
    server = start_server(plan, platform, cache, tag)
    log("compile cache: " + os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(cache, "jax") + " (ours)"))
    ref = None
    try:
        timings, prompts, served, ref = prepare(
            server, plan, platform, reference=True, cache=cache)
        startup_metrics = parse_metrics(server.get("/metrics"))
        for attempt in range(MAX_RAMPS):
            shapes = compiles(parse_metrics(server.get("/metrics")))
            timings["shapes_before_ramp"] = shapes
            side = drive(server, plan, args.seed, args.seconds,
                         args.trace == 1, profile_dir,
                         shapes_before_ramp=shapes)
            if "disturbed" not in side:
                break
            log(f"ramp {attempt + 1} called off: {side['disturbed']}")
            timings["ramps_called_off"] = attempt + 1
            wait_idle(server)
        else:
            raise ServerFailure(
                f"{MAX_RAMPS} ramps in a row met a program shape nothing "
                "had warmed: no window was measured")
        log(f"window closed; set-up was {side['t_open_launch_s']:.1f} s")
        if args.trace == 1:
            # the capture started as the window closed; the traffic's tail
            # has outlasted it
            timings["capture_wait"] = wait_capture(
                server, time.monotonic(), CAPTURE_WAIT_S[1])
        elif args.trace == 2:
            timings["traced_phase"] = traced_phase(
                server, plan, args.seed + 1, profile_dir)
        timings["idle_wait_s"] = wait_idle(server)
        served_after = correctness.run_probes(server, prompts)
        if args.trace == 2:
            cost = timings["traced_phase"]
            waited = wait_capture(
                server, cost.pop("capture_ended_at"), CAPTURE_WAIT_S[2])
            waited["telemetry_answered_s"] = max(
                waited["telemetry_answered_s"], cost["telemetry_answered_s"])
            cost.update(waited)
        state = server.state()
        device = device_report(state)
        t0 = time.monotonic()
        code = server.stop()
        timings["server_stop_s"] = time.monotonic() - t0
        log(f"server stopped with code {code}")
        trace = None
        if args.trace:
            t0 = time.monotonic()
            trace = reduce_trace(
                profile_dir, os.path.join(profile_dir, "reduced.json"))
            timings["reduce_s"] = time.monotonic() - t0
            if args.trace == 2:  # reduced: the trace itself is not kept
                shutil.rmtree(profile_dir, ignore_errors=True)
        run = build_run(plan, side, args.seconds, state, trace, timings,
                        startup_metrics, device)
        ok, reasons, detail = judge(
            plan, run, served, served_after, ref,
            plan.cell.deployment["logit_tolerance"])
    except ServerFailure as e:
        log(f"FAILED: {e}\n{server.log_tail()}")
        return 1
    finally:
        server.stop()
        if ref is not None:
            ref.kill()
    tried = stats.attempted(run["records"], args.seconds)
    failed = sum(stats.is_failed(r) for r in tried)
    for reason in reasons:
        log("not correct: " + reason)
    if plan.rehearse:
        # a CPU run says nothing about speed: counts only, under other names
        print(json.dumps({
            "rehearsal": True, "platform": device["platform"],
            "correct": ok, "reasons": reasons, "requests_attempted": len(tried),
            "requests_failed": failed,
            "tokens_received_in_window": stats.tokens_in_window(
                run["records"], args.seconds),
            "shapes_compiled": compiles(run["after"]),
            "per_layer_readers_ok": sorted(
                per_layer_metrics(plan, run, strict=True)),
            **detail}))
        return 0 if ok else 1
    if args.trace:
        metrics = per_layer_metrics(plan, run)
        if args.trace == 2:
            metrics = {**end_to_end_metrics(plan, run), **metrics}
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
    else:
        metrics = end_to_end_metrics(plan, run)
    line = {"correct": ok, "attempted": len(tried), "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    # every client-side statistic with its sample count, judged or not: a
    # stall inside the window shows in ttft_max_ms and itl_max_ms
    line["detail"] = {**detail, "reasons": reasons, "timings": timings,
                      "client": {k: list(v) for k, v in run["client"].items()}}
    print(json.dumps(line))
    return 0


def end_to_end_metrics(plan: Plan, run: dict) -> dict:
    out = {}
    for m in plan.cell.end_to_end:
        if m["name"] == "setup_s":
            value = run["setup_s"]
        else:
            value = run["client"][m["name"]][0]
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer_metrics(plan: Plan, run: dict, strict: bool = False) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in plan.cell.per_layer:
        reader = manifest.load_reader(m["name"])
        try:
            value = reader.read(run)
        except (KeyError, TypeError, ZeroDivisionError) as e:
            if strict:
                raise
            log(f"reader {m['name']}: {type(e).__name__}: {e}")
            value = None
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def sweep(args, plan: Plan, platform: str) -> int:
    """One server start; the open loop at each rate; the knee's table."""
    if not plan.open_loop:
        log("a closed loop has no rate to sweep")
        return 2
    cache = cache_root()
    server = start_server(plan, platform, cache, plan.cell.name + ".sweep")
    rows = []
    try:
        prepare(server, plan, platform, reference=False, cache=cache)
        for rate in args.rates:
            side = drive(server, plan, args.seed, args.seconds, False, "", rate)
            wait_idle(server, 300.0)
            records = side["records"]
            client = stats.end_to_end(records, args.seconds, plan.chips)
            queue = metric_sum(side["after"], "engine_queue_depth")
            tried = stats.attempted(records, args.seconds)
            # queue growth: TTFT of the window's last third over its first
            third = len(tried) // 3
            early = stats.mean(stats.ttfts_ms(tried[:third], args.seconds))
            late = stats.mean(stats.ttfts_ms(tried[-third:], args.seconds))
            rows.append({
                "rate": rate, "attempted": len(tried),
                "failed": sum(stats.is_failed(r) for r in tried),
                "unfinished": sum(not r.done and r.error is None for r in tried),
                "slo_share": stats.slo_share(records, args.seconds, plan.mix["limits"]),
                "ttft_mean_ms": client["ttft_mean_ms"][0],
                "ttft_p50_ms": client["ttft_p50_ms"][0],
                "ttft_p95_ms": client["ttft_p95_ms"][0],
                "ttft_first_third_ms": early, "ttft_last_third_ms": late,
                "tpot_p50_ms": client["tpot_p50_ms"][0],
                "itl_p99_ms": client["itl_p99_ms"][0],
                "output_tok_s": client["output_tok_s"][0],
                "queue_depth_at_close": queue,
                "compiles_in_window": compiles(side["after"]) - compiles(side["before"]),
            })
            log(json.dumps(rows[-1]))
    except ServerFailure as e:
        log(f"FAILED: {e}\n{server.log_tail()}")
        return 1
    finally:
        server.stop()
    knee = knee_of(rows)
    print(json.dumps({
        "sweep": plan.cell.name, "rehearsal": plan.rehearse,
        "seconds": args.seconds, "rows": rows,
        "knee": knee, "rate_at_four_fifths": knee and round(0.8 * knee, 1)}))
    return 0


def knee_of(rows: list):
    """The highest swept rate the system sustains: 90 % of requests met the
    mix's limits, the completed tokens kept up with the lowest rate's per
    request (nothing piled up), and the queue did not grow through the
    window (the last third's TTFT under twice the first third's plus half
    a second).  None if not even the lowest rate holds."""
    per_request = rows[0]["output_tok_s"] / rows[0]["rate"]
    knee = None
    for row in rows:
        holds = (
            row["failed"] == 0 and row["slo_share"] >= 90.0
            and row["output_tok_s"] >= 0.95 * per_request * row["rate"]
            and row["ttft_last_third_ms"]
            <= 2.0 * row["ttft_first_third_ms"] + 500.0)
        if not holds:
            break
        knee = row["rate"]
    return knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--mode", choices=("measure", "rehearse", "sweep"),
                    default="measure")
    ap.add_argument("--rates", type=lambda s: [float(x) for x in s.split(",")],
                    default=[], help="sweep mode: requests per second, comma-separated")
    ap.add_argument("--cpu", action="store_true",
                    help="sweep mode: rehearse the sweep on the CPU at the "
                    "tiny size (its rows then say nothing about the chip)")
    args = ap.parse_args(argv)
    try:
        cell = manifest.resolve_cell(args.workload)
    except manifest.ManifestError as e:
        log(f"FAILED: {e}")
        return 2
    plan = Plan(cell, rehearse=args.mode == "rehearse" or args.cpu)
    platform = "cpu" if plan.rehearse else "tpu"
    if args.mode == "sweep":
        return sweep(args, plan, platform)
    return measure(args, plan, platform)


if __name__ == "__main__":
    sys.exit(main())
