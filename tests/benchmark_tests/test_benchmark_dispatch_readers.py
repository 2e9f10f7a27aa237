"""The readers of the engine's dispatch accounting (PR 26) on a recorded
snapshot: made-up but consistent numbers, one case per reader."""

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import manifest, phases, server

COLUMNS = ["serial", "launched_at", "program", "tokens", "width",
           "prefill_tokens", "decode_tokens", "admit", "plan", "launch",
           "wait", "route", "yield", "wait_lag", "compiled", "chained"]


def snap(dispatches, seconds, compile_s, first_n, first_sum):
    lines = [
        f'engine_dispatches_total{{model_name="bench",program="mixed"}} {dispatches}',
        f'engine_xla_compile_seconds_total{{program="mixed"}} {compile_s}',
        f'engine_first_token_dispatches_count{{model_name="bench"}} {first_n}',
        f'engine_first_token_dispatches_sum{{model_name="bench"}} {first_sum}',
        'engine_startup_seconds_count{model_name="bench",phase="aot_load"} 1',
        'engine_startup_seconds_sum{model_name="bench",phase="aot_load"} 38.5',
        'engine_startup_seconds_count{model_name="bench",phase="weights"} 1',
        'engine_startup_seconds_sum{model_name="bench",phase="weights"} 9.25',
    ]
    for phase, s in seconds.items():
        lines.append('engine_dispatch_phase_seconds_total{model_name="bench",'
                     f'phase="{phase}"}} {s}')
    return server.parse_metrics("\n".join(lines) + "\n")


def recorded_run():
    """100 dispatches in the window: per dispatch 2 + 8 ms before the
    launch, 10 ms launch, 320 ms wait of which 4 ms lag, 30 ms route, 30 ms
    yield: a period of 400 ms.  The ring holds launches every 0.4 s with
    one gap of 2.4 s inside the last 30 s and one of 5 s before them."""
    zero = dict.fromkeys(
        ("admit", "plan", "launch", "wait", "route", "yield", "wait_lag"), 0.0)
    before = snap(50, zero, 600.0, 10, 14)
    after = snap(150, {"admit": 0.2, "plan": 0.8, "launch": 1.0, "wait": 32.0,
                       "route": 3.0, "yield": 3.0, "wait_lag": 0.4},
                 601.5, 210, 354)
    launches = [60.0, 65.0] + [70.4 + 0.4 * i for i in range(20)]
    launches += [launches[-1] + 2.4 + 0.4 * i for i in range(40)]
    rows = [[i, t, "mixed", 128, 32, 40, 8, 0, 0, 0, 0, 0, 0, 0, 0, 0]
            for i, t in enumerate(launches)]
    return {"seconds": 30.0, "before": before, "after": after,
            "startup_metrics": before,
            "telemetry": {"now": 100.0,
                          "dispatches": {"columns": COLUMNS, "rows": rows}}}


EXPECTED = {
    "dispatch.plan_ms": 10.0,
    "dispatch.launch_ms": 10.0,
    "dispatch.wait_ms": 316.0,
    "dispatch.route_ms": 30.0,
    "dispatch.yield_ms": 34.0,
    "dispatch.host_share": 100.0 * (40.0 - 31.6) / 40.0,
    "dispatch.period_max_ms": 2400.0,  # the 5 s gap lies before the window
    "dispatch.compile_s_in_window": 1.5,
    "engine.dispatches_to_first_token": 1.7,  # (354 - 14) / (210 - 10)
    "startup.aot_load_s": 38.5,
    "startup.weights_s": 9.25,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_recorded_run(name):
    value = manifest.load_reader(name).read(recorded_run())
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_matches_its_manifest_entry(name):
    reader = manifest.load_reader(name)
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == name]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)


@pytest.mark.parametrize("name", sorted(set(EXPECTED) - {"dispatch.compile_s_in_window"}))
def test_a_program_without_the_accounting_gives_nothing_to_read(name):
    """The parent of PR 26 has none of these series, no ring and no `now`:
    each reader then returns None (and does not raise), so the line leaves
    the metric out."""
    empty = server.parse_metrics("engine_queue_depth 0\n")
    run = {"seconds": 30.0, "before": empty, "after": empty,
           "startup_metrics": empty, "telemetry": {}}
    assert manifest.load_reader(name).read(run) is None


def test_the_phases_tile_the_period():
    w = phases.window_seconds(recorded_run())
    assert w["all"] == pytest.approx(40.0) and w["dispatches"] == 100
    assert phases.PHASES == ("admit", "plan", "launch", "wait", "route", "yield")
