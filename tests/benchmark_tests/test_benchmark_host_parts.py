"""The readers of the host's side of a dispatch (PR 39): the parts of
`plan`, `launch` and `route`, the CPU seconds of the loop's thread, the
collector's pauses and the compiles of anything but the engine's programs;
on made-up snapshots of the program's counters, and against the stand-in
server."""

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import manifest, parts, server
from standin import StandIn  # imported here so that conftest's fixture grows it

PARTS = ("prepare", "sampling", "pack", "upload", "call", "account",
         "deliver", "register")
PHASES = ("admit", "plan", "launch", "wait", "route", "yield")
PART_READERS = {f"dispatch.{part}_ms": part for part in PARTS}
NAMES = (*PART_READERS, "host.wait_cpu_ms", "host.loop_cpu_share",
         "host.gc_pause_ms", "dispatch.other_compile_s_in_window")


def snap(dispatches=None, part=None, cpu=None, wall=None, gc=None, compile=None):
    """A scrape with the series given; a series left out is absent."""
    lines = []
    if dispatches is not None:
        lines.append(
            f'engine_dispatches_total{{model_name="bench",program="mixed"}} {dispatches}')
    for name, s in (part or {}).items():
        lines.append('engine_dispatch_part_seconds_total{model_name="bench",'
                     f'part="{name}"}} {s}')
    for name, s in (cpu or {}).items():
        lines.append('engine_dispatch_phase_cpu_seconds_total{model_name="bench",'
                     f'phase="{name}"}} {s}')
    for name, s in (wall or {}).items():
        lines.append('engine_dispatch_phase_seconds_total{model_name="bench",'
                     f'phase="{name}"}} {s}')
    for generation, s in (gc or {}).items():
        lines.append(f'engine_gc_pause_seconds_total{{generation="{generation}"}} {s}')
    if compile is not None:
        lines.append(f"engine_other_compile_seconds_total {compile}")
    return server.parse_metrics("\n".join(lines) + "\n")


def read(name, before, after):
    return manifest.load_reader(name).read({"before": before, "after": after})


@pytest.mark.parametrize("name", PART_READERS)
def test_a_part_is_its_window_s_seconds_over_the_window_s_dispatches(name):
    part = PART_READERS[name]
    others = {p: 9.0 for p in PARTS if p != part}
    before = snap(dispatches=100, part={part: 1.0, **others})
    after = snap(dispatches=300, part={part: 2.5, **{p: 99.0 for p in others}})
    assert read(name, before, after) == pytest.approx(7.5)  # 1.5 s over 200
    # a part that cost nothing in the window reads 0, not nothing
    assert read(name, before, snap(dispatches=300, part={part: 1.0})) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_gives_none_and_does_not_raise(name):
    # the parent: dispatches and the six phases, nothing of PR 39
    wall = dict.fromkeys(PHASES, 1.0)
    before = snap(dispatches=10, wall=wall)
    after = snap(dispatches=20, wall={p: 2.0 for p in PHASES})
    assert read(name, before, after) is None
    assert read(name, snap(), snap()) is None


@pytest.mark.parametrize("name", [n for n in NAMES if n.endswith("_ms")])
def test_no_dispatch_in_the_window_gives_none(name):
    full = dict(part=dict.fromkeys(PARTS, 1.0), cpu=dict.fromkeys(PHASES, 1.0),
                gc={1: 0.0, 2: 0.0})
    assert read(name, snap(dispatches=5, **full), snap(dispatches=5, **full)) is None


def test_wait_cpu_and_the_loop_s_share():
    before = snap(dispatches=0, cpu=dict.fromkeys(PHASES, 0.0),
                  wall=dict.fromkeys(PHASES, 0.0))
    cpu = {"admit": 0.1, "plan": 0.5, "launch": 0.4, "wait": 4.0,
           "route": 0.1, "yield": 0.1}
    wall = {"admit": 0.1, "plan": 0.5, "launch": 0.6, "wait": 18.5,
            "route": 0.1, "yield": 0.2, "wait_lag": 0.3}
    after = snap(dispatches=100, cpu=cpu, wall=wall)
    assert read("host.wait_cpu_ms", before, after) == pytest.approx(40.0)
    # 5.2 CPU seconds of 20.0 on the wall; wait_lag is inside wait
    assert read("host.loop_cpu_share", before, after) == pytest.approx(26.0)
    # an engine under an injected clock books no CPU: 0, not nothing
    idle = snap(dispatches=100, cpu=dict.fromkeys(PHASES, 0.0), wall=wall)
    assert read("host.loop_cpu_share", before, idle) == 0.0
    assert parts.loop_cpu_share(
        {"before": before, "after": snap(dispatches=100, cpu=cpu)}) is None


def test_the_collector_s_pauses_all_generations_per_dispatch():
    before = snap(dispatches=10, gc={1: 0.5, 2: 1.0})
    after = snap(dispatches=60, gc={1: 0.6, 2: 1.4})
    assert read("host.gc_pause_ms", before, after) == pytest.approx(10.0)
    assert read("host.gc_pause_ms", before, snap(dispatches=60, gc={1: 0.5, 2: 1.0})) == 0.0


def test_other_compiles_are_seconds_of_the_window_not_divided():
    before = snap(dispatches=10, compile=4.0)
    after = snap(dispatches=60, compile=4.75)
    name = "dispatch.other_compile_s_in_window"
    assert read(name, before, after) == pytest.approx(0.75)
    assert read(name, before, snap(dispatches=60, compile=4.0)) == 0.0
    # a program that counts none (the engine's own programs are
    # `dispatch.compile_s_in_window`'s) gives nothing to read
    assert read(name, before, snap(dispatches=60)) is None


@pytest.mark.parametrize("name", NAMES)
def test_entry_is_lower_is_better_and_in_every_cell(name):
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == name]
    assert entry["better"] == "lower" and "workloads" not in entry
    reader = manifest.load_reader(name)
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)


def test_the_stand_in_s_parts_make_up_its_phases():
    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    values = {name: read(name, before, after) for name in NAMES}
    assert all(v is not None for v in values.values())
    plan = sum(values[f"dispatch.{p}_ms"] for p in ("prepare", "sampling", "pack"))
    launch = sum(values[f"dispatch.{p}_ms"] for p in ("upload", "call", "account"))
    assert plan == pytest.approx(2.0) and launch == pytest.approx(3.0)
    assert values["host.wait_cpu_ms"] == pytest.approx(20.0)
    assert values["host.loop_cpu_share"] == pytest.approx(50.0)
    assert values["host.gc_pause_ms"] == 0.0
    assert values["dispatch.other_compile_s_in_window"] == 0.0
