"""The engine's account of the HOST's side of a dispatch, for the readers
of its parts, its CPU seconds and the pauses of the whole process (PR 39).

Beside the six phases (`kbench/phases.py`) the program times what `plan`,
`launch` and `route` are made of into
`engine_dispatch_part_seconds_total{part}` (prepare | sampling | pack |
upload | call | account | deliver | register), books the CPU seconds of the
loop's thread to `engine_dispatch_phase_cpu_seconds_total{phase}`, and
counts what stops the whole process: `engine_other_compile_seconds_total`
(compiles of anything but the engine's own programs) and
`engine_gc_pause_seconds_total{generation}`.
Readers take the window's delta, most of them over the delta of
`engine_dispatches_total`.  A program without a series (absent from the
scrape, which is not a delta of zero) gives nothing to read.
"""

from .phases import PHASES
from .server import metric_delta


def window_delta(run: dict, name: str, **labels):
    """The window's delta of the samples of `name` that carry `labels`;
    None where the closing scrape has no such sample."""
    want = set(labels.items())
    if not any(n == name and want <= ls for n, ls in run["after"]):
        return None
    return metric_delta(run["before"], run["after"], name, **labels)


def per_dispatch_ms(run: dict, name: str, **labels):
    """Mean milliseconds per dispatch of the window of a seconds counter."""
    seconds = window_delta(run, name, **labels)
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    if seconds is None or not dispatches:
        return None
    return 1e3 * seconds / dispatches


def part_ms(run: dict, part: str):
    return per_dispatch_ms(
        run, "engine_dispatch_part_seconds_total", part=part)


def phase_cpu_ms(run: dict, phase: str):
    return per_dispatch_ms(
        run, "engine_dispatch_phase_cpu_seconds_total", phase=phase)


def loop_cpu_share(run: dict):
    """CPU seconds of the loop's thread over the wall seconds of the same
    six phases, in per cent."""
    cpu = window_delta(run, "engine_dispatch_phase_cpu_seconds_total")
    wall = sum(
        metric_delta(run["before"], run["after"],
                     "engine_dispatch_phase_seconds_total", phase=phase)
        for phase in PHASES)
    if cpu is None or not wall:
        return None
    return 100.0 * cpu / wall
