"""The engine's own accounting of a dispatch, for the `dispatch.*` readers.

The program stamps six consecutive phases on every iteration of its loop
that dispatched (admit, plan, launch, wait, route, yield; they tile the
launch-to-launch period) into `engine_dispatch_phase_seconds_total{phase}`,
plus `wait_lag`: the part of `wait` in which the result was already on the
host and the event loop had not yet resumed the engine.  Readers take the
window's delta of these over the delta of `engine_dispatches_total`; a
program without the counters gives nothing to read.
"""

from .server import metric_delta

PHASES = ("admit", "plan", "launch", "wait", "route", "yield")


def window_seconds(run: dict) -> dict:
    """{phase: seconds in the window} with `wait_lag`, `dispatches` and
    `all` (the six phases' sum); None where the program counted nothing."""
    before, after = run["before"], run["after"]
    n = metric_delta(before, after, "engine_dispatches_total")
    if not n:
        return None
    out = {phase: metric_delta(before, after,
                               "engine_dispatch_phase_seconds_total",
                               phase=phase)
           for phase in (*PHASES, "wait_lag")}
    out["all"] = sum(out[phase] for phase in PHASES)
    out["dispatches"] = n
    return out


def per_dispatch_ms(run: dict, plus=(), minus=()):
    """Mean milliseconds per dispatch of the phases `plus` less `minus`."""
    w = window_seconds(run)
    if w is None:
        return None
    seconds = sum(w[p] for p in plus) - sum(w[p] for p in minus)
    return 1e3 * seconds / w["dispatches"]
