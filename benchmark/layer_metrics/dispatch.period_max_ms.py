"""Longest launch-to-launch interval among the dispatches of the engine's ring launched in the `seconds` before the snapshot's own `now` (`/admin/telemetry`, fetched as the window closes): a stall of the loop shows here whatever caused it."""

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_mean_ms"


def read(run):
    ring = run["telemetry"].get("dispatches")
    now = run["telemetry"].get("now")
    if not ring or now is None:
        return None
    at = ring["columns"].index("launched_at")
    launches = sorted(row[at] for row in ring["rows"]
                      if row[at] >= now - run["seconds"])
    if len(launches) < 2:
        return None
    return 1e3 * max(b - a for a, b in zip(launches, launches[1:]))
