"""From a profiler trace (xplane.pb) to the numbers the benchmark reports.

Two steps, so that the arithmetic can be tested on a small recorded trace
without the profiler: `extract()` reads the xplane with nothing but JAX
(`jax.profiler.ProfileData`) into plain lists, and `reduce()` turns those
into device busy time, per-operation time, and the longest idle gaps named
by what the host was doing.  Run as a child under JAX_PLATFORMS=cpu:

    python benchmark/kbench/xplane_reduce.py <profile dir> <out.json>
"""

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the device line whose events are single operations
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MAX_HOST_EVENTS = 3_000_000


def newest_xplane(profile_dir: str):
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


#: `fusion.5414 = f32[48,1187,128]{1,0,2:T(8,128)S(1)} fusion(...)`: on the
#: TPU an operation's event is named by its HLO text
HLO_TEXT = re.compile(
    r"^%?(?P<base>[\w\-]+?)(?:\.\d+)*\s*=\s*(?P<result>\(?[a-z]+\d*\[[\d,]*\])"
    r"(?:.*?[}\])]\s+(?P<opcode>[a-z][\w\-]*)\()?", re.S)


def parse_op(name: str):
    """(label, opcode) of a device event.  `%sort.12 = f32[48,151936]{..}
    sort(...)` -> (`sort_f32_48_151936_`, `sort`): the operation without
    its serial number, with its (first) result's type and shape.  A name
    that is not HLO text keeps its base and has no opcode."""
    m = HLO_TEXT.match(name)
    if not m:
        return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))[:80], ""
    result = m.group("result").lstrip("(")
    dtype, dims = result[:-1].split("[")
    label = f"{m.group('base')}_{dtype}_{dims.replace(',', '_')}_"
    return label, m.group("opcode") or ""


def extract(path: str) -> dict:
    """Plain lists from an xplane.pb: per device the operations
    [label, start_ns, duration_ns, opcode]; for the host its events
    [name, start_ns, duration_ns] (python tracer and TraceMe alike)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, line_names = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            line_names[plane.name] = [line.name for line in plane.lines]
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    label, opcode = parse_op(e.name)
                    ops.append([label, float(e.start_ns),
                                float(e.duration_ns), opcode])
            devices[m.group(2)] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and len(host) < MAX_HOST_EVENTS:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host, "lines": line_names}


def self_times(ops):
    """[(label, opcode, self_ns)]: an operation's duration less that of the
    operations nested inside it (a `while` holds its body's operations on
    the same line), so that times add up to the busy time."""
    out = []
    stack = []  # [end, index into out]
    for label, start, dur, opcode in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[2] -= min(dur, stack[-1][0] - start)
        out.append([label, opcode, dur])
        stack.append([start + dur, len(out) - 1])
    return [(label, opcode, max(0.0, ns)) for label, opcode, ns in out]


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def host_label(name: str) -> str:
    """`$engine.py:3144 _step_mixed` -> `engine.py:3144 _step_mixed`."""
    return name.lstrip("$").strip() or "unknown"


def reduce(extracted: dict, top: int = 10) -> dict:
    """The trace's numbers.  Times in seconds.

    busy_s: per device the union of its operations' intervals, averaged
    over the devices.  window_s: first to last event of the whole trace.
    op_s / opcode_s: self seconds by operation label / by HLO opcode,
    summed over devices and divided by their number (so shares of busy
    time are per chip).  idle_gaps: the
    gaps of device 0 between its busy intervals, each named by the
    innermost host event under way at the gap's middle.
    """
    devices = extracted["devices"]
    if not devices or not any(devices.values()):
        return {"busy_s": 0.0, "window_s": 0.0, "op_s": {}, "opcode_s": {},
                "device_ops": [], "idle_gaps": [], "n_devices": len(devices)}
    starts, ends = [], []
    for ops in devices.values():
        starts += [o[1] for o in ops]
        ends += [o[1] + o[2] for o in ops]
    for _, s, d in extracted["host"]:
        starts.append(s)
        ends.append(s + d)
    t0, t1 = min(starts), max(ends)
    n = len(devices)
    busy, op_s, opcode_s, merged0 = 0.0, {}, {}, None
    for key in sorted(devices):
        ops = devices[key]
        merged = _union([o[1], o[1] + o[2]] for o in ops)
        busy += sum(e - s for s, e in merged)
        if merged0 is None:
            merged0 = merged
        for label, opcode, ns in self_times(ops):
            op_s[label] = op_s.get(label, 0.0) + ns
            opcode_s[opcode] = opcode_s.get(opcode, 0.0) + ns
    op_s = {k: v / n / 1e9 for k, v in op_s.items()}
    opcode_s = {k: v / n / 1e9 for k, v in opcode_s.items()}
    # idle gaps of the first device, by what the host was doing
    host = sorted(extracted["host"], key=lambda e: e[1])
    host_starts = [e[1] for e in host]
    gaps = {}
    edges = [[t0, t0]] + merged0 + [[t1, t1]]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        length = start - end
        if length <= 0:
            continue
        mid = end + length / 2
        label = "gaps_under_0.1_ms" if length < 1e5 else "unknown"
        if length >= 1e5:
            i = bisect.bisect_right(host_starts, mid) - 1
            steps = 0
            while i >= 0 and steps < 5000:
                name, s, d = host[i]
                if s + d > mid:
                    label = host_label(name)
                    break
                i -= 1
                steps += 1
        gaps[label] = gaps.get(label, 0.0) + length
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "op_s": op_s,
        "opcode_s": opcode_s,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v / 1e9] for k, v in top_gaps],
        "n_devices": n,
    }


def share_of_busy(reduced: dict, predicate) -> float:
    """Percent of device busy time spent in the HLO opcodes `predicate`
    picks."""
    if not reduced or not reduced.get("busy_s"):
        return None
    picked = sum(v for k, v in reduced["opcode_s"].items() if predicate(k))
    return 100.0 * picked / reduced["busy_s"]


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def main(argv) -> int:
    profile_dir, out_path = argv[1], argv[2]
    path = newest_xplane(profile_dir)
    if path is None:
        print(f"no xplane.pb under {profile_dir}", file=sys.stderr)
        return 1
    extracted = extract(path)
    reduced = reduce(extracted)
    reduced["lines"] = extracted["lines"]
    with open(out_path, "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
