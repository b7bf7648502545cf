"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

Reads with `jax.profiler.ProfileData` and nothing else. The harness marks the
traced window with two instant host annotations (`chipbench.window_start`,
`chipbench.window_end`) and wraps what it does in `chipbench.<kind>`
annotations (advance, deliver), which land on the device's clock.

    reduce_trace(path) -> {
      "window_s":  seconds between the two marks,
      "busy_s":    union of the intervals in which an operation ran on the
                   device, inside the window, averaged over the device planes,
      "programs":  {program name: {"count": executions, "seconds": device time}},
      "executions": program executions in the window (first device),
      "device_ops": [[program name, seconds], ...]   the 10 that took most time,
      "idle_gaps":  [[what the host was doing, seconds], ...]  idle time of the
                   first device charged to the benchmark's annotation that
                   covers each gap and, inside it, to the innermost host
                   event of the profiler at the gap's middle; the 10 largest,
    }
"""

from __future__ import annotations

import bisect
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
MARK = "chipbench."
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """`jit__consolidate(1234567)` -> `jit__consolidate`."""
    return _SUFFIX.sub("", event_name).strip()


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """The events the reduction needs, as plain tuples in nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == OP_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            devices.append({"name": plane.name, "modules": modules, "ops": ops})
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                if events:
                    host.append(sorted(events))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def _covering(line: dict, at: float, scan: int = 64) -> tuple:
    """On one host thread, the innermost `chipbench.<kind>` annotation that
    covers time `at` and the innermost other event that covers it: (kind, event)."""
    kind = inner = None
    marks, mark_starts = line["marks"], line["mark_starts"]
    for j in range(bisect.bisect_right(mark_starts, at) - 1, -1, -1):
        if marks[j][1] >= at:
            kind = marks[j][2][len(MARK):]
            break
    if kind is None:
        return None, None
    events, starts = line["events"], line["starts"]
    i = bisect.bisect_right(starts, at) - 1
    for j in range(i, max(i - scan, -1), -1):
        if events[j][1] >= at:
            inner = events[j][2]
            break
    return kind, inner


def reduce_events(events: dict, top: int = 10) -> dict:
    marks = [(s, e, n) for line in events["host"] for s, e, n in line if n.startswith(MARK)]
    starts = [s for s, _e, n in marks if n == MARK + "window_start"]
    ends = [e for _s, e, n in marks if n == MARK + "window_end"]
    if not events["devices"]:
        raise ValueError("the trace holds no device plane")
    if not starts or not ends:
        raise ValueError("the trace lacks the window's marks")
    lo, hi = min(starts), max(ends)

    busy_ns, first_busy = [], None
    for dev in events["devices"]:
        busy = _union(_clip(dev["ops"] or [(s, e) for s, e, _n in dev["modules"]], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        if first_busy is None:
            first_busy = busy

    programs: dict = {}
    executions = 0
    for s, e, name in events["devices"][0]["modules"]:
        if e <= lo or s >= hi:
            continue
        executions += 1
        p = programs.setdefault(program_name(name), {"count": 0, "seconds": 0.0})
        p["count"] += 1
        p["seconds"] += (min(e, hi) - max(s, lo)) / 1e9

    # idle gaps of the first device, charged to what the host was doing
    gaps, at = [], lo
    for s, e in first_busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    lines = []
    for line in events["host"]:
        line_marks = [ev for ev in line if ev[2].startswith(MARK) and not ev[2].startswith(MARK + "window_")]
        if line_marks:
            others = [ev for ev in line if not ev[2].startswith(MARK)]
            lines.append({"marks": line_marks, "mark_starts": [ev[0] for ev in line_marks],
                          "events": others, "starts": [ev[0] for ev in others]})
    # cut each gap where one of the benchmark's annotations starts or ends, so
    # that a long gap is shared out among what the host did during it
    cuts = sorted({t for s, e, n in marks if not n.startswith(MARK + "window_") for t in (s, e)})
    pieces = []
    for s, e in gaps:
        at = s
        for t in cuts[bisect.bisect_right(cuts, s) : bisect.bisect_left(cuts, e)]:
            pieces.append((at, t))
            at = t
        pieces.append((at, e))
    charged: dict = {}
    for s, e in pieces:
        mid = (s + e) / 2
        found = [c for c in (_covering(line, mid) for line in lines) if c[0] is not None]
        # several threads can be inside a request at once (a read waits on the
        # lock while a refresh runs): charge the one that is doing traced work
        kind, inner = next((c for c in found if c[1] is not None), found[0] if found else (None, None))
        label = "outside any request" if kind is None else (kind if inner is None else f"{kind}>{inner}")
        charged[label] = charged.get(label, 0.0) + (e - s) / 1e9

    by_time = sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs": programs,
        "executions": executions,
        "device_ops": [[n, p["seconds"]] for n, p in by_time[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(charged.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce_trace(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    return reduce_events(load(path, device_prefix))


def describe(path: str, per_line: int = 3) -> None:
    """Prints the planes, lines and first events of a trace: look at one by
    hand before trusting the reduction on a new device or JAX version."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:per_line]:
                print("     ", e.name, e.start_ns, e.duration_ns)


if __name__ == "__main__":
    import json
    import sys

    describe(sys.argv[1])
    print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
