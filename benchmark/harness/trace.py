"""From a ``jax.profiler`` trace to device numbers.

    python benchmark/harness/trace.py <file.xplane.pb>   # prints JSON

Run as a process of its own, after the gateway has gone and with
``JAX_PLATFORMS=cpu``: reading a trace needs jax's ``ProfileData`` and
must not touch the chip. What it reads: on each device plane
(``/device:TPU:<n>``) the line of XLA ops gives the intervals in which an
operation ran, and the line of XLA modules one event per execution of a
compiled program, named after its jitted function (``jit_spatial_step``).
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start_ns, end_ns)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total / 1e9


def program_name(event_name: str) -> str:
    """``jit_spatial_step(1234)`` -> ``jit_spatial_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_label(event_name: str) -> str:
    """An XLA op's event is named by its whole HLO line; keep the name the
    trace gives it, its result's shape and its opcode:
    ``%fusion.2 = s32[8193]{0:T(1024)} fusion(...)`` ->
    ``%fusion.2 s32[8193] fusion``."""
    m = re.match(r"(%?[\w.\-]+) = \(?(\w+\[[\d,]*\])\S* (?:.*?\) )?([\w\-]+)\(",
                 event_name)
    return " ".join(m.groups()) if m else event_name[:120]


def reduce_planes(planes, device_plane=DEVICE_PLANE, ops_line=OPS_LINE,
                  modules_line=MODULES_LINE) -> dict:
    """``planes``: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]. Returns the traced window, the seconds in which an
    operation ran (mean over the device planes), each program's count and
    seconds, the ten operations that took most time, and the ten longest
    gaps with the programs on either side."""
    first, last = None, None
    chips = []
    for plane, lines in planes:
        for _, events in lines:
            for _, start, dur in events:
                first = start if first is None else min(first, start)
                last = start + dur if last is None else max(last, start + dur)
        if re.search(device_plane, plane):
            chips.append(lines)
    if first is None or not chips:
        return {"device_planes": 0}
    busy, modules, ops, gaps = [], {}, {}, []
    for lines in chips:
        op_events = [e for name, events in lines
                     if re.search(ops_line, name) for e in events]
        mod_events = sorted(
            (e for name, events in lines
             if re.search(modules_line, name) for e in events),
            key=lambda e: e[1])
        busy.append(union_seconds(
            (s, s + d) for _, s, d in (op_events or mod_events)))
        for name, _, dur in op_events:
            name = op_label(name)
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        for name, _, dur in mod_events:
            entry = modules.setdefault(program_name(name),
                                       {"count": 0, "seconds": 0.0})
            entry["count"] += 1
            entry["seconds"] += dur / 1e9
        edges = ([("window start", first, 0.0)] + mod_events
                 + [("window end", last, 0.0)])
        for (a, s0, d0), (b, s1, _) in zip(edges, edges[1:]):
            if s1 > s0 + d0:
                gaps.append((f"{program_name(a)} -> {program_name(b)}",
                             (s1 - s0 - d0) / 1e9))
    n = len(chips)
    top = lambda pairs: [[k, v] for k, v in sorted(
        pairs, key=lambda kv: -kv[1])[:10]]
    return {
        "device_planes": n,
        "window_s": (last - first) / 1e9,
        "busy_s": sum(busy) / n,
        "modules": {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
                    for k, v in modules.items()},
        "device_ops": top((k, v / n) for k, v in ops.items()),
        "idle_gaps": top(gaps),
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events])
              for line in plane.lines])
            for plane in data.planes]


if __name__ == "__main__":
    planes = read_planes(sys.argv[1])
    out = reduce_planes(planes)
    out["planes"] = [[name, [[line, len(events)] for line, events in lines]]
                     for name, lines in planes]
    print(json.dumps(out))
