"""The device's idle time, put down to what the gateway's loop thread was
doing: the program's own spans, read from the same ``jax.profiler`` trace
as the device's operations, on the profiler's one clock.

    python benchmark/harness/host_spans.py <file.xplane.pb>   # prints JSON

Run like ``trace.py``: a process of its own, after the gateway has gone,
with ``JAX_PLATFORMS=cpu``. While a profiler session is live the gateway
enters a ``TraceAnnotation`` named ``channeld/<span>`` around each of its
flight recorder's regions (``channeld_tpu/core/tracing.py``); they land on
the ``/host:CPU`` plane, on the line of the thread that made them. The
loop thread is the line that holds the ``channeld/tick.*`` events. A
program that makes no such event (one older than its spans) gives
``{"host_spans": 0}`` and every reader then returns ``None``.

Beside the trace file it writes ``idle_by_host_span.json``: idle seconds under
each innermost span of the loop thread (self time: a span's interval less
what its children cover), the ten longest idle gaps with the seconds of
each under each such span, and how many executions of ``jit_spatial_step``
lie between the start of a ``channeld/step.dispatch`` and the end of the
``channeld/step.fetch`` that follows it, which is what shows the clock to
be one.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import subprocess
import sys

PREFIX = "channeld/"
TICK = PREFIX + "tick."
GLOBAL_TICK = TICK + "GLOBAL"
OUTSIDE = "(no span)"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"
STEP_PROGRAM = "jit_spatial_step"


def merged(intervals) -> list:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def innermost(spans) -> list:
    """``spans``: ``(name, start, end)`` of ONE thread, nested by
    containment. Returns ``[(start, end, innermost name, outermost
    name)]``, disjoint and sorted: each span's self time, cut where a
    child covers it."""
    out: list = []
    stack: list = []  # (name, end)
    at = None

    def emit(upto):
        if stack and upto > at:
            out.append((at, upto, stack[-1][0], stack[0][0]))

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            at = max(at, stack.pop()[1])
        if stack:
            emit(start)
            end = min(end, stack[-1][1])  # a child never outlives its parent
        at = start
        stack.append((name, end))
    while stack:
        emit(stack[-1][1])
        at = max(at, stack.pop()[1])
    return out


def split_idle(device_op_intervals, host_spans, window):
    """Where the device's idle time went.

    ``device_op_intervals``: ``(start_ns, end_ns)`` of every operation on
    one device; ``host_spans``: ``(line, name, start_ns, end_ns)`` of
    every ``channeld/`` event of the host plane; ``window``: ``(start_ns,
    end_ns)``. Returns ``None`` where no line holds a ``channeld/tick.*``
    event; else seconds: ``idle_s``, its three parts ``global_tick_s``
    (the loop thread inside ``channeld/tick.GLOBAL``), ``channel_ticks_s``
    (inside any other ``channeld/tick.*``), ``unspanned_s`` (inside
    neither: callbacks, scheduling, sleep), ``by_span`` (idle seconds
    under each innermost span of the loop thread) and ``gaps``, the ten
    longest idle intervals, each with its start (``start_ns``, and
    ``at_s`` from the window's) and its own ``by_span``.
    """
    ticks_on: dict = {}
    for line, name, _, _ in host_spans:
        if name.startswith(TICK):
            ticks_on[line] = ticks_on.get(line, 0) + 1
    if not ticks_on:
        return None
    loop = max(ticks_on, key=ticks_on.get)
    w0, w1 = window
    busy = merged((max(s, w0), min(e, w1)) for s, e in device_op_intervals
                  if e > w0 and s < w1)
    edges = [w0] + [t for pair in busy for t in pair] + [w1]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    segments = innermost((name, s, e) for line, name, s, e in host_spans
                         if line == loop)
    starts = [seg[0] for seg in segments]

    by_span: dict = {}
    parts = {"global_tick_s": 0.0, "channel_ticks_s": 0.0, "unspanned_s": 0.0}
    gaps = []
    for a, b in idle:
        under: dict = {}
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s, e, inner, outer = segments[i]
            i += 1
            cut = min(e, b) - max(s, a)
            if cut <= 0:
                continue
            covered += cut
            under[inner] = under.get(inner, 0) + cut
            part = ("global_tick_s" if outer == GLOBAL_TICK else
                    "channel_ticks_s" if outer.startswith(TICK) else
                    "unspanned_s")
            parts[part] += cut / 1e9
        if b - a > covered:
            under[OUTSIDE] = b - a - covered
            parts["unspanned_s"] += (b - a - covered) / 1e9
        for name, ns in under.items():
            by_span[name] = by_span.get(name, 0.0) + ns / 1e9
        gaps.append((b - a, a, under))
    gaps.sort(key=lambda g: -g[0])
    ranked = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {
        "loop_line": loop,
        "idle_s": sum(b - a for a, b in idle) / 1e9,
        **parts,
        "by_span": ranked(by_span),
        "gaps": [{"start_ns": a, "at_s": (a - w0) / 1e9, "seconds": ns / 1e9,
                  "by_span": ranked({k: v / 1e9 for k, v in under.items()})}
                 for ns, a, under in gaps[:10]],
    }


def steps_inside(executions, host_spans) -> dict:
    """How many ``executions`` (``(start_ns, end_ns)`` of one program on
    the device) start after the start of a ``channeld/step.dispatch`` and
    end before the end of the ``channeld/step.fetch`` that follows it on
    the same line. On two clocks next to none would. ``bracketed`` leaves
    out the window's edges: an execution under way before the program
    had learnt of the session, or one whose fetch the session's end cut
    off, has no pair of spans to lie between."""
    by_line: dict = {}
    for line, name, s, e in host_spans:
        if name in (PREFIX + "step.dispatch", PREFIX + "step.fetch"):
            by_line.setdefault(line, []).append((s, e, name))
    brackets = []  # (dispatch start, end of the fetch that follows)
    for events in by_line.values():
        events.sort()
        for (s, _, name), nxt in zip(events, events[1:]):
            if name.endswith("dispatch") and nxt[2].endswith("fetch"):
                brackets.append((s, nxt[1]))
    brackets.sort()
    opened = [b[0] for b in brackets]
    inside = bracketed = 0
    for s, e in executions:
        i = bisect.bisect_right(opened, s) - 1
        inside += i >= 0 and e <= brackets[i][1]
        bracketed += bool(brackets) and s >= opened[0] and e <= brackets[-1][1]
    return {"program": STEP_PROGRAM, "executions": len(executions),
            "bracketed": bracketed, "inside_dispatch_to_fetch": inside}


def reduce_planes(planes) -> dict:
    """``planes`` as ``trace.read_planes`` gives them. The window is the
    one ``trace.reduce_planes`` takes: first to last event of any plane."""
    first = last = None
    for _, lines in planes:
        for _, events in lines:
            for _, start, dur in events:
                first = start if first is None else min(first, start)
                last = start + dur if last is None else max(last, start + dur)
    host_spans = [(i, name, s, s + d)
                  for plane, lines in planes if plane == HOST_PLANE
                  for i, (_, events) in enumerate(lines)
                  for name, s, d in events if name.startswith(PREFIX)]
    chips = [lines for plane, lines in planes if re.search(DEVICE_PLANE, plane)]
    if not host_spans or not chips or first is None:
        return {"host_spans": 0}
    splits, modules, executions = [], [], []
    for lines in chips:
        ops = [(s, s + d) for name, events in lines
               if re.search(OPS_LINE, name) for _, s, d in events]
        mods = sorted((s, s + d, re.sub(r"\(\d+\)$", "", name))
                      for line, events in lines
                      if re.search(MODULES_LINE, line)
                      for name, s, d in events)
        split = split_idle(ops or [m[:2] for m in mods], host_spans,
                           (first, last))
        if split is None:
            return {"host_spans": len(host_spans)}
        begun = [m[0] for m in mods]
        for gap in split["gaps"]:  # the names breakdown.idle_gaps gives it
            i = bisect.bisect_right(begun, gap["start_ns"]) - 1
            before = mods[i][2] if i >= 0 else "window start"
            after = mods[i + 1][2] if i + 1 < len(mods) else "window end"
            gap["between"] = f"{before} -> {after}"
        splits.append(split)
        executions += [m[:2] for m in mods if m[2] == STEP_PROGRAM]
    n = len(splits)
    out = dict(splits[0])  # gaps and by_span: the first chip's
    for key in ("idle_s", "global_tick_s", "channel_ticks_s", "unspanned_s"):
        out[key] = sum(s[key] for s in splits) / n
    out["host_spans"] = len(host_spans)
    out["window_s"] = (last - first) / 1e9
    out["clock"] = steps_inside(executions, host_spans)
    return out


# ---------------------------------------------------------------------------
# the readers' side
# ---------------------------------------------------------------------------

_read: dict = {}  # trace file -> what its reduction printed


def of_run(ctx: dict):
    """The reduction of this run's trace, made once for the three idle
    shares: ``None`` where the run was not traced, wrote no trace, or the
    gateway made no ``channeld/`` event."""
    if not ctx.get("trace"):
        return None
    found = glob.glob(os.path.join(ctx["base"], "out", "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _read:
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), path],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if done.returncode != 0:
            from benchmark.harness.gateway import BenchFailure

            raise BenchFailure("reading the host's spans failed:\n"
                               + done.stderr[-2000:])
        _read[path] = json.loads(done.stdout.strip().splitlines()[-1])
    out = _read[path]
    return out if out.get("idle_s") is not None else None


def idle_pct(ctx: dict, part: str):
    """``part`` of the device's idle time as a share of the traced window
    ``device_idle_pct`` is taken over. That window is the seconds the
    profiler was on, which the first and last event fall short of: the
    rest, when nothing at all was recorded, is idle under no span."""
    split = of_run(ctx)
    if split is None:
        return None
    window_s = ctx["trace"]["window_s"]
    seconds = split[part]
    if part == "unspanned_s":
        seconds += max(0.0, window_s - split["window_s"])
    return 100.0 * seconds / window_s


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness.trace import read_planes

    result = reduce_planes(read_planes(sys.argv[1]))
    if result.get("idle_s") is not None:
        with open(os.path.join(os.path.dirname(os.path.abspath(sys.argv[1])),
                               "idle_by_host_span.json"), "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
