"""Always-on flight recorder: tick-timeline tracing.

Aggregate Prometheus histograms answer "how slow was the gateway last
minute"; they cannot answer "where did THIS tick's budget go" or "what
happened to THAT handover as it crossed two gateways". The flight
recorder closes that gap as a permanent layer (CheetahGIS-style
streaming-spatial operation and Spider-style cross-node transactions
both presuppose correlated, low-overhead telemetry):

- **Fixed memory, lock-free on the hot path.** Spans live in per-thread
  ring buffers (``threading.local``; the asyncio runtime is effectively
  one writer per thread, so an index bump + list store is race-free).
  The ring never grows: overflow overwrites the OLDEST span and is
  counted exactly (``dropped``), so the recorder always holds the
  newest ticks — flight-recorder semantics, not a log.
- **Tick-scoped, sampling-free.** Every span is stamped with the
  current GLOBAL tick number (``set_tick`` from the GLOBAL channel
  tick). Tick-scoped stages are few per tick (ingest drain, message
  dispatch, fan-out encode, device step, readback, handover
  orchestration, trunk I/O), so recording each one costs two
  ``monotonic_ns`` reads and a ring store (~100-200ns) — cheap enough
  to never sample.
- **Trace ids across gateways.** A cross-gateway handover or client
  redirect carries its trace id over the trunk (``traceId`` on
  TrunkHandoverPrepare/Ack/StageRedirect), so one id stitches spans
  from both gateways' recorders into a single reconstructible trace.
- **One clock with the device.** While a ``jax.profiler`` session is
  live (``recorder.profiling``, refreshed once per GLOBAL tick), every
  ``region()`` is also a ``jax.profiler.TraceAnnotation`` named
  ``channeld/<span>``: the profiler stamps it itself, beside the
  device's XLA ops, on the line of the thread that made it. The ring's
  own stamps are ``monotonic_ns`` and cannot be laid over a device
  trace; the annotations can.
- **Three exits**: ``dump_trace()`` writes Chrome/Perfetto
  ``trace_event`` JSON (open in ui.perfetto.dev or chrome://tracing —
  the same story as ``-profile tpu``); anomalies (tick-budget blow,
  overload transition, handover/migration abort, failover epoch)
  freeze the ring and auto-dump the last N ticks, counted in
  ``trace_dumps_total{trigger}``; and per-stage cost feeds the
  ``tick_stage_ms{stage}`` histograms whether or not span recording is
  enabled.

See doc/observability.md.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from typing import Optional

from ..utils.logger import get_logger
from .affinity import affinity as _affinity

logger = get_logger("tracing")

# Span kinds (trace_event "ph" values).
_COMPLETE = "X"
_INSTANT = "i"

_trace_counter = itertools.count(1)
_dump_counter = itertools.count(1)

# ``jax.profiler.TraceAnnotation``, bound on first need. It is looked
# for only in a process that has imported jax already: no other can hold
# a profiler session, and core/ must load where jax is absent.
_annotation = None


def _profiler_live() -> bool:
    """Whether a ``jax.profiler`` session is recording right now,
    whoever opened it (``TraceMe.is_enabled()``, ~20ns)."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation as _annotation
    return _annotation.is_enabled()


def new_trace_id(prefix: str = "") -> str:
    """Process-unique trace id; ``prefix`` ties it to an origin (e.g.
    the federation gateway id) so a stitched cross-gateway trace shows
    where it started."""
    return f"{prefix or 'g'}-{os.getpid():x}-{next(_trace_counter):x}"


class _Ring:
    """Fixed-capacity span store for ONE writer thread. Overflow
    overwrites the oldest entry and bumps ``dropped`` — the recorder
    keeps the newest spans with exact drop accounting."""

    __slots__ = ("buf", "cap", "idx", "count", "dropped", "tid")

    def __init__(self, cap: int, tid: int):
        self.cap = cap
        self.buf: list = [None] * cap
        self.idx = 0  # next write position
        self.count = 0  # live entries (<= cap)
        self.dropped = 0  # entries overwritten by wrap
        self.tid = tid

    def put(self, entry: tuple) -> None:
        i = self.idx
        # Entry lands BEFORE the count bump: a cross-thread snapshot
        # reading buf[:count] must never see a not-yet-stored slot.
        self.buf[i] = entry
        if self.count == self.cap:
            self.dropped += 1
        else:
            self.count += 1
        self.idx = (i + 1) % self.cap

    def snapshot(self) -> list:
        """Entries oldest-first (freeze-and-copy; O(cap))."""
        if self.count < self.cap:
            return [e for e in self.buf[: self.count]]
        return self.buf[self.idx:] + self.buf[: self.idx]


class _Region:
    """One ``recorder.region()``: the block it wraps is a ring span (and
    a ``tick_stage_ms`` observation when it is a stage) and, while a
    profiler session is live, a ``channeld/<name>`` annotation around
    both clock reads, so containment holds in either sink."""

    __slots__ = ("rec", "name", "lane", "stage", "start_ns", "ann", "void")

    def __init__(self, rec: "FlightRecorder", name: str, lane: int,
                 stage: bool):
        self.rec = rec
        self.name = name
        self.lane = lane
        self.stage = stage
        self.ann = None
        self.void = False

    def discard(self) -> None:
        """The block found that it had nothing to do (a held device
        tick): leave no span and no observation, as a site that records
        after the fact would not have."""
        self.void = True

    def __enter__(self) -> "_Region":
        if self.rec.profiling:
            ann = self.ann = _annotation("channeld/" + self.name)
            ann.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur_ns = time.monotonic_ns() - self.start_ns
        if self.void:
            self.void = False
        else:
            if self.stage:
                _stage_ms(self.name).observe(dur_ns / 1e6)
            rec = self.rec
            if rec.enabled:
                rec._ring().put((
                    _COMPLETE, self.name, self.lane, self.start_ns, dur_ns,
                    rec.tick, None,
                ))
        ann = self.ann
        if ann is not None:
            self.ann = None
            ann.__exit__(exc_type, exc, tb)


class FlightRecorder:
    """Process-wide recorder (one instance: ``recorder``).

    Hot-path contract: a site that has a block to wrap uses
    ``with recorder.region(name)``; one that only learns afterwards that
    there was something to time uses ``now()`` + ``span()`` /
    ``stage()`` / ``instant()`` and guards on ``recorder.enabled`` (one
    attribute load while disabled). Entries are tuples
    ``(kind, name, lane, start_ns, dur_ns, tick, trace_id)``.
    """

    def __init__(self):
        self._local = threading.local()
        self._rings: dict[int, _Ring] = {}
        self._rings_lock = threading.Lock()
        self.configure()

    # ---- configuration ---------------------------------------------------

    def configure(
        self,
        enabled: bool = True,
        ring_spans: int = 8192,
        dump_ticks: int = 200,
        dump_path: str = "profiles",
        anomaly_cooldown_s: float = 5.0,
        origin: str = "",
    ) -> None:
        self.enabled = enabled
        self.ring_spans = max(16, int(ring_spans))
        self.dump_ticks = max(1, int(dump_ticks))
        self.dump_path = dump_path
        self.anomaly_cooldown_s = anomaly_cooldown_s
        self.origin = origin
        self.tick = 0
        # A jax.profiler session is live (set_tick refreshes it).
        self.profiling = False
        self.anomalies: list[dict] = []
        self._last_dump_at = -1e9
        with self._rings_lock:
            self._rings.clear()
        self._local = threading.local()
        self._epoch_ns = time.monotonic_ns()

    def reset(self) -> None:
        """Test hook: drop every ring and restore defaults."""
        self.configure()

    # ---- hot path --------------------------------------------------------

    @staticmethod
    def now() -> int:
        return time.monotonic_ns()

    def _ring(self) -> _Ring:
        ring = getattr(self._local, "ring", None)
        if ring is None:
            ring = _Ring(self.ring_spans, threading.get_ident())
            self._local.ring = ring
            with self._rings_lock:
                self._rings[ring.tid] = ring
        return ring

    def span(self, name: str, start_ns: int, lane: int = 0,
             trace: Optional[str] = None,
             end_ns: Optional[int] = None) -> None:
        """Record one complete span that began at ``start_ns`` (from
        :meth:`now`) and ends now (or at ``end_ns``)."""
        if not self.enabled:
            return
        if end_ns is None:
            end_ns = time.monotonic_ns()
        self._ring().put((
            _COMPLETE, name, lane, start_ns, end_ns - start_ns,
            self.tick, trace,
        ))

    def instant(self, name: str, lane: int = 0,
                trace: Optional[str] = None) -> None:
        if not self.enabled:
            return
        self._ring().put((
            _INSTANT, name, lane, time.monotonic_ns(), 0, self.tick, trace,
        ))

    def stage(self, stage: str, start_ns: int, lane: int = 0,
              trace: Optional[str] = None,
              end_ns: Optional[int] = None) -> None:
        """A named per-tick stage: records the span AND observes the
        ``tick_stage_ms{stage}`` histogram (the histogram moves even
        with span recording disabled, so live dashboards keep their
        per-stage budgets either way). ``end_ns`` overrides "now" for
        aggregated stages (e.g. the per-follower readback total)."""
        if end_ns is None:
            end_ns = time.monotonic_ns()
        _stage_ms(stage).observe((end_ns - start_ns) / 1e6)
        if self.enabled:
            self._ring().put((
                _COMPLETE, stage, lane, start_ns, end_ns - start_ns,
                self.tick, trace,
            ))

    def region(self, name: str, lane: int = 0,
               stage: bool = False) -> _Region:
        """Context manager around the work itself: on exit it does what
        :meth:`span` (``stage=True``: :meth:`stage`) does, and while a
        profiler session is live the block is also the annotation
        ``channeld/<name>`` in the device trace."""
        return _Region(self, name, lane, stage)

    def set_tick(self, tick: int) -> None:
        """Stamp subsequent spans with the GLOBAL tick number, and look
        whether a profiler session is live (called once per GLOBAL
        tick, so regions follow any session within one tick)."""
        _affinity.expect("tick-loop")
        self.tick = tick
        self.profiling = _profiler_live()

    # ---- introspection ---------------------------------------------------

    def stats(self) -> dict:
        with self._rings_lock:
            rings = list(self._rings.values())
        return {
            "enabled": self.enabled,
            "rings": len(rings),
            "spans": sum(r.count for r in rings),
            "dropped": sum(r.dropped for r in rings),
            "tick": self.tick,
            "anomalies": len(self.anomalies),
        }

    def _freeze(self) -> list:
        """``[(tid, entries oldest-first)]``: the raw copy of every
        ring, all an anomaly costs the thread that tripped it."""
        with self._rings_lock:
            rings = list(self._rings.values())
        return [(ring.tid, ring.snapshot()) for ring in rings]

    def snapshot(self, last_ticks: Optional[int] = None) -> list[dict]:
        """Freeze every ring and return span dicts (oldest-first per
        ring), optionally restricted to the last N ticks."""
        floor = None
        if last_ticks is not None:
            floor = self.tick - last_ticks + 1
        out: list[dict] = []
        for tid, entries in self._freeze():
            for e in entries:
                kind, name, lane, start_ns, dur_ns, tick, trace = e
                if floor is not None and tick < floor:
                    continue
                d = {
                    "kind": kind, "name": name, "lane": lane,
                    "start_ns": start_ns, "dur_ns": dur_ns, "tick": tick,
                    "tid": tid,
                }
                if trace is not None:
                    d["trace"] = trace
                out.append(d)
        out.sort(key=lambda d: d["start_ns"])
        return out

    # ---- dumps -----------------------------------------------------------

    def _dump_path(self, trigger: str) -> str:
        """profiles/trace_<trigger>_<stamp>.<seq>_<pid>.json — the seq
        component keeps same-second dumps (sub-second anomaly cooldowns,
        back-to-back SIGUSR2s) from overwriting each other."""
        os.makedirs(self.dump_path, exist_ok=True)
        stamp = time.strftime("%Y%m%d%H%M%S")
        seq = next(_dump_counter)
        return os.path.join(
            self.dump_path,
            f"trace_{trigger}_{stamp}.{seq}_{os.getpid()}.json",
        )

    def to_trace_events(self, spans: list[dict]) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON object for ``spans``
        (as returned by :meth:`snapshot`): the dumps' format, as an
        object. The dumps themselves are written by :meth:`_render`."""
        pid = os.getpid()
        events = []
        # One timeline row per (thread, lane): channel ticks get their
        # own rows, the default lane groups the rest. Row ids are
        # allocated per dump (first-seen order) — spatial channel ids
        # start at 0x10000, so any arithmetic fold would collide
        # distinct channels onto one row and render false nesting.
        rows: dict[tuple, int] = {}
        for s in spans:
            ts_us = (s["start_ns"] - self._epoch_ns) / 1e3
            ev = {
                "name": s["name"],
                "ph": s["kind"],
                "ts": ts_us,
                "pid": pid,
                "tid": rows.setdefault((s["tid"], s["lane"]), len(rows)),
                "args": {"tick": s["tick"], "lane": s["lane"]},
            }
            if s["kind"] == _COMPLETE:
                ev["dur"] = s["dur_ns"] / 1e3
            else:
                ev["s"] = "t"  # instant scope: thread
            if "trace" in s:
                ev["args"]["trace"] = s["trace"]
            events.append(ev)
        with self._rings_lock:
            # The anomaly path calls this from its off-thread writer; a
            # writer thread registering its first ring mid-iteration
            # must not kill the dump with dict-changed-size.
            dropped = sum(r.dropped for r in self._rings.values())
        meta = {
            "origin": self.origin or f"pid:{pid}",
            "tick": self.tick,
            "dropped": dropped,
        }
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": meta,
        }

    def _render(self, frozen: list, floor: Optional[int],
                extra: dict) -> tuple[str, int]:
        """``(text, events)``: what ``json.dumps`` makes of
        :meth:`to_trace_events` over ``frozen``'s entries from tick
        ``floor`` on, with ``extra`` added to ``otherData`` — written
        straight from the ring tuples. An anomaly dump is ~9,000 events;
        as dicts they cost the interpreter ~90 ms and 25,000 objects for
        the collector to walk, on a loop that has no idle time to lend
        (PERF.md, PR 25); as text ~15 ms and none."""
        picked = [(tid, e) for tid, entries in frozen for e in entries
                  if floor is None or e[5] >= floor]
        picked.sort(key=lambda te: te[1][3])  # stable, like snapshot()'s
        pid, epoch = os.getpid(), self._epoch_ns
        rows: dict[tuple, int] = {}
        quoted: dict[str, str] = {}
        events = []
        for tid, (kind, name, lane, start_ns, dur_ns, tick, trace) in picked:
            q = quoted.get(name)
            if q is None:
                q = quoted[name] = json.dumps(name)
            row = rows.setdefault((tid, lane), len(rows))
            args = f'{{"tick": {tick}, "lane": {lane}'
            if trace is not None:
                args += f', "trace": {json.dumps(trace)}'
            last = (f'"dur": {dur_ns / 1e3!r}' if kind == _COMPLETE
                    else '"s": "t"')
            events.append(
                f'{{"name": {q}, "ph": "{kind}", '
                f'"ts": {(start_ns - epoch) / 1e3!r}, "pid": {pid}, '
                f'"tid": {row}, "args": {args}}}, {last}}}')
        meta = self.to_trace_events([])["otherData"]
        meta.update(extra)
        return ('{"traceEvents": [' + ", ".join(events)
                + '], "displayTimeUnit": "ms", "otherData": '
                + json.dumps(meta) + "}"), len(events)

    def dump_trace(self, path: Optional[str] = None,
                   last_ticks: Optional[int] = None,
                   trigger: str = "manual") -> str:
        """Write the ring contents as Perfetto JSON; returns the path.
        Counted in ``trace_dumps_total{trigger}`` like the anomaly
        path, so manual/sigusr2/shutdown dumps show on /metrics too."""
        from . import metrics

        metrics.trace_dumps.labels(trigger=trigger).inc()
        floor = None if last_ticks is None else self.tick - last_ticks + 1
        text, events = self._render(self._freeze(), floor,
                                    {"trigger": trigger})
        if path is None:
            path = self._dump_path(trigger)
        with open(path, "w") as f:
            f.write(text)
        logger.info("flight-recorder trace (%s, %d events) -> %s",
                    trigger, events, path)
        return path

    def note_anomaly(self, trigger: str, detail: str = "",
                     force: bool = False) -> Optional[str]:
        """An anomalous tick: count it, and (cooldown permitting) freeze
        the ring and auto-dump the last ``dump_ticks`` ticks. Returns
        the dump path when one was written. A disabled recorder is a
        full no-op — call sites guard on ``recorder.enabled`` and this
        matches them: ``-trace false`` means no anomaly accounting at
        all, not a metric without dumps. ``force`` skips the cooldown
        CHECK (the window still resets): for triggers that are rare by
        construction AND must always ship a timeline — an SLO breach
        (core/slo.py: rising-edge + min-events gated) would otherwise
        lose its dump slot to a storm of per-tick tick_budget anomalies
        on a saturated box. Only the raw ring copy is synchronous (the
        ``trace_freeze`` stage); the tick filter, the sort and the JSON
        (:meth:`_render`) run on a daemon thread, so the tick that
        tripped the anomaly is not widened by its own dump."""
        if not self.enabled:
            return None
        from . import metrics

        metrics.trace_dumps.labels(trigger=trigger).inc()
        record = {"trigger": trigger, "detail": detail, "tick": self.tick,
                  "t": time.monotonic()}
        self.anomalies.append(record)
        del self.anomalies[:-256]  # bounded like everything else here
        now = time.monotonic()
        if not force and now - self._last_dump_at < self.anomaly_cooldown_s:
            return None
        self._last_dump_at = now
        # Only the ring freeze (list slices) runs on the tick path;
        # filtering, sorting, JSON and disk all happen off-thread — an
        # anomaly dump must never widen the very tick it is recording.
        with self.region("trace_freeze", stage=True):
            frozen = self._freeze()
            floor = self.tick - self.dump_ticks + 1
            path = self._dump_path(trigger)
        record["path"] = path

        def _write():
            _affinity.enter("trace-dumper")
            try:
                text, events = self._render(
                    frozen, floor, {"trigger": trigger, "detail": detail})
                with open(path, "w") as f:
                    f.write(text)
                logger.warning(
                    "anomaly %s (%s): last %d ticks (%d spans) frozen -> %s",
                    trigger, detail or "-", self.dump_ticks, events, path,
                )
            except OSError as e:  # pragma: no cover - disk trouble
                logger.error("anomaly dump failed: %s", e)

        threading.Thread(target=_write, daemon=True,
                         name=f"trace-dump-{trigger}").start()
        return path


# Cached per-stage histogram children (label resolution is dict work;
# stages are a small fixed set, so resolve each once).
_stage_children: dict = {}


def _stage_ms(stage: str):
    child = _stage_children.get(stage)
    if child is None:
        from . import metrics

        child = metrics.tick_stage_ms.labels(stage=stage)
        _stage_children[stage] = child
    return child


recorder = FlightRecorder()


def open_device_trace(path: str) -> None:
    """Start a ``jax.profiler`` trace into ``path``: the device's lines,
    XLA's host lines and, from the next GLOBAL tick on, every recorder
    region as ``channeld/<span>``. The one place in the program that
    knows how a device trace is opened; the Python tracer stays off (it
    would stamp every call of every tick)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # level-1 TraceMes: the annotations
    jax.profiler.start_trace(path, profiler_options=options)


def close_device_trace() -> None:
    """Stop the trace :func:`open_device_trace` started and write it."""
    import jax

    jax.profiler.stop_trace()


def configure_from_settings() -> None:
    """Apply the -trace* flags (run_server boot path)."""
    from .settings import global_settings as st

    recorder.configure(
        enabled=st.trace_enabled,
        ring_spans=st.trace_ring_spans,
        dump_ticks=st.trace_dump_ticks,
        dump_path=st.profile_path,
        anomaly_cooldown_s=st.trace_anomaly_cooldown_s,
        origin=st.federation_gateway_id,
    )


def install_trace_dump_signal() -> bool:
    """Bind SIGUSR2 to a manual flight-recorder dump: ``kill -USR2
    <pid>`` freezes the ring and writes the full timeline as Perfetto
    JSON (path logged). Installed at server start; False where SIGUSR2
    does not exist or outside the main thread."""
    import signal

    def _on_sigusr2(signum, frame) -> None:
        recorder.dump_trace(trigger="sigusr2")

    sig = getattr(signal, "SIGUSR2", None)
    if sig is None:
        return False
    try:
        signal.signal(sig, _on_sigusr2)
    except ValueError:
        return False  # not the main thread
    return True


# The collector's pauses, by generation: [milliseconds, pauses, start and
# end of the newest, monotonic ns]. ``_on_gc`` writes them and nothing
# else does; ``flush_gc_pauses`` keeps what it has carried to /metrics
# in ``_gc_carried``, so neither side zeroes what the other adds to.
_gc_pauses = {1: [0.0, 0, 0, 0], 2: [0.0, 0, 0, 0]}
_gc_carried = {1: [0.0, 0], 2: [0.0, 0]}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry. It runs inside the collector, on
    whichever thread tripped it, maybe under a lock that thread holds
    (the /metrics thread allocates under the registry's): so it touches
    no registry and no ring, it adds into plain numbers. Generation 0
    runs hundreds of times a second and takes microseconds: for it this
    returns at once."""
    generation = info["generation"]
    if generation == 0:
        return
    pauses = _gc_pauses[generation]
    if phase == "start":
        pauses[2] = time.monotonic_ns()
    elif pauses[2]:
        pauses[3] = time.monotonic_ns()
        pauses[0] += (pauses[3] - pauses[2]) / 1e6
        pauses[1] += 1


def flush_gc_pauses() -> None:
    """Carry the collector's pauses to ``gc_pause_ms{generation}``, and
    the newest of each generation to the ring as a ``gc.gen<n>`` span
    (the GLOBAL tick, core/channel.py ``_flush_wait_counters``)."""
    from . import metrics

    for generation, pauses in _gc_pauses.items():
        carried = _gc_carried[generation]
        total_ms, count = pauses[0], pauses[1]
        if count == carried[1]:
            continue
        metrics.gc_pause_ms.labels(generation=str(generation)).add(
            total_ms - carried[0], count - carried[1])
        carried[0], carried[1] = total_ms, count
        recorder.span(f"gc.gen{generation}", pauses[2], end_ns=pauses[3])


def install_gc_callback() -> None:
    """Put the collector's pauses on the record (run_server boot path;
    idempotent)."""
    from . import metrics

    for generation in _gc_pauses:  # on /metrics before the first pause
        metrics.gc_pause_ms.labels(generation=str(generation))
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def register_shutdown_dump() -> None:
    """Dump the ring on process exit (run_server boot path only — a
    library embedding must opt in, or every pytest run would write
    profiles/)."""
    import atexit

    def _on_exit() -> None:
        if recorder.enabled and any(
            r.count for r in recorder._rings.values()
        ):
            recorder.dump_trace(trigger="shutdown")

    atexit.register(_on_exit)


def reset_tracing() -> None:
    """Test hook."""
    recorder.reset()
