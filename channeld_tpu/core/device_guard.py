"""Device supervision & in-process engine recovery (doc/device_recovery.md).

Every resilience plane before this one (chaos, overload, failover,
balancer, federation, global control) assumed the device engine itself
never fails: an XLA error, a hung dispatch, or silently corrupted device
state in ``SpatialEngine.tick()`` would propagate up through
``channel.tick_once`` and take down the whole gateway — stranding its
shard until the fleet's death declaration adopts it. This module makes a
single-chip fault a local, bounded event instead:

- **Watchdog.** The guarded step runs on a dedicated worker thread and
  the tick waits at most ``device_step_deadline_s`` (the jax call
  blocks, so hang detection must be off-thread). The step is two halves
  around that wait: ``begin_step`` (state checks, staging, submit) and
  ``finish_step`` (classification, sentinel, recovery). The GLOBAL
  channel's tick task *awaits* between them (``await_step``), so the
  loop serves every other channel while the worker and the chip run the
  step; a direct caller blocks (``wait_step``; ``run_step`` is the three
  in a row). A timed-out step is
  abandoned: the engine's generation fence is bumped so the zombie
  worker can never commit its tail state over a rebuilt engine, the
  worker pool is discarded, and the failure is FATAL (a wedged chip
  does not get better by retrying into it).

- **Classification.** Step exceptions are transient-vs-fatal:
  transient (queue pressure, allocator hiccups — the retryable XLA
  status codes) retries with exponential backoff up to
  ``device_retry_max`` attempts while the gateway degrades; anything
  else, an exhausted retry budget, a hang, or a sentinel hit is fatal.

- **Corruption sentinel.** NaN/out-of-range device rot is caught from
  the *already-fetched* batched readback arrays — the handover rows,
  the handover count, the due bitmap — with pure-host range checks. No
  new device->host transfers are added (tpulint's hot-readback rule
  stays clean): a NaN position maps outside the world and a rotted cell
  baseline surfaces as an impossible src cell in a crossing row, which
  is exactly what the checks pin.

- **In-process rebuild.** On a fatal failure the engine is rebuilt from
  the host-side shadow: the entity registry, query params and sub
  intervals are already authoritative on host, and the per-slot cell
  baselines are re-seeded from the grid's ``_data_cell`` placement
  ledger with the failover journal's in-flight dsts outranking it (a
  mid-crossing entity re-baselines to where its data is actually
  bound). The rebuilt arrays are verified bit-identical against the
  shadow before the gateway resumes device service; entities that
  moved during the outage re-detect their crossings from the reseeded
  baseline, so nothing is lost or duplicated.

While the engine is down the gateway *degrades instead of dying*:
``run_step`` returns None, the controller holds device-dependent work
(due fan-out decisions, crossing orchestration, follower passes), the
overload ladder is pinned to L2+ (shedding outranks a dead engine), and
the flight recorder freezes an anomaly dump at the failure tick. A
fatal failure and a completed rebuild each write an immediate snapshot
through the shared fsync'd ``write_snapshot`` path, so a crash during
recovery still boot-restores to the newest state.

Every recovery is counted twice on purpose — the
``device_recoveries_total{cause}`` counter AND the guard's python-side
ledger — so ``scripts/device_soak.py`` proves the accounting exact.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import time
from enum import IntEnum
from typing import Optional

import numpy as np

from ..chaos.injector import chaos as _chaos
from ..utils.logger import get_logger
from .affinity import affinity as _affinity
from .settings import global_settings
from .tracing import recorder as _trace

logger = get_logger("device_guard")


class DeviceState(IntEnum):
    ACTIVE = 0  # serving
    DEGRADED = 1  # transient step failure; retrying with backoff
    REBUILDING = 2  # fatal failure; in-process rebuild in progress
    FAILED = 3  # the rebuild itself failed; retrying on a backoff


class DeviceStepError(RuntimeError):
    """A device step failure with an explicit transient/fatal tag (used
    by the chaos injection and available to engine wrappers)."""

    def __init__(self, message: str, transient: bool = False):
        super().__init__(message)
        self.transient = transient


class StepInFlight:
    """One guarded step between its halves: the staged batch the worker
    runs and the future it answers on."""

    __slots__ = ("engine", "batch", "fut")

    def __init__(self, engine, batch, fut):
        self.engine = engine
        self.batch = batch
        self.fut = fut


# Substrings of the retryable XLA/jax status families. Real runtime
# errors surface as RuntimeError/XlaRuntimeError with the status name in
# the message; everything NOT matching is treated as fatal — when in
# doubt, rebuild (a wrong "transient" guess burns the whole retry budget
# inside a corrupted engine).
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "ABORTED",
    "DEADLINE_EXCEEDED",
)


def classify_failure(exc: BaseException) -> str:
    """'transient' or 'fatal' for one device-step exception."""
    if isinstance(exc, DeviceStepError):
        return "transient" if exc.transient else "fatal"
    text = str(exc)
    if any(marker in text for marker in _TRANSIENT_MARKERS):
        return "transient"
    return "fatal"


class DeviceGuard:
    """Process-wide device supervision state machine (one instance:
    ``guard``). The TPU spatial controller routes its per-tick engine
    step through :meth:`run_step`; everything else reads state."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.state = DeviceState.ACTIVE
        # Python-side recovery ledger; must match
        # device_recoveries_total exactly (the soak cross-checks).
        self.recovery_counts: dict[str, int] = {}
        self.failure_counts: dict[str, int] = {}
        self.events: list[dict] = []
        self.held_ticks = 0
        self.recovery_times_s: list[float] = []
        self._retry_count = 0
        self._not_before = 0.0
        self._rebuild_attempts = 0
        self._rebuild_fut: Optional[concurrent.futures.Future] = None
        self._rebuild_t0 = 0.0
        self._failed_at: Optional[float] = None
        self._fatal_cause = ""
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._started = time.monotonic()
        self._publish_state()

    # ---- plumbing --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return global_settings.device_guard_enabled

    def _publish_state(self) -> None:
        try:  # lazy: metrics must not be a module-load dependency
            from . import metrics

            metrics.device_state.set(int(self.state))
        except Exception:
            pass

    def _set_state(self, state: DeviceState) -> None:
        if state == self.state:
            return
        old = self.state
        self.state = state
        self.events.append({
            "t": round(time.monotonic() - self._started, 3),
            "from": old.name,
            "to": state.name,
        })
        log = logger.info if state == DeviceState.ACTIVE else logger.warning
        log("device state %s -> %s", old.name, state.name)
        self._publish_state()

    def _count_recovery(self, cause: str) -> None:
        """Double-entry recovery accounting: python ledger AND the
        prometheus counter move together, always."""
        self.recovery_counts[cause] = self.recovery_counts.get(cause, 0) + 1
        from . import metrics

        metrics.device_recoveries.labels(cause=cause).inc()

    def _count_failure(self, cause: str) -> None:
        self.failure_counts[cause] = self.failure_counts.get(cause, 0) + 1
        from . import metrics

        metrics.device_step_failures.labels(cause=cause).inc()

    def _executor(self) -> concurrent.futures.ThreadPoolExecutor:
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="device-step"
            )
        return self._pool

    def _abandon_executor(self) -> None:
        """Give up on a hung worker: the pool (and its stuck thread) is
        discarded without waiting; the next step gets a fresh one."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def shutdown(self) -> None:
        """Test/teardown hook: release the worker thread."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # ---- the guarded step ------------------------------------------------

    def run_step(self, controller) -> Optional[dict]:
        """Run one supervised engine step for ``controller``
        (TPUSpatialController) and block for it: the two halves in a
        row, for callers that are not the GLOBAL channel's tick task.
        Returns the step result with the batched readback arrays already
        materialized on host — or None while the engine is down/held
        (the controller must hold all device-dependent work for that
        tick)."""
        step = self.begin_step(controller)
        if step is None:
            return None
        self.wait_step(step)
        return self.finish_step(controller, step)

    def begin_step(self, controller) -> Optional[StepInFlight]:
        """First half, on the loop thread: the state machine's checks,
        the chaos gate, staging (``engine.stage_step``: dirty sets taken,
        rows gathered) and the submit to the worker. None = no step was
        made this tick (engine down/held, or a rebuild was driven)."""
        # Affinity: the guard's state machine is loop-thread-only; all
        # device waits happen on the worker via _step_body.
        _affinity.expect("tick-loop")
        now = time.monotonic()
        if self.state != DeviceState.ACTIVE:
            if now < self._not_before:
                self.held_ticks += 1
                return None
            if self.state in (DeviceState.REBUILDING, DeviceState.FAILED):
                self._attempt_rebuild(controller)
                self.held_ticks += 1
                return None  # serve again from the NEXT tick
            # DEGRADED: backoff elapsed — retry the step below.
        engine = controller.engine
        if _chaos.armed and _chaos.fire("device.nan"):
            # Chaos: silent device-state rot (NaN positions + garbage
            # cell baselines). Planted BEFORE the step so the sentinel
            # must catch it from the ordinary readback, exactly like a
            # real bit-flip would have to be caught.
            engine.corrupt_device_state_for_chaos()
        batch = engine.stage_step()
        fut = self._executor().submit(self._step_body, engine, batch)
        _affinity.step_flight(True)
        return StepInFlight(engine, batch, fut)

    def wait_step(self, step: StepInFlight) -> None:
        """Block the calling thread until the step is done or its
        deadline passes (``finish_step`` tells which)."""
        concurrent.futures.wait(
            [step.fut],
            timeout=max(global_settings.device_step_deadline_s, 0.001),
        )

    async def await_step(self, step: StepInFlight) -> None:
        """The same wait for the GLOBAL channel's tick task: the loop
        runs the other channels' ticks, reads and writes meanwhile. On
        the deadline the worker's future stays as it is (a running step
        cannot be cancelled): ``finish_step`` fences and abandons it.
        Awaited bare under ``wait_for``, not through ``asyncio.wait``:
        each future between the worker and this task is one more trip
        through the loop's ready queue, and on a saturated loop a trip
        is a whole round of callbacks (PERF.md, PR 26)."""
        try:
            await asyncio.wait_for(
                asyncio.wrap_future(step.fut),
                max(global_settings.device_step_deadline_s, 0.001),
            )
        except asyncio.CancelledError:
            _affinity.step_flight(False)  # nobody finishes it
            step.engine.end_flight(step.batch)
            raise
        except Exception:
            # The deadline (the step still runs: a hang) or the step's
            # own error: finish_step tells them apart and classifies.
            pass

    def finish_step(self, controller, step: StepInFlight) -> Optional[dict]:
        """Second half, on the loop thread, once the wait is over: a
        step still running is a hang; one that raised is retried or
        rebuilt; one that answered passes the sentinel."""
        _affinity.expect("tick-loop")
        _affinity.step_flight(False)
        engine, fut = step.engine, step.fut
        churn = engine.end_flight(step.batch)
        if not fut.done() or fut.cancelled():
            # (Cancelled: the deadline passed with the step still queued
            # behind the worker.) Fence first, then abandon: the zombie
            # re-checks the generation before touching the engine and
            # before committing its tail state (ops/engine.py
            # run_staged). Its batch is handed back like a failed
            # step's: the rebuild uploads every table whole from the
            # host mirrors anyway, and nothing depends on that here.
            engine.bump_generation()
            self._abandon_executor()
            engine.restage(step.batch)
            fut.add_done_callback(_log_zombie)
            self._count_failure("hang")
            logger.error(
                "device step exceeded the %.2fs watchdog deadline; "
                "abandoning the worker and rebuilding",
                global_settings.device_step_deadline_s,
            )
            self._enter_fatal(controller, "hang")
            return None
        try:
            result = fut.result(timeout=0)  # done: checked above
        except Exception as exc:
            # What the failed step never committed goes back to the
            # dirty sets: the retry (or the rebuild) carries it.
            engine.restage(step.batch)
            self._count_failure("step_error")
            if (
                classify_failure(exc) == "transient"
                and self._retry_count < global_settings.device_retry_max
            ):
                self._retry_count += 1
                backoff = (
                    global_settings.device_retry_backoff_ms / 1000.0
                ) * (2 ** (self._retry_count - 1))
                now = time.monotonic()
                self._not_before = now + backoff
                if self._failed_at is None:
                    self._failed_at = now
                logger.warning(
                    "transient device step failure (%r); retry %d/%d "
                    "in %.0fms", exc, self._retry_count,
                    global_settings.device_retry_max, backoff * 1000.0,
                )
                self._set_state(DeviceState.DEGRADED)
                self._pin_ladder()
                return None
            self._enter_fatal(controller, "step_error")
            return None
        corrupt = self._sentinel(engine, result)
        if corrupt:
            self._count_failure("corruption")
            logger.error("device readback sentinel: %s; rebuilding",
                         corrupt)
            self._enter_fatal(controller, "corruption")
            return None
        if self.state == DeviceState.DEGRADED:
            # A retried step came back clean: transient recovery,
            # no rebuild needed.
            self._finish_recovery("transient")
        self._retry_count = 0
        if churn is not None:
            # Slots and rows that changed owner during the flight: the
            # result's consumers leave them out (ops/engine.py
            # StepChurn).
            result["churn"] = churn
        return result

    @staticmethod
    def _step_body(engine, batch) -> dict:
        """Worker-thread body: chaos gates, the engine step from its
        staged batch, and the batched readback fetch — ALL device waits
        happen here so the watchdog deadline covers dispatch and
        transfer alike."""
        _affinity.enter("device-worker")
        if _chaos.armed:
            stall = _chaos.stall_s("device.step_hang")
            if stall:
                # Models a wedged dispatch: the blocking sleep stands in
                # for a jax call that never completes within deadline.
                time.sleep(stall)
            if _chaos.fire("device.step_error"):
                raise DeviceStepError(
                    "chaos: injected device step error "
                    "(RESOURCE_EXHAUSTED)", transient=True,
                )
        if batch.gen != engine.generation:
            # This step was abandoned while the chaos stall (or a real
            # queue wait) held the worker: never touch the engine.
            raise RuntimeError("stale device tick abandoned by watchdog")
        result = engine.run_staged(batch)
        # The per-tick batched readbacks, fetched ONCE inside the
        # guarded window (a hung transfer is a hang, not a mystery
        # stall in the controller) and handed on as numpy so the
        # controller's handover_list/_publish_due add no new transfers.
        # The first of them blocks until the passes have run, so
        # ``step.fetch`` is the wait for the chip plus the transfer.
        with _trace.region("step.fetch", stage=True):
            result["handovers"] = np.asarray(result["handovers"])  # tpulint: disable=hot-readback -- THE designed once-per-tick batched fetch; downstream reuses these arrays
            result["handover_count"] = int(result["handover_count"])  # tpulint: disable=hot-readback -- rides the same designed per-tick fetch as the rows above
            result["due_packed"] = np.asarray(result["due_packed"])  # tpulint: disable=hot-readback -- rides the same designed per-tick fetch as the rows above
            if result.get("query_blob") is not None:
                result["query_blob"] = np.asarray(result["query_blob"])  # tpulint: disable=hot-readback -- the standing-query plane's ONE changed-rows transfer, pre-fetched inside the guarded window (doc/query_engine.md)
        if result.get("sim_census") is not None:
            with _trace.region("step.census_fetch", stage=True):
                result["sim_census"] = tuple(
                    np.asarray(a)  # tpulint: disable=hot-readback -- the sim plane's census-cadence batched fetch (its ONLY readback, doc/simulation.md), pre-fetched inside the guarded window; NOT per-tick
                    for a in result["sim_census"]
                )
        return result

    # ---- corruption sentinel ---------------------------------------------

    @staticmethod
    def _sentinel(engine, result: dict) -> Optional[str]:
        """Range/shape checks over the already-fetched readback arrays;
        returns a description of the rot, or None when clean. All
        device readbacks in this engine are integer/bool arrays, so
        float NaN/inf rot cannot surface literally — it surfaces as
        impossible values (a NaN position assigns outside the world; a
        rotted baseline produces a crossing from a cell that does not
        exist), which is exactly what is pinned here."""
        count = result["handover_count"]
        rows = result["handovers"]
        if count < 0 or count > engine.entity_capacity:
            return f"handover count {count} outside [0, capacity]"
        n_cells = engine.grid.num_cells
        head = rows[: min(count, len(rows))]
        if len(head):
            slots = head[:, 0]
            cells = head[:, 1:]
            if int(slots.max(initial=0)) >= engine.entity_capacity:
                return "handover row slot beyond entity capacity"
            bad = (cells < 0) | (cells >= n_cells)
            # The compaction's discard lane can leave slot == -1 rows;
            # only rows naming a real slot must carry real cells.
            if bool((bad & (slots >= 0)[:, None]).any()):
                return (
                    "handover row cites an impossible cell "
                    f"(grid has {n_cells})"
                )
        due = result["due_packed"]
        if len(due) != (engine.sub_capacity + 7) // 8:
            return "due bitmap length mismatch"
        q_blob = result.get("query_blob")
        if q_blob is not None:
            q_count = int(q_blob[0])  # tpulint: disable=hot-readback -- q_blob was pre-fetched as host numpy in _step_body; this indexes host memory, not the device
            q_cap = engine.query_capacity * n_cells
            if q_count < 0 or q_count > q_cap:
                return f"query change count {q_count} outside [0, Q*C]"
            q_rows = q_blob[1:].reshape(-1, 3)
            head = q_rows[: min(q_count, len(q_rows))]
            if len(head):
                live = head[:, 0] >= 0
                if int(head[:, 0].max(initial=0)) >= engine.query_capacity:
                    return "query change row beyond query capacity"
                bad_cell = (head[:, 1] < 0) | (head[:, 1] >= n_cells)
                if bool((bad_cell & live).any()):
                    return (
                        "query change row cites an impossible cell "
                        f"(grid has {n_cells})"
                    )
        return None

    # ---- failure / recovery ----------------------------------------------

    def _pin_ladder(self) -> None:
        from .overload import governor

        governor.pin_floor(2, "device engine down")

    def _release_ladder(self) -> None:
        from .overload import governor

        governor.release_floor()

    def _enter_fatal(self, controller, cause: str) -> None:
        if self._failed_at is None:
            self._failed_at = time.monotonic()
        self._fatal_cause = cause
        self._rebuild_attempts = 0
        self._retry_count = 0
        self._set_state(DeviceState.REBUILDING)
        self._pin_ladder()
        if _trace.enabled:
            # Freeze the timeline at the failure tick: the dump holds
            # the stages that led into the fault.
            _trace.note_anomaly(
                "device_failure", f"{cause}: engine down, rebuilding"
            )
        controller.on_device_fatal(cause)
        # Crash-during-recovery durability: snapshot NOW, before the
        # rebuild runs, through the shared fsync'd path — written
        # SYNCHRONOUSLY: a loop task would not get a turn until after
        # _attempt_rebuild releases the loop thread, which is exactly
        # too late for the crash-during-rebuild case this write exists
        # for (the tick is already stalled for the rebuild anyway).
        self._snapshot("device_fatal", sync=True)
        self._attempt_rebuild(controller)

    def _attempt_rebuild(self, controller) -> None:
        """Drive the in-process rebuild WITHOUT parking the event loop:
        the rebuild's device calls (device_put, the verification
        readbacks) run on the SAME deadline-guarded worker as the step —
        against a genuinely wedged device a synchronous rebuild would
        block the loop thread for seconds: no ticks, no trunk
        heartbeats (a federated peer would declare this gateway DEAD
        over a fault it is actively recovering from), no SIGTERM drain.
        Instead the wait per tick is bounded at min(step deadline, 1s):
        the common millisecond rebuild completes inside it
        (synchronous semantics), a slow one degrades to per-tick
        polling, and one wedged past 4x the step deadline is abandoned
        into FAILED (backoff retry) behind the same generation fence as
        a hung step."""
        from . import metrics

        engine = controller.engine
        if self._rebuild_fut is None:
            self._set_state(DeviceState.REBUILDING)
            try:
                if _chaos.armed and _chaos.fire("device.rebuild_fail"):
                    raise RuntimeError("chaos: injected rebuild failure")
                seeds = controller.rebuild_seed_cells()
            except Exception as exc:
                self._rebuild_failed(exc)
                return
            self._rebuild_t0 = time.monotonic()
            self._rebuild_fut = self._executor().submit(
                self._rebuild_body, engine, seeds, engine.generation
            )
        fut = self._rebuild_fut
        try:
            mismatches = fut.result(timeout=min(
                max(global_settings.device_step_deadline_s, 0.001), 1.0
            ))
        except concurrent.futures.TimeoutError:
            deadline = max(global_settings.device_step_deadline_s * 4, 0.004)
            if time.monotonic() - self._rebuild_t0 >= deadline:
                self._rebuild_fut = None
                engine.bump_generation()
                self._abandon_executor()
                fut.add_done_callback(_log_zombie)
                self._rebuild_failed(RuntimeError(
                    "rebuild exceeded the watchdog deadline (device "
                    "still wedged)"
                ))
            return  # still rebuilding: poll again next tick
        except Exception as exc:
            self._rebuild_fut = None
            self._rebuild_failed(exc)
            return
        self._rebuild_fut = None
        if mismatches:
            self._rebuild_failed(RuntimeError(
                f"rebuild verification failed: {mismatches}"
            ))
            return
        took_ms = (time.monotonic() - self._rebuild_t0) * 1000.0
        metrics.device_rebuild_ms.observe(took_ms)
        logger.warning(
            "engine rebuilt in-process from the host shadow: %d entities "
            "re-seeded, verified bit-identical (%.1fms)",
            engine.entity_count(), took_ms,
        )
        self._finish_recovery(self._fatal_cause)
        # Recovery durability: the rebuilt state is the newest truth.
        self._snapshot("device_recovered")

    def _rebuild_failed(self, exc: BaseException) -> None:
        self._count_failure("rebuild_fail")
        self._rebuild_attempts += 1
        backoff = (
            global_settings.device_retry_backoff_ms / 1000.0
        ) * (2 ** min(self._rebuild_attempts, 6))
        self._not_before = time.monotonic() + backoff
        logger.error(
            "in-process engine rebuild failed (attempt %d: %r); "
            "retrying in %.0fms", self._rebuild_attempts, exc,
            backoff * 1000.0,
        )
        self._set_state(DeviceState.FAILED)

    @staticmethod
    def _rebuild_body(engine, seeds: dict, gen: int):
        """Worker-thread rebuild: re-seed from the host shadow, then the
        bit-identical verification readbacks. Two fences keep an
        abandoned (timed-out) rebuild from ever clobbering a later
        successful one when the device unwedges: the engine's rebuild
        lock serializes concurrent rebuild bodies outright, and
        ``expect_generation`` inside rebuild_device_state refuses to
        commit once the watchdog bumped the generation — the stale
        worker raises AFTER its blocking transfers, BEFORE any
        engine-visible mutation."""
        _affinity.enter("device-worker")
        if not engine._rebuild_lock.acquire(
            timeout=max(global_settings.device_step_deadline_s * 4, 0.004)
        ):
            raise RuntimeError(
                "rebuild lock held by an abandoned rebuild (device "
                "still wedged)"
            )
        try:
            if gen != engine.generation:
                raise RuntimeError("stale rebuild abandoned by watchdog")
            engine.rebuild_device_state(seeds, expect_generation=gen)
            return engine.verify_device_state(seeds)
        finally:
            engine._rebuild_lock.release()

    def _finish_recovery(self, cause: str) -> None:
        recovery_s = (
            time.monotonic() - self._failed_at
            if self._failed_at is not None else 0.0
        )
        self.recovery_times_s.append(recovery_s)
        deadline = global_settings.device_recovery_deadline_s
        if recovery_s > deadline:
            logger.warning(
                "device recovery took %.2fs (deadline %.2fs)",
                recovery_s, deadline,
            )
        self._count_recovery(cause)
        self.events.append({
            "t": round(time.monotonic() - self._started, 3),
            "recovered": cause,
            "recovery_s": round(recovery_s, 3),
        })
        self._failed_at = None
        self._fatal_cause = ""
        self._retry_count = 0
        self._not_before = 0.0
        self._set_state(DeviceState.ACTIVE)
        self._release_ladder()

    def _snapshot(self, reason: str, sync: bool = False) -> None:
        """Immediate snapshot through the shared fsync'd write path
        (core/snapshot.py). ``sync`` writes inline (the fatal-entry
        snapshot: it must be durable BEFORE the rebuild stalls the loop
        thread); otherwise the disk IO runs off-thread when an event
        loop is up so the tick never stalls on fsync."""
        path = global_settings.snapshot_path
        if not path:
            return
        try:
            from .snapshot import take_snapshot, write_snapshot

            snap = take_snapshot()
            import asyncio

            if sync:
                write_snapshot(snap, path)
                logger.info("snapshot written on %s (%d channels)",
                            reason, len(snap.channels))
                return
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                write_snapshot(snap, path)
            else:
                task = loop.create_task(
                    asyncio.to_thread(write_snapshot, snap, path)
                )
                task.add_done_callback(_log_snapshot_error)
            logger.info("snapshot scheduled on %s (%d channels)",
                        reason, len(snap.channels))
        except Exception:
            logger.exception("%s snapshot failed", reason)

    # ---- reporting -------------------------------------------------------

    def report(self) -> dict:
        return {
            "state": self.state.name,
            "recovery_counts": dict(self.recovery_counts),
            "failure_counts": dict(self.failure_counts),
            "recovery_times_s": [round(s, 3) for s in self.recovery_times_s],
            "held_ticks": self.held_ticks,
            "events": list(self.events),
        }


def _log_snapshot_error(task) -> None:
    """Off-thread snapshot writes must never surface as unretrieved
    task exceptions (e.g. the target dir vanished under a test
    teardown); the failure is logged, the gateway unaffected."""
    if task.cancelled():
        return
    exc = task.exception()
    if exc is not None:
        logger.warning("device-recovery snapshot write failed: %r", exc)


def _log_zombie(fut) -> None:
    exc = fut.exception()
    if exc is not None:
        logger.info("abandoned device step finished with %r", exc)
    else:
        logger.info("abandoned device step finished late (discarded)")


# The process-wide guard. The TPU controller holds a module reference;
# a disabled guard costs one attribute load per tick.
guard = DeviceGuard()


def reset_device_guard() -> None:
    """Test hook."""
    guard.shutdown()
    guard.reset()
