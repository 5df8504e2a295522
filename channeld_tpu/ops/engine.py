"""SpatialEngine: device-resident spatial decision state + tick driver.

Host-side façade over the batched kernels in spatial_ops: fixed-capacity
slot arrays with a free-list for dynamic entity membership (the device
analog of the reference's entity maps), a query table for client AOI
interests, and the fan-out subscription clock. One ``tick()`` performs
the whole per-frame decision pass on device and returns host-consumable
results (handover list, interest masks, due subscriptions).

Dirty positions are staged host-side between ticks and shipped as one
scatter per tick — the H2D traffic is O(moved entities), not O(capacity).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.affinity import affinity as _affinity
from ..core.tracing import recorder as _trace
from ..utils.logger import get_logger
from .spatial_ops import (
    AOI_BOX,
    AOI_CONE,
    AOI_NONE,
    AOI_SPHERE,
    AOI_SPOTS,
    SIM_IDLE,
    SIM_SEEK,
    GridSpec,
    QuerySet,
    SimParams,
    diff_query_masks,
    parse_query_blob,
    sim_step,
    spatial_step,
)

logger = get_logger("ops.engine")


def _bucket(rows) -> np.ndarray:
    """A dirty-row collection as an i32 index vector padded to the next
    power of two by repeating its last index. Scatter shapes then come
    from a fixed set that ``warmup`` compiles before the listeners open,
    instead of following the dirty count into a fresh XLA compile inside
    the watchdog window (on the chip that read as a hang; PR 21). A
    repeated ``.set`` of the same row with the same value is idempotent."""
    k = len(rows)
    idx = np.fromiter(rows, np.int32, k)
    pad = (1 << (k - 1).bit_length()) - k
    if pad == 0:
        return idx
    return np.concatenate([idx, np.full(pad, idx[-1], np.int32)])


def _buckets(capacity: int) -> list[int]:
    """Every length ``_bucket`` can return for a table of ``capacity``."""
    return [1 << b for b in range((capacity - 1).bit_length() + 1)]


@jax.jit
def _set_rows(arr, idx, vals):
    """``arr.at[idx].set(vals)`` as ONE program per (table, bucket). The
    eager form dispatches about eight small programs per new shape
    (index normalisation, broadcasts, the scatter). Not donated: the old
    array stays valid until the fenced store replaces it."""
    return arr.at[idx].set(vals)


class StepBatch:
    """What one device step takes from the host, gathered by
    ``SpatialEngine.stage_step`` on the thread that owns the host
    mirrors (the tick loop) and consumed by ``run_staged`` on whichever
    thread makes the device calls (the guard's worker). The dirty sets
    are TAKEN at staging (swapped for empty ones) and the rows to upload
    are gathered into arrays of the batch's own, so a mutator that runs
    while the step is in flight marks dirty for the NEXT flush and never
    tears this one (doc/concurrency.md#the-step-in-flight).

    A ``*_rows`` field is ``(taken, idx, values...)``: the taken dirty
    collection (what ``restage`` hands back), its bucketed index vector
    and the gathered rows. The worker sets a field to None as it commits
    that block, so what is left after a failed step is exactly what
    never reached the device.

    ``churn`` is the other direction of the same flight: what the loop
    did to the registries while the step ran (see ``StepChurn``)."""

    __slots__ = (
        "gen", "now_ms", "run_sim", "census_due", "sim_tick",
        "entity", "seed", "sim_full", "sim_rows", "flee", "spots_full",
        "spots_rows", "queries", "sub_full", "sub_last", "sub_rows",
        "q_reset", "churn",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)


class StepChurn:
    """The entity slots, query rows and sub slots that changed owner
    (were freed or allocated) while one step was in flight. The step's
    result cites slots and rows, and is read through registries that
    have moved on: what it says of a churned slot belongs to an owner
    who left (or to nobody yet), so every consumer of the result leaves
    those out (``handover_list``, ``interested_cells_batch``, the
    controller's due publish, the query plane's consume, the census
    absorb). Nothing is lost by it: the departed owner was unsubscribed
    or untracked synchronously, and the new owner's rows went dirty for
    the next flush, which also resets the slot's device baseline
    (doc/concurrency.md#the-step-in-flight)."""

    __slots__ = ("entities", "queries", "subs")

    def __init__(self):
        self.entities: set[int] = set()
        self.queries: set[int] = set()
        self.subs: set[int] = set()


class SpatialEngine:
    def __init__(
        self,
        grid: GridSpec,
        entity_capacity: int = 1 << 17,
        query_capacity: int = 1 << 12,
        sub_capacity: int = 1 << 16,
        max_handovers: int = 4096,
        mesh=None,
        sharding: str = "entities",
        cell_bucket: int = 0,
        query_rows_max: int = 8192,
    ):
        """``mesh``: a jax.sharding.Mesh to shard the entity slot arrays
        over (from parallel.mesh.make_mesh / make_mesh_2d). None = the
        single-device fused step. The serving results are identical either
        way (pinned by tests/test_ops.py engine parity); the mesh step
        exchanges per-cell occupancy with psum over ICI/DCN and gathers
        per-shard handover rows — the TPU answer to the reference's
        multi-server spatial world (ref: spatial.go:387-590).

        ``sharding`` picks the meshed step: "entities" (psum occupancy,
        replicated AOI) or "cells" (space-partitioned: all_to_all entity
        redistribution to per-shard cell blocks + column-block AOI +
        ring-halo borders — parallel/spatial_alltoall.py). "cells" with
        ``cell_bucket`` > 0 caps the per-(source, dest) redistribution
        bucket; overflowed entities are reported undelivered and
        re-offered next tick (0 = exact delivery)."""
        if sharding not in ("entities", "cells"):
            raise ValueError(f"unknown sharding {sharding!r}")
        self._mesh = mesh
        self._sharding = sharding
        self._cell_bucket = cell_bucket
        # shared=fence declarations (doc/concurrency.md#fences): engine
        # state is written from the tick-loop (mutators, staging; the
        # unguarded step) AND the device-guard worker (the guarded step
        # + the in-process rebuild). While a step is in flight the loop
        # RUNS (the GLOBAL tick task awaits the worker), under a split
        # by ownership: the loop owns the host mirrors and the dirty
        # collections, the worker owns the ``_d_*`` handles and reads
        # the staged batch alone (``stage_step`` / ``run_staged``);
        # whatever swaps handles wholesale waits for the step's finish
        # (``_affinity.expect_no_step``). Past that the one true
        # concurrency is a watchdog-abandoned zombie worker unwedging
        # late — which the generation fence makes safe: every
        # engine-visible store re-checks the generation between staging
        # and store (machine-checked by tpulint's fence-discipline
        # rule).
        self._mesh_step = None  # tpulint: shared=fence
        # Cells-plane shed diagnostics, refreshed each mesh tick.
        self.last_overflow = 0  # tpulint: shared=fence
        if mesh is not None:
            n_dev = int(mesh.devices.size)
            # Entity arrays shard evenly over every mesh axis.
            entity_capacity = ((entity_capacity + n_dev - 1) // n_dev) * n_dev
            from jax.sharding import NamedSharding, PartitionSpec

            self._entity_ns = NamedSharding(
                mesh, PartitionSpec(tuple(mesh.axis_names))
            )
            # Query and sub tables are read whole by every shard.
            self._replicated_ns = NamedSharding(mesh, PartitionSpec())
        else:
            self._entity_ns = None
            self._replicated_ns = None
        self.grid = grid
        self.entity_capacity = entity_capacity
        self.query_capacity = query_capacity
        self.sub_capacity = sub_capacity
        self.max_handovers = max_handovers

        # Host mirrors (numpy) + dirty staging.
        self._positions = np.zeros((entity_capacity, 3), np.float32)
        self._valid = np.zeros(entity_capacity, bool)
        self._free = list(range(entity_capacity - 1, -1, -1))
        self._slot_of_entity: dict[int, int] = {}
        self._entity_of_slot = np.zeros(entity_capacity, np.uint32)
        self._dirty_slots: set[int] = set()  # tpulint: shared=fence
        self._seed_cells: dict[int, int] = {}  # slot -> forced prev cell  # tpulint: shared=fence

        self._q_kind = np.zeros(query_capacity, np.int32)
        self._q_center = np.zeros((query_capacity, 2), np.float32)
        self._q_extent = np.zeros((query_capacity, 2), np.float32)
        self._q_dir = np.zeros((query_capacity, 2), np.float32)
        self._q_angle = np.zeros(query_capacity, np.float32)
        self._q_free = list(range(query_capacity - 1, -1, -1))
        self._q_of_conn: dict[int, int] = {}
        # [Q,C] spots dist table (-1 = no interest), allocated on the
        # first spots query so the common compiled step never carries it
        # (one recompile then). The device copy updates by row scatter —
        # H2D is O(changed rows x C), never the whole table.
        self._q_spot_dist: Optional[np.ndarray] = None
        # World-space spot sources per connection: the dist rows above
        # are in CELL space, so a grid swap (apply_grid — adaptive
        # partitioning) must re-rasterize every row from these.
        self._spot_sources: dict[int, tuple] = {}
        self._d_spot_dist = None  # tpulint: shared=fence
        self._spot_dirty_rows: set[int] = set()  # tpulint: shared=fence
        self._queries_dirty = True  # tpulint: shared=fence

        # Standing-query plane (doc/query_engine.md): when enabled the
        # tick diffs this tick's interest/dist masks against the
        # committed device baseline and compacts the delta to changed
        # (query, cell, dist) rows — the plane's ONE d2h transfer.
        self.track_query_changes = False
        self.query_rows_max = query_rows_max
        # Committed (interest, dist) baseline pair; None = empty baseline
        # (next diff full-emits every interested row).
        self._d_q_prev = None  # tpulint: shared=fence
        # Rows whose baseline must be zeroed before the next diff: a
        # freshly-allocated (or freed) row may be REUSED by a new query,
        # and a stale baseline would swallow the overlap between the old
        # and new masks (never re-emitted = lost subscription).
        self._q_prev_reset_rows: set[int] = set()  # tpulint: shared=fence
        # Bumped whenever the committed baseline is thrown away wholesale
        # (rebuild_device_state / apply_grid): the host plane sees the
        # epoch move and full-resyncs its mirrors instead of trusting
        # deltas that no longer connect to its last-applied state.
        self.query_epoch = 0  # tpulint: shared=fence

        # Host staging for the sub table. The device's last-fan-out column
        # is authoritative after each tick (fanout_due advances it); the
        # host mirror only carries *explicit* writes (add/reset/interval),
        # applied as row scatters — a full rebuild from the mirror would
        # snap every sub's window start back to stale values.
        # (Written by the rebuild too, which snaps the active rows to
        # now: on the worker for the guard, on the loop for a geometry
        # epoch, never while a step or another rebuild runs.)
        self._sub_last = np.zeros(sub_capacity, np.int32)  # tpulint: shared=fence
        self._sub_interval = np.zeros(sub_capacity, np.int32)
        self._sub_active = np.zeros(sub_capacity, bool)
        self._sub_free = list(range(sub_capacity - 1, -1, -1))
        # Per-column dirty tracking: interval/active writes must never
        # drag the stale host `last` along (an interval-only change would
        # otherwise snap that sub's window start back arbitrarily far).
        self._sub_dirty_slots: set[int] = set()  # interval+active cols  # tpulint: shared=fence
        self._sub_last_dirty: set[int] = set()  # last-fan-out column  # tpulint: shared=fence

        # Device state (entity arrays sharded over the mesh when given).
        # .copy(): jax's H2D transfer is async and may read the numpy
        # buffer after this call; _positions/_valid are mutated by
        # add/update_entity before the first tick, so the live buffers
        # must never be handed to the transfer (see _stage_flush).
        if self._entity_ns is not None:
            self._d_positions = jax.device_put(
                self._positions.copy(), self._entity_ns
            )
            self._d_valid = jax.device_put(self._valid.copy(), self._entity_ns)
            self._d_cell = jax.device_put(
                np.full(entity_capacity, -1, np.int32), self._entity_ns
            )
        else:
            self._d_positions = jnp.asarray(self._positions.copy())  # tpulint: shared=fence
            self._d_valid = jnp.asarray(self._valid.copy())  # tpulint: shared=fence
            self._d_cell = jnp.full(entity_capacity, -1, jnp.int32)  # tpulint: shared=fence
        self._d_queries: Optional[QuerySet] = None  # tpulint: shared=fence
        self._d_sub_state = None  # tpulint: shared=fence

        # Simulation plane (channeld_tpu/sim, doc/simulation.md): agents
        # occupy ORDINARY entity slots — the sim pass advances their
        # positions in the same device arrays every downstream plane
        # reads (crossings, AOI, fan-out, standing queries), so NPCs are
        # indistinguishable from humans past this point and cost zero
        # extra transfers. The kinematic columns (velocity, FSM state,
        # waypoint) follow the positions staging discipline: host
        # shadows + dirty-slot scatters, full re-upload when the device
        # copy is dropped. The device is authoritative for agent rows
        # between censuses; the host shadow refreshes only at census
        # boundaries (absorb_census), which is why a rebuild reproduces
        # the last census exactly — the replay contract doc/simulation.md
        # pins.
        self.sim_enabled = False  # tpulint: shared=fence
        self.sim_seed = 0
        self.sim_params: Optional[SimParams] = None
        self.sim_tick = 0  # counter-based RNG cursor  # tpulint: shared=fence
        # Per-tick scheduling flags, set by the controller on the tick
        # loop before the step is staged; ``stage_step`` copies them
        # into the batch (same handoff as the dirty collections), so the
        # worker never reads these two.
        self.run_sim_pass = False  # tpulint: shared=fence
        self.sim_census_due = False  # tpulint: shared=fence
        self._agent_mask = np.zeros(entity_capacity, bool)
        self._vel = np.zeros((entity_capacity, 3), np.float32)
        self._sim_state = np.zeros(entity_capacity, np.int32)
        self._sim_target = np.zeros((entity_capacity, 3), np.float32)
        self._sim_dirty: set[int] = set()  # tpulint: shared=fence
        # Danger mask (bool[num_cells]) rasterized by the sim plane from
        # query-plane sensor hits; uploaded only when a sensor's
        # interest set changes — never per tick.
        self._flee_cells: Optional[np.ndarray] = None
        self._flee_dirty = False  # tpulint: shared=fence
        self._d_agent = None  # tpulint: shared=fence
        self._d_vel = None  # tpulint: shared=fence
        self._d_sim_state = None  # tpulint: shared=fence
        self._d_sim_target = None  # tpulint: shared=fence
        self._d_flee = None  # tpulint: shared=fence
        # (key, seed, empty danger mask): see _sim_constants.
        self._sim_consts = None  # tpulint: shared=atomic
        # Double-entry ledger mirroring sim_device_rebuilds_total{result}
        # (scripts/sim_soak.py cross-checks both sides).
        self.sim_rebuild_counts: dict[str, int] = {}  # tpulint: shared=fence

        # The churn of the step in flight, None when none is: opened by
        # ``stage_step``, closed by ``end_flight``, written by the
        # allocators and the removers in between. Loop thread only.
        self._flight: Optional[StepChurn] = None

        self._start = time.monotonic()
        self.last_result: Optional[dict] = None  # tpulint: shared=fence
        # Abandoned-step fence (core/device_guard.py): the watchdog bumps
        # this when it gives up on a hung step; a zombie worker thread
        # completing the old tick later must not commit its tail state
        # over a rebuilt engine (tick() re-checks before committing).
        self.generation = 0  # tpulint: shared=fence
        # Serializes concurrent rebuild bodies (a watchdog-abandoned
        # rebuild's worker vs its retry on a fresh worker): the stale
        # one must never interleave transfers with — or commit over —
        # the live one. See device_guard._rebuild_body.
        import threading

        self._rebuild_lock = threading.Lock()
        # Fused Mosaic assign+count on TPU backends (pallas_kernels);
        # the sharded step uses plain XLA inside shard_map.
        from .pallas_kernels import pallas_available

        self.use_pallas = pallas_available() and mesh is None
        # What this engine runs on, said once at construction and served
        # under /introspect: a gateway on the chip must be tellable from
        # one that is not (JAX falls back to the CPU with only a warning
        # when TPU init fails and JAX_PLATFORMS is unset).
        from ..native import codec as native_codec
        from ..utils.devices import describe_devices

        self.device_info = {
            **describe_devices(mesh),
            "use_pallas": self.use_pallas,
            "native_codec": native_codec is not None,
        }
        logger.info(
            "spatial engine on platform=%(platform)s "
            "device_kind=%(device_kind)r device_count=%(device_count)d "
            "mesh=%(mesh)s use_pallas=%(use_pallas)s "
            "native_codec=%(native_codec)s", self.device_info,
        )

    # ---- entity slots ----------------------------------------------------

    def now_ms(self) -> int:
        return int((time.monotonic() - self._start) * 1000)

    def add_entity(self, entity_id: int, x: float, y: float, z: float) -> int:
        slot = self._slot_of_entity.get(entity_id)
        if slot is None:
            if not self._free:
                raise RuntimeError("entity capacity exhausted")
            slot = self._free.pop()
            if self._flight is not None:
                self._flight.entities.add(slot)
            self._slot_of_entity[entity_id] = slot
            self._entity_of_slot[slot] = entity_id
            # Fresh slot: clear any previous occupant's cell so reuse can't
            # fabricate a crossing on the first tick.
            self._seed_cells[slot] = -1
        self._positions[slot] = (x, y, z)
        self._valid[slot] = True
        self._dirty_slots.add(slot)
        return slot

    def seed_cell(self, slot: int, cell: int) -> None:
        """Set the device-side previous cell for a slot before its first
        tick (used to seed a just-sighted entity's old position)."""
        self._seed_cells[slot] = cell

    def update_entity(self, entity_id: int, x: float, y: float, z: float) -> None:
        slot = self._slot_of_entity.get(entity_id)
        if slot is None:
            self.add_entity(entity_id, x, y, z)
            return
        self._positions[slot] = (x, y, z)
        self._dirty_slots.add(slot)

    def remove_entity(self, entity_id: int) -> None:
        slot = self._slot_of_entity.pop(entity_id, None)
        if slot is None:
            return
        self._valid[slot] = False
        self._dirty_slots.add(slot)
        if self._agent_mask[slot]:
            # A departed agent's slot must stop stepping immediately —
            # a reused slot would otherwise inherit the sim pass.
            self._agent_mask[slot] = False
            self._sim_dirty.add(slot)
        self._free.append(slot)
        if self._flight is not None:
            self._flight.entities.add(slot)

    def entity_count(self) -> int:
        return len(self._slot_of_entity)

    def slot_of_entity(self, entity_id: int) -> Optional[int]:
        return self._slot_of_entity.get(entity_id)

    def entity_id_of_slot(self, slot: int) -> int:
        return int(self._entity_of_slot[slot])

    # ---- queries ---------------------------------------------------------

    def _query_slot(self, conn_id: int) -> int:
        q = self._q_of_conn.get(conn_id)
        if q is None:
            if not self._q_free:
                raise RuntimeError("query capacity exhausted")
            q = self._q_free.pop()
            if self._flight is not None:
                self._flight.queries.add(q)
            self._q_of_conn[conn_id] = q
            # Fresh owner for this row: zero its diff baseline before the
            # next tick so the previous occupant's mask can't swallow the
            # overlap with the new query (see _q_prev_reset_rows).
            self._q_prev_reset_rows.add(q)
        return q

    def set_query(
        self,
        conn_id: int,
        kind: int,
        center_xz: tuple[float, float],
        extent_xz: tuple[float, float] = (0.0, 0.0),
        direction_xz: tuple[float, float] = (1.0, 0.0),
        angle: float = 0.0,
    ) -> None:
        q = self._query_slot(conn_id)
        self._spot_sources.pop(conn_id, None)  # no longer a spots query
        self._q_kind[q] = kind
        self._q_center[q] = center_xz
        self._q_extent[q] = extent_xz
        norm = float(np.hypot(*direction_xz)) or 1.0
        self._q_dir[q] = (direction_xz[0] / norm, direction_xz[1] / norm)
        self._q_angle[q] = angle
        self._queries_dirty = True

    def set_spots_query(
        self,
        conn_id: int,
        spots_xz: list[tuple[float, float]],
        dists: Optional[list[int]] = None,
    ) -> None:
        """Spots AOI on the device plane: rasterize the spot list to a
        per-cell interest row (ref: spatial.go spots loop — each spot's
        cell, dist = dists[i] when given else 0; out-of-world spots
        skipped). Where several spots land in one cell the last spot's
        dist wins — the host path's dict-overwrite order. The row is a
        dist table with -1 = no interest (see QuerySet.spot_dist)."""
        import math

        q = self._query_slot(conn_id)
        self._spot_sources[conn_id] = (
            [tuple(s) for s in spots_xz],
            list(dists) if dists is not None else None,
        )
        if self._q_spot_dist is None:
            self._q_spot_dist = np.full(
                (self.query_capacity, self.grid.num_cells), -1, np.int32
            )
        self._q_kind[q] = AOI_SPOTS
        dist_row = np.full(self.grid.num_cells, -1, np.int32)
        g = self.grid
        for i, (x, z) in enumerate(spots_xz):
            # Divide-then-floor, exactly like the host path and
            # assign_cells — float floor-division disagrees on boundaries
            # (1.0 // 0.1 == 9.0 but floor(1.0 / 0.1) == 10).
            col = math.floor((x - g.offset_x) / g.cell_w)
            row = math.floor((z - g.offset_z) / g.cell_h)
            if not (0 <= col < g.cols and 0 <= row < g.rows):
                continue
            cell = row * g.cols + col
            # Clamp to int32 max: wire dists are uint32, and 0xFFFFFFFF
            # must not alias the -1 sentinel.
            dist_row[cell] = (
                min(int(dists[i]), 2**31 - 1)
                if dists is not None and i < len(dists) else 0
            )
        self._q_spot_dist[q] = dist_row
        self._spot_dirty_rows.add(q)
        self._queries_dirty = True

    def remove_query(self, conn_id: int) -> None:
        q = self._q_of_conn.pop(conn_id, None)
        self._spot_sources.pop(conn_id, None)
        if q is not None:
            self._q_kind[q] = AOI_NONE
            if self._q_spot_dist is not None:
                self._q_spot_dist[q] = -1
                self._spot_dirty_rows.add(q)
            self._q_free.append(q)
            if self._flight is not None:
                self._flight.queries.add(q)
            # A freed row emits no removal rows (the plane unsubscribes
            # synchronously at deregistration) and must hand its next
            # owner a clean diff baseline.
            self._q_prev_reset_rows.add(q)
            self._queries_dirty = True

    def query_row_of_conn(self, conn_id: int) -> Optional[int]:
        return self._q_of_conn.get(conn_id)

    # ---- subscriptions ---------------------------------------------------

    def add_subscription(self, interval_ms: int, first_due_ms: int = 0) -> int:
        if not self._sub_free:
            raise RuntimeError("subscription capacity exhausted")
        s = self._sub_free.pop()
        if self._flight is not None:
            self._flight.subs.add(s)
        self._sub_last[s] = first_due_ms
        self._sub_interval[s] = interval_ms
        self._sub_active[s] = True
        self._sub_dirty_slots.add(s)
        self._sub_last_dirty.add(s)
        return s

    def remove_subscription(self, s: int) -> None:
        self._sub_active[s] = False
        self._sub_free.append(s)
        self._sub_dirty_slots.add(s)
        if self._flight is not None:
            self._flight.subs.add(s)

    def set_sub_interval(self, s: int, interval_ms: int) -> None:
        """Re-subscription merged new options (ref: subscription.go:34-60)."""
        self._sub_interval[s] = interval_ms
        self._sub_dirty_slots.add(s)

    def reset_sub_clock(self, s: int, now_ms: int) -> None:
        """Snap the sub's window start to ``now`` — mirrors the host path's
        first-fan-out behavior (tick_data sets latest_fanout_time = now)."""
        self._sub_last[s] = now_ms
        self._sub_last_dirty.add(s)

    # ---- simulation plane (channeld_tpu/sim, doc/simulation.md) ----------

    def seed_agents(self, entries, seed: int, params: SimParams,
                    vels=None, states=None, targets=None) -> list[int]:
        """Register a simulated population into ordinary entity slots.

        ``entries`` is [(entity_id, x, y, z)]. ``vels``/``states``/
        ``targets`` restore a census (WAL replay, federation adoption);
        a fresh spawn starts IDLE at rest, targeting its own position.
        Mesh-sharded engines don't run the sim pass (the kernel is
        single-device; documented in doc/simulation.md). Returns the
        slots used."""
        if self._mesh is not None:
            raise RuntimeError("sim plane requires a single-device engine")
        slots = []
        for i, (eid, x, y, z) in enumerate(entries):
            slot = self.add_entity(eid, float(x), float(y), float(z))
            self._agent_mask[slot] = True
            self._vel[slot] = vels[i] if vels is not None else (0.0, 0.0, 0.0)
            self._sim_state[slot] = (
                int(states[i]) if states is not None else SIM_IDLE
            )
            self._sim_target[slot] = (
                targets[i] if targets is not None else (x, y, z)
            )
            self._sim_dirty.add(slot)
            slots.append(slot)
        self.sim_seed = int(seed) & 0xFFFFFFFF
        self.sim_params = params
        self.sim_enabled = True
        return slots

    def agent_slots(self) -> np.ndarray:
        """Live agent slot indices, ascending (host-shadow truth)."""
        return np.nonzero(self._agent_mask & self._valid)[0]

    def agent_count(self) -> int:
        return int(np.count_nonzero(self._agent_mask & self._valid))

    def agent_ids(self, slots: Optional[np.ndarray] = None) -> np.ndarray:
        """Entity ids for ``slots`` (default: all live agent slots)."""
        if slots is None:
            slots = self.agent_slots()
        return self._entity_of_slot[slots]

    def is_agent(self, entity_id: int) -> bool:
        slot = self._slot_of_entity.get(entity_id)
        return slot is not None and bool(self._agent_mask[slot])

    def absorb_census(self, slots: np.ndarray, positions, vel, state,
                      target) -> None:
        """Fold a fetched census (full-capacity device arrays, already
        numpy) back into the host shadows WITHOUT marking anything dirty
        — the values came FROM the device, so re-uploading them would be
        pure waste and re-staging them could clobber a newer device
        tick. After this call the host shadow is bit-identical to the
        device for every agent row, which is what makes the next
        rebuild/verify exact."""
        self._positions[slots] = positions[slots]
        self._vel[slots] = vel[slots]
        self._sim_state[slots] = state[slots]
        self._sim_target[slots] = target[slots]

    def set_flee_cells(self, cells) -> None:
        """Install the danger mask driving FLEE: an iterable of micro
        cell indices (query-plane sensor hits, rasterized by the sim
        plane). Uploaded on the next flush — only when this is called,
        never per tick."""
        mask = np.zeros(self.grid.num_cells, bool)
        for c in cells:
            if 0 <= c < self.grid.num_cells:
                mask[c] = True
        self._flee_cells = mask
        self._flee_dirty = True

    def sim_stampede(self, cell: int) -> None:
        """CHAOS ONLY (``sim.stampede``): herd every agent toward one
        cell — a deterministic handover/density burst that exercises
        partition splits and overload shedding from the sim plane.
        Host-staged like any other mutation, so it rides the ordinary
        fenced scatter into the next tick."""
        g = self.grid
        cx = g.offset_x + (cell % g.cols + 0.5) * g.cell_w
        cz = g.offset_z + (cell // g.cols + 0.5) * g.cell_h
        slots = self.agent_slots()
        self._sim_state[slots] = SIM_SEEK
        self._sim_target[slots, 0] = cx
        self._sim_target[slots, 2] = cz
        self._vel[slots] = 0.0
        self._sim_dirty.update(int(s) for s in slots)

    def corrupt_sim_state_for_chaos(self) -> None:
        """CHAOS ONLY (``sim.step_nan``): rot the agent rows the way a
        bad kernel output would — NaN positions/velocities on a quarter
        of the agents, plus garbage prev-cell baselines on the same rows
        so the fault carries the impossible-src-cell signature the
        readback sentinel detects (same detection path as ``device.nan``;
        the triggered rebuild re-seeds the rotted rows from the host
        shadow and the population resumes its replayable trajectory)."""
        _affinity.expect_no_step("corrupt_sim_state_for_chaos")
        live = self.agent_slots()
        n = max(1, len(live) // 4)
        rows = live[:n].astype(np.int32)
        self._d_cell = self._keep_entity_sharding(
            self._d_cell.at[rows].set(1 << 24)
        )
        self._d_positions = self._keep_entity_sharding(
            self._d_positions.at[rows].set(float("nan"))
        )
        if self._d_vel is not None:
            self._d_vel = self._keep_entity_sharding(
                self._d_vel.at[rows].set(float("nan"))
            )

    def _count_sim_rebuild(self, result: str) -> None:
        """Double-entry sim rebuild accounting: python ledger AND
        prometheus move together on every verification of the agent
        arrays (scripts/sim_soak.py asserts both sides agree)."""
        self.sim_rebuild_counts[result] = (
            self.sim_rebuild_counts.get(result, 0) + 1
        )
        from ..core import metrics

        metrics.sim_device_rebuilds.labels(result=result).inc()

    # ---- the tick --------------------------------------------------------

    def _keep_entity_sharding(self, arr):
        """Scatter updates must not silently migrate a mesh-sharded array
        (device_put is a no-op when the sharding already matches)."""
        if self._entity_ns is None:
            return arr
        return jax.device_put(arr, self._entity_ns)

    def _put_replicated(self, arr: np.ndarray):
        """Upload a whole query/sub column. With a mesh it is committed
        replicated, matching the sharded step's in_specs; a bare
        jnp.asarray would sit on device 0 and be re-broadcast into
        shard_map on every tick."""
        if self._replicated_ns is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self._replicated_ns)

    def _stage_flush(self, b: StepBatch) -> None:
        """Take every dirty collection and gather its rows into ``b``:
        the host half of the flush, on the thread that owns the host
        mirrors. The fancy-index gathers and ``.copy()`` calls are what
        keeps a later host write (and jax's asynchronous H2D copy) away
        from the arrays the device calls read. Which handles are still
        None is read here: they change only inside a step or a rebuild,
        and neither is under way while the loop stages."""
        if self._dirty_slots:
            taken, self._dirty_slots = self._dirty_slots, set()
            idx = _bucket(taken)
            b.entity = (taken, idx, self._positions[idx], self._valid[idx])
        if self._seed_cells:
            seeds, self._seed_cells = self._seed_cells, {}
            b.seed = (seeds, _bucket(seeds.keys()), _bucket(seeds.values()))
        if self.sim_enabled:
            if self._d_vel is None:
                # First upload (or post-rebuild re-upload) of the whole
                # kinematic column set.
                b.sim_full = (self._vel.copy(), self._sim_state.copy(),
                              self._sim_target.copy(),
                              self._agent_mask.copy())
                self._sim_dirty = set()
            elif self._sim_dirty:
                taken, self._sim_dirty = self._sim_dirty, set()
                idx = _bucket(taken)
                b.sim_rows = (taken, idx, self._vel[idx],
                              self._sim_state[idx], self._sim_target[idx],
                              self._agent_mask[idx])
            if self._flee_cells is not None and (
                self._d_flee is None or self._flee_dirty
            ):
                b.flee = self._flee_cells.copy()
                self._flee_dirty = False
        if self._q_spot_dist is not None:
            if self._d_spot_dist is None:
                b.spots_full = self._q_spot_dist.copy()
                self._spot_dirty_rows = set()
            elif self._spot_dirty_rows:
                taken, self._spot_dirty_rows = self._spot_dirty_rows, set()
                idx = _bucket(taken)
                b.spots_rows = (taken, idx, self._q_spot_dist[idx])
        if (self._d_queries is None or self._queries_dirty
                or b.spots_full is not None or b.spots_rows is not None):
            b.queries = (self._q_kind.copy(), self._q_center.copy(),
                         self._q_extent.copy(), self._q_dir.copy(),
                         self._q_angle.copy())
            self._queries_dirty = False
        if self._d_sub_state is None:
            b.sub_full = (self._sub_last.copy(), self._sub_interval.copy(),
                          self._sub_active.copy())
            self._sub_dirty_slots = set()
            self._sub_last_dirty = set()
        else:
            # Per-column rows of explicit host writes only — the
            # device's last-fan-out values for untouched slots stay
            # authoritative (fanout_due advances them device-side).
            if self._sub_last_dirty:
                taken, self._sub_last_dirty = self._sub_last_dirty, set()
                idx = _bucket(taken)
                b.sub_last = (taken, idx, self._sub_last[idx])
            if self._sub_dirty_slots:
                taken, self._sub_dirty_slots = self._sub_dirty_slots, set()
                idx = _bucket(taken)
                b.sub_rows = (taken, idx, self._sub_interval[idx],
                              self._sub_active[idx])

    def restage(self, b: StepBatch) -> None:
        """Hand back what a failed step never committed: the taken
        indices rejoin the dirty collections (the next staging gathers
        their rows afresh from the host mirrors, so a newer write wins).
        Same thread as ``stage_step``. A whole-table upload that did not
        commit needs nothing: its handle is still None and the next
        staging takes the whole table again. After a hang the worker may
        still hold ``b`` (fenced: it commits nothing more, but it can be
        between a fence and the ``= None`` of a block it did commit), so
        each field is read once; a row handed back that had reached the
        device is uploaded again, which is harmless."""
        taken = {name: getattr(b, name) for name in (
            "entity", "seed", "sim_rows", "flee", "spots_rows", "queries",
            "sub_last", "sub_rows", "q_reset",
        )}
        if taken["entity"] is not None:
            self._dirty_slots |= taken["entity"][0]
        if taken["seed"] is not None:
            for slot, cell in taken["seed"][0].items():
                self._seed_cells.setdefault(slot, cell)
        if taken["sim_rows"] is not None:
            self._sim_dirty |= taken["sim_rows"][0]
        if taken["flee"] is not None:
            self._flee_dirty = True
        if taken["spots_rows"] is not None:
            self._spot_dirty_rows |= taken["spots_rows"][0]
        if taken["queries"] is not None:
            self._queries_dirty = True
        if taken["sub_last"] is not None:
            self._sub_last_dirty |= taken["sub_last"][0]
        if taken["sub_rows"] is not None:
            self._sub_dirty_slots |= taken["sub_rows"][0]
        if taken["q_reset"] is not None:
            self._q_prev_reset_rows |= taken["q_reset"][0]

    def _apply_staged(self, b: StepBatch) -> None:
        """The device half of the flush: every upload and scatter of
        ``b``, on the thread that may wait for the chip. It reads the
        batch and the ``_d_*`` handles and no host mirror."""
        def _fence() -> None:
            # Stale-tick fence (core/device_guard.py): a watchdog-
            # abandoned worker that unwedges mid-flush must not commit
            # staged arrays over a rebuilt engine. Each block stages
            # its device work into locals and re-checks the generation
            # immediately before the engine-visible assignment, so the
            # exposure shrinks from the whole flush to one store.
            if b.gen != self.generation:
                raise RuntimeError("stale device tick abandoned by watchdog")

        _fence()
        if b.entity is not None:
            _, idx, positions, valid = b.entity
            d_positions = self._keep_entity_sharding(
                _set_rows(self._d_positions, idx, positions)
            )
            d_valid = self._keep_entity_sharding(
                _set_rows(self._d_valid, idx, valid)
            )
            _fence()
            self._d_positions = d_positions
            self._d_valid = d_valid
            b.entity = None
        if b.seed is not None:
            _, slots, cells = b.seed
            d_cell = self._keep_entity_sharding(
                _set_rows(self._d_cell, slots, cells)
            )
            _fence()
            self._d_cell = d_cell
            b.seed = None
        if b.sim_full is not None:
            vel, state, target, agent = b.sim_full
            d_vel = jnp.asarray(vel)
            d_state = jnp.asarray(state)
            d_target = jnp.asarray(target)
            d_agent = jnp.asarray(agent)
            _fence()
            self._d_vel = d_vel
            self._d_sim_state = d_state
            self._d_sim_target = d_target
            self._d_agent = d_agent
            b.sim_full = None
        elif b.sim_rows is not None:
            _, idx, vel, state, target, agent = b.sim_rows
            d_vel = _set_rows(self._d_vel, idx, vel)
            d_state = _set_rows(self._d_sim_state, idx, state)
            d_target = _set_rows(self._d_sim_target, idx, target)
            d_agent = _set_rows(self._d_agent, idx, agent)
            _fence()
            self._d_vel = d_vel
            self._d_sim_state = d_state
            self._d_sim_target = d_target
            self._d_agent = d_agent
            b.sim_rows = None
        if b.flee is not None:
            d_flee = jnp.asarray(b.flee)
            _fence()
            self._d_flee = d_flee
            b.flee = None
        if b.spots_full is not None:
            d_spot = self._put_replicated(b.spots_full)
            _fence()
            self._d_spot_dist = d_spot
            b.spots_full = None
        elif b.spots_rows is not None:
            _, idx, rows = b.spots_rows
            d_spot = _set_rows(self._d_spot_dist, idx, rows)
            _fence()
            self._d_spot_dist = d_spot
            b.spots_rows = None
        if b.queries is not None:
            d_queries = QuerySet(
                *(self._put_replicated(col) for col in b.queries),
                self._d_spot_dist,
            )
            _fence()
            self._d_queries = d_queries
            b.queries = None
        if b.sub_full is not None:
            d_sub = tuple(self._put_replicated(col) for col in b.sub_full)
            _fence()
            self._d_sub_state = d_sub
            b.sub_full = None
        elif b.sub_last is not None or b.sub_rows is not None:
            last, interval, active = self._d_sub_state
            if b.sub_last is not None:
                _, idx, values = b.sub_last
                last = _set_rows(last, idx, values)
            if b.sub_rows is not None:
                _, idx, intervals, actives = b.sub_rows
                interval = _set_rows(interval, idx, intervals)
                active = _set_rows(active, idx, actives)
            _fence()
            self._d_sub_state = (last, interval, active)
            b.sub_last = None
            b.sub_rows = None

    def warmup(self) -> None:
        """Compile the tick's common (no-spots) step on empty tables —
        called at controller load, BEFORE listeners open. Without this the
        first live tick pays multi-second XLA compilation inside the
        channel tick, stalling the event loop long enough for the unauth
        reaper to blacklist slow-authing peers (observed end-to-end with
        the meshed cells plane). The warmup tick mutates nothing the
        serving path reads: tables are empty and inactive.

        Then every bucket of every flush scatter (``_bucket``): the
        guard reads a compile inside its watchdog window as a hang, and
        on the chip the first 100K-agent flush was one. The results are
        thrown away, so no table changes. The sim columns share the
        entity tables' shapes and so their programs; the spots table
        does not exist before the first spots query, which recompiles
        the step anyway."""
        for _ in range(2):
            # Twice: the second tick takes the first one's outputs as
            # its inputs, as every serving tick does. Under a mesh their
            # shardings differ from the freshly made arrays' and the
            # query diff compiles again.
            jax.block_until_ready(self.tick(now_ms=0))
        self.last_result = None
        t0 = time.monotonic()
        tables = [
            (self.entity_capacity,
             (self._d_positions, self._d_valid, self._d_cell)),
            # last/interval share one i32 program.
            (self.sub_capacity, self._d_sub_state[::2]),
        ]
        for capacity, arrays in tables:
            for k in _buckets(capacity):
                idx = np.zeros(k, np.int32)
                for arr in arrays:
                    _set_rows(arr, idx,
                              np.zeros((k,) + arr.shape[1:], arr.dtype))
        if self._d_q_prev is not None:
            for k in _buckets(self.query_capacity):
                idx = np.zeros(k, np.int32)
                _set_rows(self._d_q_prev[0], idx, np.bool_(False))
                _set_rows(self._d_q_prev[1], idx, np.int32(0))
        logger.info("flush scatter buckets warmed in %.1fs",
                    time.monotonic() - t0)

    def _sim_constants(self):
        """``(seed, empty danger mask)`` as device arrays, made once for
        the engine's seed and grid. Made anew in every tick they were
        two more programs to dispatch, and every dispatch is a turn at
        the interpreter lock, which the device worker shares with the
        loop thread since the GLOBAL tick awaits the step (PERF.md,
        PR 26): the tick counter and the clock, which do change, go in
        as numpy scalars with the pass's own arguments."""
        key = (self.sim_seed, self.grid.num_cells)
        held = self._sim_consts
        if held is None or held[0] != key:
            held = self._sim_consts = (
                key, jnp.uint32(self.sim_seed),
                jnp.zeros(self.grid.num_cells, bool),
            )
        return held[1], held[2]

    def sim_warmup(self) -> None:
        """Compile the sim step at plane activation, for the same reason
        ``warmup`` exists: the first live sim tick must not pay XLA
        compilation inside the guarded window (a multi-second stall
        there reads as a hang and trips the watchdog). Runs on
        throwaway arrays of the live shapes — sim_step donates its
        inputs, so the live arrays are never handed to a warmup."""
        if self.sim_params is None:
            return
        n = self.entity_capacity
        seed, no_flee = self._sim_constants()
        jax.block_until_ready(
            sim_step(
                self.grid,
                jnp.zeros((n, 3), jnp.float32),
                jnp.zeros((n, 3), jnp.float32),
                jnp.zeros(n, jnp.int32),
                jnp.zeros((n, 3), jnp.float32),
                jnp.zeros(n, bool),
                no_flee,
                self.sim_params,
                seed,
                np.int32(0),
            )
        )

    def stage_step(self, now_ms: Optional[int] = None) -> StepBatch:
        """The host half of one step, on the thread that owns the host
        mirrors: take the dirty collections, gather their rows, and fix
        the step's scheduling inputs (the sim flags the controller set
        for this tick, the RNG cursor, the diff rows to reset). From
        here until the step's result is in hand the mutators may run:
        they write mirrors and dirty sets this batch no longer reads."""
        b = StepBatch()
        b.churn = self._flight = StepChurn()
        b.gen = self.generation
        b.now_ms = self.now_ms() if now_ms is None else now_ms
        self._stage_flush(b)
        b.run_sim = self.sim_enabled and self.run_sim_pass
        b.census_due = self.sim_census_due
        b.sim_tick = self.sim_tick
        if self._q_prev_reset_rows:
            if self.track_query_changes:
                taken = self._q_prev_reset_rows
                self._q_prev_reset_rows = set()
                b.q_reset = (taken, _bucket(taken))
            else:
                # No baseline while tracking is off — when it turns on,
                # the None baseline full-emits anyway.
                self._q_prev_reset_rows.clear()
        return b

    def run_staged(self, b: StepBatch) -> dict:
        """The device half: uploads, the passes, the commit of the
        ``_d_*`` handles, all behind ``b``'s generation. Every call that
        can wait on the chip is in here, so the guard's watchdog covers
        them by covering this."""
        with _trace.region("step.flush", stage=True):
            self._apply_staged(b)
        # From the first pass's enqueue to the commit of the _d_*
        # handles. On one device nothing in here waits for the chip (the
        # waits are the fetches that follow, ``step.fetch``); a mesh
        # tick merges its shards' handover rows on the host inside it.
        with _trace.region("step.dispatch", stage=True):
            out = self._dispatch_passes(b)
        self.last_result = out
        return out

    def tick(self, now_ms: Optional[int] = None) -> dict:
        """Run one device decision pass on the calling thread; returns
        numpy-backed results."""
        batch = self.stage_step(now_ms)
        try:
            return self.run_staged(batch)
        except BaseException:
            self.restage(batch)
            raise
        finally:
            self.end_flight(batch)

    def end_flight(self, b: StepBatch) -> Optional[StepChurn]:
        """The step staged as ``b`` is over for the loop (answered,
        failed or given up): owner changes are no longer recorded.
        Returns those the flight saw, or None when it saw none, for the
        step's result to carry as ``result["churn"]``. Same thread as
        ``stage_step``."""
        self._flight = None
        churn = b.churn
        if churn.entities or churn.queries or churn.subs:
            return churn
        return None

    def _dispatch_passes(self, b: StepBatch) -> dict:
        # Sim pass first (device->device): agents advance, then the
        # spatial pass reads the SAME position array — crossings, AOI,
        # standing queries and fan-out all see the moved agents this
        # very tick, with zero extra transfers. The flags came with the
        # batch: the controller set them on the loop thread before it
        # staged, and may set the next tick's while this one runs.
        now_ms = b.now_ms
        sim_committed = None
        census_due = False
        positions = self._d_positions
        if b.run_sim and self._d_vel is not None:
            seed, no_flee = self._sim_constants()
            flee = self._d_flee
            if flee is None:
                flee = no_flee
            sim_committed = sim_step(
                self.grid,
                positions,
                self._d_vel,
                self._d_sim_state,
                self._d_sim_target,
                self._d_agent,
                flee,
                self.sim_params,
                seed,
                np.int32(b.sim_tick),
            )
            positions = sim_committed[0]
            census_due = b.census_due
        if self._mesh is not None:
            out = self._mesh_tick(now_ms)
        else:
            out = spatial_step(
                self.grid,
                positions,
                self._d_cell,
                self._d_valid,
                self._d_queries,
                self._d_sub_state,
                self.max_handovers,
                np.int32(now_ms),
                use_pallas=self.use_pallas,
            )
        q_prev = None
        if self.track_query_changes:
            prev = self._d_q_prev
            if prev is None:
                prev = (
                    jnp.zeros(out["interest"].shape, bool),
                    jnp.zeros(out["interest"].shape, jnp.int32),
                )
            elif b.q_reset is not None:
                # Reused rows start from an empty baseline (pure compute
                # on the old arrays; committed only after the gen check).
                idx = b.q_reset[1]
                prev = (_set_rows(prev[0], idx, np.bool_(False)),
                        _set_rows(prev[1], idx, np.int32(0)))
            q_blob, q_prev_i, q_prev_d = diff_query_masks(
                prev[0], prev[1], out["interest"], out["dist"],
                self.query_rows_max,
            )
            out["query_blob"] = q_blob
            out["query_epoch"] = self.query_epoch
            q_prev = (q_prev_i, q_prev_d)
        if b.gen != self.generation:
            # The watchdog abandoned this step (device_guard): the
            # engine may already be rebuilt — committing this tick's
            # tail state would corrupt the fresh baseline.
            raise RuntimeError("stale device tick abandoned by watchdog")
        # Baseline for the next tick: crossings that overflowed the handover
        # row budget keep their old cell so they are re-detected, not lost.
        if sim_committed is not None:
            # The sim batch commits ATOMICALLY with the spatial commit
            # and only past the fence above — a watchdog-abandoned step
            # can never leave a torn population (positions advanced but
            # kinematics not, or vice versa); the abandoned tick's
            # donated buffers die with it and the guard's rebuild
            # re-uploads every column from the host shadow.
            (self._d_positions, self._d_vel, self._d_sim_state,
             self._d_sim_target) = sim_committed
            self.sim_tick = b.sim_tick + 1
            if census_due:
                # Device handles for the census columns; the guard
                # pre-fetches them to numpy inside the guarded window
                # (core/device_guard.py), the sim plane absorbs them.
                out["sim_census"] = (
                    self._d_positions, self._d_vel, self._d_sim_state,
                    self._d_sim_target,
                )
                out["sim_tick"] = self.sim_tick
        self._d_cell = out["committed_prev"]
        self._d_sub_state = (
            out["new_last_fanout_ms"],
            self._d_sub_state[1],
            self._d_sub_state[2],
        )
        if q_prev is not None:
            self._d_q_prev = q_prev
            b.q_reset = None
        return out

    def _mesh_tick(self, now_ms: int) -> dict:
        """The sharded decision pass, normalized to the single-device
        result contract (handover_count + merged global-slot rows)."""
        from ..parallel.mesh import merge_handover_shards

        with_spots = self._d_queries.spot_dist is not None
        if self._mesh_step is None or self._mesh_step.with_spots != with_spots:
            n_shards = int(self._mesh.devices.size)
            per_shard = max(1, -(-self.max_handovers // n_shards))
            if self._sharding == "cells":
                from ..parallel.spatial_alltoall import (
                    build_cell_serving_step,
                )

                bucket = self._cell_bucket or (
                    self.entity_capacity // n_shards
                )
                self._mesh_step = build_cell_serving_step(
                    self.grid, self._mesh, bucket, per_shard, with_spots
                )
            else:
                from ..parallel.mesh import build_sharded_step

                self._mesh_step = build_sharded_step(
                    self.grid, self._mesh, per_shard, with_spots
                )
        if self._sharding == "cells":
            from ..parallel.spatial_alltoall import cell_serving_spatial_step

            out = cell_serving_spatial_step(
                self._mesh_step, self._d_positions, self._d_cell,
                self._d_valid, self._d_queries, self._d_sub_state, now_ms,
            )
            self.last_overflow = int(np.asarray(out["overflow"]).sum())
        else:
            from ..parallel.mesh import sharded_spatial_step

            out = sharded_spatial_step(
                self._mesh_step,
                self._d_positions,
                self._d_cell,
                self._d_valid,
                self._d_queries,
                self._d_sub_state,
                now_ms,
            )
        count, rows = merge_handover_shards(
            out["handover_counts"], out["handovers"]
        )
        out["handover_count"] = count
        out["handovers"] = rows
        return out

    def undelivered_slots(self, result: dict) -> list[int]:
        """Slots whose cells-plane redistribution bucket was full this
        tick (empty for exact delivery / other shardings). They remain in
        the ingest arrays and are re-offered automatically next tick;
        the controller sheds visibly (metric + security log)."""
        und = result.get("undelivered")
        if und is None:
            return []
        return np.nonzero(np.asarray(und))[0].tolist()

    def handover_list(self, result: dict) -> list[tuple[int, int, int]]:
        """[(entity_id, src_cell, dst_cell)] from a tick result.

        Every row present must be consumed: the device already committed
        these crossings (committed_prev), so a clamped row would be a
        permanently lost handover. Mesh ticks can report slightly more
        than max_handovers (per-shard budgets round up); single-device
        counts beyond the row budget re-detect next tick."""
        count, rows = result["handover_count"], result["handovers"]
        if not isinstance(rows, np.ndarray):
            # Unguarded path: the device guard fetches these inside its
            # supervised window (core/device_guard.py ``step.fetch``).
            with _trace.region("step.fetch", stage=True):
                count = int(count)
                rows = np.asarray(rows)
        rows = rows[: min(count, len(rows))]
        churn = result.get("churn")
        gone = churn.entities if churn is not None else ()
        return [
            (int(self._entity_of_slot[slot]), int(src), int(dst))
            for slot, src, dst in rows
            if slot >= 0 and slot not in gone
        ]

    def interested_cells(self, result: dict, conn_id: int) -> dict[int, int]:
        """{cell_index: grid_distance} for one connection's query."""
        q = self._q_of_conn.get(conn_id)
        if q is None:
            return {}
        interest = np.asarray(result["interest"][q])
        dist = np.asarray(result["dist"][q])
        cells = np.nonzero(interest)[0]
        return {int(c): int(dist[c]) for c in cells}

    def interested_cells_batch(
        self, result: dict, conn_ids
    ) -> dict[int, dict[int, int]]:
        """{conn_id: {cell_index: grid_distance}} for MANY queries in one
        device->host transfer of the whole interest + dist tables.

        The per-connection form above pulls one row per call — one
        device round-trip per AOI follower per tick, measured at
        ~330us/follower on a CPU host (PR 10's own run): past ~100
        followers that alone blew the 33ms GLOBAL tick. The
        masks already live in two device arrays, so the follower pass
        fetches them once and slices rows on host — O(1) transfers per
        tick regardless of follower count.

        A connection whose row changed owner during the step's flight
        is left out: the row's mask is its last owner's."""
        churn = result.get("churn")
        gone = churn.queries if churn is not None else ()
        rows = [
            (cid, q) for cid in conn_ids
            if (q := self._q_of_conn.get(cid)) is not None and q not in gone
        ]
        if not rows:
            return {}
        interest = np.asarray(result["interest"])
        dist = np.asarray(result["dist"])
        out: dict[int, dict[int, int]] = {}
        for cid, q in rows:
            cells = np.nonzero(interest[q])[0]
            drow = dist[q]
            out[cid] = {int(c): int(drow[c]) for c in cells}
        return out

    def query_changed_rows(self, result: dict) -> tuple[int, np.ndarray]:
        """(total_changed, rows i32[query_rows_max, 3]) from a tick
        result — the standing-query plane's ONE device->host transfer
        per tick (doc/query_engine.md). The fetched blob is cached back
        onto the result dict, so however many consumers ask, the
        transfer happens at most once per tick (the device guard
        pre-fetches it inside the guarded step window; this path is the
        unguarded fallback). Row layout: (query_row, cell, new_dist)
        with dist == -1 meaning interest removed; rows beyond
        min(total, query_rows_max) are -1 padding. Returns (0, empty)
        when tracking was off for this tick."""
        blob = result.get("query_blob")
        if blob is None:
            return 0, np.zeros((0, 3), np.int32)
        if not isinstance(blob, np.ndarray):
            with _trace.region("step.fetch", stage=True):
                blob = np.asarray(blob)  # tpulint: disable=hot-readback -- the plane's designed once-per-tick changed-rows fetch (unguarded path; cached on the result)
            result["query_blob"] = blob
        return parse_query_blob(blob)

    # ---- supervision & recovery (core/device_guard.py) -------------------

    def tracked_entities(self) -> list[tuple[int, int]]:
        """[(entity_id, slot)] for every live registration — what the
        device guard walks to compute per-slot rebuild baselines."""
        return list(self._slot_of_entity.items())

    def bump_generation(self) -> None:
        """Fence off an abandoned (hung) step: a zombie worker thread
        finishing the old tick later raises instead of committing its
        tail state over whatever the guard rebuilt meanwhile."""
        self.generation += 1

    def rebuild_device_state(self, slot_cells: dict[int, int],
                             now_ms: Optional[int] = None,
                             expect_generation: Optional[int] = None) -> None:
        """In-process device-state rebuild from the host-side shadow
        (doc/device_recovery.md). The host mirrors are authoritative for
        everything except two device-advanced columns:

        - the per-slot *previous cell* baseline, which the caller passes
          in as ``slot_cells`` (computed from the grid's ``_data_cell``
          placement ledger + the failover journal's in-flight dsts, so a
          mid-crossing entity re-baselines to where its data is actually
          bound — the next tick re-detects any move since);
        - the sub table's last-fan-out column, which is snapped to
          ``now``: every sub's window restarts, so fan-out resumes one
          full interval from the rebuild instead of bursting or
          silently slipping.

        Everything device-side is re-created from fresh copies; nothing
        the corrupted arrays held survives.

        ``expect_generation``: the caller's stale-rebuild fence — the
        fresh arrays are built FIRST (the wedge-prone blocking
        transfers), and nothing engine-visible mutates unless the
        generation still matches. A rebuild the watchdog abandoned
        (which bumped the generation) raises here when it unwedges
        instead of committing stale state over a later verified one."""
        _affinity.expect_no_step("rebuild_device_state")
        if now_ms is None:
            now_ms = self.now_ms()
        if expect_generation is None:
            expect_generation = self.generation
        cells = np.full(self.entity_capacity, -1, np.int32)
        for slot, cell in slot_cells.items():
            cells[slot] = cell
        if self._entity_ns is not None:
            d_positions = jax.device_put(
                self._positions.copy(), self._entity_ns
            )
            d_valid = jax.device_put(self._valid.copy(), self._entity_ns)
            d_cell = jax.device_put(cells.copy(), self._entity_ns)
        else:
            d_positions = jnp.asarray(self._positions.copy())
            d_valid = jnp.asarray(self._valid.copy())
            d_cell = jnp.asarray(cells.copy())
        if expect_generation != self.generation:
            raise RuntimeError("stale rebuild abandoned by watchdog")
        self.generation += 1
        self._d_positions = d_positions
        self._d_valid = d_valid
        self._d_cell = d_cell
        self._dirty_slots.clear()
        self._seed_cells.clear()
        # Query tables: host staging is fully authoritative; force a
        # wholesale re-upload (the spots table re-uploads from scratch
        # on the next flush when present).
        self._d_queries = None
        self._d_spot_dist = None
        self._spot_dirty_rows.clear()
        self._queries_dirty = True
        # Sim kinematic columns: the host shadow (last census + explicit
        # stages) is authoritative; dropping the device copies forces the
        # whole-column re-upload path on the flush below, which is what
        # makes the rebuilt arrays bit-identical to the shadow
        # (verify_device_state proves it, sim_device_rebuilds_total
        # counts it).
        self._d_vel = None
        self._d_sim_state = None
        self._d_sim_target = None
        self._d_agent = None
        self._d_flee = None
        self._flee_dirty = self._flee_cells is not None
        self._sim_dirty.clear()
        # Standing-query diff baseline: gone with the rest of the device
        # state. The epoch bump tells the host plane its mirrors no
        # longer connect to the next tick's delta stream — it must
        # full-resync (every query re-emits against the empty baseline).
        self._d_q_prev = None
        self._q_prev_reset_rows.clear()
        self.query_epoch += 1
        # Sub table: intervals/active from the host mirror; the
        # device-authoritative last-fan-out column restarts at now.
        self._sub_last[self._sub_active] = now_ms
        self._d_sub_state = None
        self._sub_dirty_slots.clear()
        self._sub_last_dirty.clear()
        batch = StepBatch()
        batch.gen = self.generation
        self._stage_flush(batch)
        self._apply_staged(batch)
        self.last_result = None

    def apply_grid(self, grid, slot_cells: dict[int, int],
                   now_ms: Optional[int] = None,
                   expect_generation: Optional[int] = None) -> None:
        """Swap the cell grid and rebuild every grid-shaped device array
        (adaptive partitioning, doc/partitioning.md: the controller
        mirrors the cell tree's uniform micro grid onto the device at
        each geometry epoch). Reuses the supervised-rebuild machinery —
        the caller passes the same placement-ledger cell baselines
        (in NEW-grid indices) the crash rebuild uses, the generation
        fence makes a watchdog-abandoned swap unable to commit, and
        ``verify_device_state`` afterwards proves the rebuilt arrays
        bit-identical to the host shadow. Grid-shaped state that cannot
        be carried over is rebuilt from world-space sources: the spots
        dist table re-rasterizes from ``_spot_sources``; the compiled
        (mesh) step re-traces lazily on the next tick."""
        _affinity.expect_no_step("apply_grid")
        self.grid = grid
        # The grid is baked into the compiled mesh step: force a
        # re-build/re-trace on the next tick.
        self._mesh_step = None
        # Spots rows are [Q, num_cells] in cell space: drop both copies
        # and re-rasterize every row against the new grid.
        self._q_spot_dist = None
        self._d_spot_dist = None
        self._spot_dirty_rows.clear()
        # The flee mask is [num_cells] in cell space: drop it; the sim
        # plane re-rasterizes its sensors' hits against the new geometry
        # (its on_geometry hook fires after the swap).
        self._flee_cells = None
        for conn_id, (spots, dists) in list(self._spot_sources.items()):
            self.set_spots_query(conn_id, spots, dists)
        self.rebuild_device_state(slot_cells, now_ms=now_ms,
                                  expect_generation=expect_generation)

    def verify_device_state(self, slot_cells: dict[int, int]) -> list[str]:
        """Bit-identical rebuild verification: fetch the just-rebuilt
        device arrays and compare them against the host shadow (and the
        seeded cell baselines). Returns mismatch descriptions (empty ==
        verified). Rebuild-path only — never called from the tick, so
        these transfers are the designed one-off recovery cost, not a
        hot-path readback."""
        errors: list[str] = []
        cells = np.full(self.entity_capacity, -1, np.int32)
        for slot, cell in slot_cells.items():
            cells[slot] = cell
        # equal_nan on the float arrays: NaN coordinates are tolerated
        # input (they assign outside the world) and round-trip the
        # device bit-identically — without this, one NaN position would
        # fail verification forever and turn a recoverable fault into a
        # permanent outage.
        if not np.array_equal(np.asarray(self._d_positions), self._positions,
                              equal_nan=True):
            errors.append("positions differ from host shadow")
        if not np.array_equal(np.asarray(self._d_valid), self._valid):
            errors.append("valid mask differs from host shadow")
        if not np.array_equal(np.asarray(self._d_cell), cells):
            errors.append("cell baselines differ from placement seeds")
        if self._d_queries is not None:
            for name, dev, host, has_nan in (
                ("query kinds", self._d_queries.kind, self._q_kind, False),
                ("query centers", self._d_queries.center, self._q_center,
                 True),
                ("query extents", self._d_queries.extent, self._q_extent,
                 True),
            ):
                if not np.array_equal(np.asarray(dev), host,
                                      equal_nan=has_nan):
                    errors.append(f"{name} differ from host shadow")
        if self._d_sub_state is not None:
            last, interval, active = self._d_sub_state
            if not np.array_equal(np.asarray(interval), self._sub_interval):
                errors.append("sub intervals differ from host shadow")
            if not np.array_equal(np.asarray(active), self._sub_active):
                errors.append("sub active mask differs from host shadow")
            if not np.array_equal(np.asarray(last), self._sub_last):
                errors.append("sub clock differs from rebuild seed")
        if self.sim_enabled and self._d_vel is not None:
            sim_errors: list[str] = []
            for name, dev, host, has_nan in (
                ("agent velocities", self._d_vel, self._vel, True),
                ("agent FSM states", self._d_sim_state, self._sim_state,
                 False),
                ("agent waypoints", self._d_sim_target, self._sim_target,
                 True),
                ("agent mask", self._d_agent, self._agent_mask, False),
            ):
                if not np.array_equal(np.asarray(dev), host,
                                      equal_nan=has_nan):
                    sim_errors.append(f"{name} differ from host shadow")
            if self._flee_cells is not None and self._d_flee is not None:
                if not np.array_equal(np.asarray(self._d_flee),
                                      self._flee_cells):
                    sim_errors.append("flee mask differs from host shadow")
            errors.extend(sim_errors)
            self._count_sim_rebuild(
                "verified" if not sim_errors else "mismatch"
            )
        return errors

    def corrupt_device_state_for_chaos(self) -> None:
        """CHAOS ONLY (``device.nan``): silently rot the device state the
        way a bad DMA / bit-flipped HBM page would — NaN positions plus
        garbage prev-cell baselines. The NaN positions make the affected
        entities vanish from cell assignment (assign_cells maps NaN
        outside the world); the garbage baselines surface as impossible
        src cells in the next tick's handover rows, which is exactly the
        signature the readback sentinel checks for."""
        _affinity.expect_no_step("corrupt_device_state_for_chaos")
        live = list(self._slot_of_entity.values())
        n = max(1, len(live) // 4)
        # Garbage baselines on one subset: their (still-valid) positions
        # produce crossing rows with an impossible src cell next tick —
        # the sentinel's detectable signature. NaN positions on a
        # DISJOINT subset: those entities silently vanish from cell
        # assignment (NaN maps outside the world), the truly silent rot
        # the sentinel-triggered rebuild also heals.
        garbage = np.fromiter(live[:n], np.int32, min(n, len(live)))
        nan_rows = np.fromiter(live[n:2 * n], np.int32, len(live[n:2 * n]))
        self._d_cell = self._keep_entity_sharding(
            self._d_cell.at[garbage].set(1 << 24)
        )
        if len(nan_rows):
            self._d_positions = self._keep_entity_sharding(
                self._d_positions.at[nan_rows].set(float("nan"))
            )
