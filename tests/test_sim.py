"""On-device world simulation (channeld_tpu/sim; doc/simulation.md).

The interaction matrix for the agent population: counter-based RNG
replayability, bit-identical host-shadow rebuilds with double-entry
accounting, the generation fence against torn sim batches, agents
crossing cells through the ordinary handover path, agents and humans
sharing cell tables and the standing-query plane, overload L2 cadence
halving with exact shed accounting, WAL census replay across a kill -9,
geometry-epoch re-homing, and the sim.* chaos points under the device
guard.
"""

import numpy as np
import pytest

from channeld_tpu.chaos import arm, disarm
from channeld_tpu.core import metrics
from channeld_tpu.core.channel import get_channel
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.overload import OverloadLevel, governor
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.types import ConnectionType, MessageType
from channeld_tpu.core.wal import boot_replay, reset_wal, wal
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.ops.engine import SpatialEngine
from channeld_tpu.ops.spatial_ops import (
    SIM_IDLE,
    SIM_SEEK,
    SIM_WANDER,
    GridSpec,
    SimParams,
)
from channeld_tpu.protocol import control_pb2
from channeld_tpu.sim.plane import AGENT_ID_OFFSET
from channeld_tpu.spatial.controller import SpatialInfo, set_spatial_controller
from channeld_tpu.spatial.tpu_controller import TPUSpatialController

from helpers import FetchCounter, StubConnection, fresh_runtime

ENTITY_START = 0x80000
AGENT_BASE = ENTITY_START + AGENT_ID_OFFSET


@pytest.fixture(autouse=True)
def runtime():
    gch = fresh_runtime()
    register_sim_types()
    yield gch
    disarm()
    governor.level = OverloadLevel.L0
    reset_wal()


def fast_params(**over):
    base = dict(dt=0.1, max_speed=12.0, accel=48.0, separation=0.6,
                cohesion=0.15, arrive_radius=1.5, crowd=8,
                p_wander=0.6, p_seek=0.3, p_idle=0.05)
    base.update(over)
    return SimParams(**base)


def make_engine(agents=32, seed=7, params=None):
    grid = GridSpec(offset_x=0.0, offset_z=0.0, cell_w=25.0, cell_h=100.0,
                    cols=4, rows=1)
    eng = SpatialEngine(grid, entity_capacity=128, query_capacity=8)
    rng = np.random.default_rng(seed)
    entries = [
        (AGENT_BASE + i, float(rng.uniform(2, 98)), 0.0,
         float(rng.uniform(2, 98)))
        for i in range(agents)
    ]
    eng.seed_agents(entries, seed, params or fast_params())
    eng.run_sim_pass = True
    return eng, entries


def engine_seeds(eng):
    """{slot: cell} baselines from current host-shadow positions."""
    g = eng.grid
    seeds = {}
    for eid, slot in eng.tracked_entities():
        x, _, z = eng._positions[slot]
        col = min(max(int((x - g.offset_x) / g.cell_w), 0), g.cols - 1)
        row = min(max(int((z - g.offset_z) / g.cell_h), 0), g.rows - 1)
        seeds[slot] = row * g.cols + col
    return seeds


def sim_snapshot(eng):
    slots = eng.agent_slots()
    return (
        np.asarray(eng._d_positions)[slots].copy(),
        np.asarray(eng._d_vel)[slots].copy(),
        np.asarray(eng._d_sim_state)[slots].copy(),
        np.asarray(eng._d_sim_target)[slots].copy(),
    )


def make_world(channels_for=(1,), **settings_over):
    global_settings.tpu_entity_capacity = 256
    global_settings.tpu_query_capacity = 16
    global_settings.sim_enabled = True
    global_settings.sim_agents = settings_over.pop("agents", 24)
    global_settings.sim_census_every_ticks = settings_over.pop("census", 1)
    global_settings.sim_max_speed = 20.0
    global_settings.sim_p_wander = 0.6
    for k, v in settings_over.items():
        setattr(global_settings, k, v)
    ctl = TPUSpatialController()
    ctl.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=4, GridRows=1, ServerCols=1, ServerRows=1,
             ServerInterestBorderSize=1)
    )
    set_spatial_controller(ctl)
    server = StubConnection(1, ConnectionType.SERVER)
    ctx = MessageContext(
        msg_type=MessageType.CREATE_CHANNEL,
        msg=control_pb2.CreateChannelMessage(),
        connection=server,
    )
    channels = ctl.create_channels(ctx)
    for ch in channels:
        subscribe_to_channel(server, ch, None)
    return ctl, server, channels


def run_ticks(ctl, channels, n=1):
    for _ in range(n):
        ctl.tick()
        for ch in channels:
            ch.tick_once(0)


# ---------------------------------------------------------------------------
# kernel: replayability + movement
# ---------------------------------------------------------------------------


def test_trajectories_replay_bit_exact():
    """The replayability contract: same seed + same tick count = the
    same population state, bit for bit (counter-based RNG; no hidden
    device state)."""
    a, _ = make_engine(seed=11)
    b, _ = make_engine(seed=11)
    for _ in range(8):
        a.tick()
        b.tick()
    for got, want in zip(sim_snapshot(a), sim_snapshot(b)):
        assert np.array_equal(got, want, equal_nan=True)
    moved = np.abs(sim_snapshot(a)[0] - sim_snapshot(b)[0]).sum()
    assert moved == 0.0
    # And the population actually moves (WANDER kicks in at p=0.6).
    c, entries = make_engine(seed=11)
    start = np.array([[e[1], e[2], e[3]] for e in entries], np.float32)
    for _ in range(8):
        c.tick()
    assert np.abs(sim_snapshot(c)[0] - start).sum() > 1.0
    assert c.sim_tick == 8


def test_distinct_seeds_diverge():
    a, _ = make_engine(seed=1)
    b, _ = make_engine(seed=2)
    for _ in range(6):
        a.tick()
        b.tick()
    assert not np.array_equal(sim_snapshot(a)[0], sim_snapshot(b)[0])


def test_fsm_states_and_world_clamp():
    """Agents leave IDLE, and integration keeps every agent inside the
    world bounds (the kernel clamps with a margin)."""
    eng, _ = make_engine(agents=64, seed=3)
    for _ in range(30):
        eng.tick()
    pos, _, state, _ = sim_snapshot(eng)
    assert set(np.unique(state)) <= {SIM_IDLE, SIM_WANDER, SIM_SEEK, 3}
    assert (state != SIM_IDLE).any()
    assert pos[:, 0].min() >= 0.0 and pos[:, 0].max() <= 100.0
    assert pos[:, 2].min() >= 0.0 and pos[:, 2].max() <= 100.0
    assert np.isfinite(pos).all()


def test_non_agent_rows_untouched_by_sim_pass():
    """Human-driven entities pass through the sim kernel unchanged —
    the agent mask gates every write lane."""
    eng, _ = make_engine(agents=8, seed=5)
    eng.add_entity(ENTITY_START + 1, 50.0, 0.0, 50.0)
    for _ in range(5):
        eng.tick()
    slot = eng.slot_of_entity(ENTITY_START + 1)
    assert np.allclose(
        np.asarray(eng._d_positions)[slot], [50.0, 0.0, 50.0]
    )


def test_meshed_engine_refuses_agents():
    from channeld_tpu.parallel.mesh import mesh_from_config

    mesh = mesh_from_config(8, 1)
    if mesh is None:
        pytest.skip("no virtual device mesh")
    grid = GridSpec(offset_x=0.0, offset_z=0.0, cell_w=25.0, cell_h=100.0,
                    cols=4, rows=1)
    eng = SpatialEngine(grid, entity_capacity=64, query_capacity=8,
                        mesh=mesh)
    with pytest.raises(RuntimeError, match="single-device"):
        eng.seed_agents([(AGENT_BASE, 10.0, 0.0, 10.0)], 1, fast_params())


def test_gateway_refuses_sim_on_a_meshed_engine():
    """``-sim true`` with a mesh stops the boot: a population asked for
    and silently never stepped would pass for a quiet world."""
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.parallel.mesh import mesh_from_config
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    if mesh_from_config(8, 1) is None:
        pytest.skip("no virtual device mesh")
    global_settings.sim_enabled = True
    ctl = TPUSpatialController()
    with pytest.raises(ValueError, match="-sim true cannot run on a meshed"):
        ctl.load_config(dict(
            WorldOffsetX=0, WorldOffsetZ=0, GridWidth=25, GridHeight=100,
            GridCols=4, GridRows=1, ServerCols=1, ServerRows=1,
            MeshDevices=8,
        ))
    assert ctl.engine is None and ctl.simplane is None


# ---------------------------------------------------------------------------
# rebuild: bit-identical + the generation fence (torn-batch regression)
# ---------------------------------------------------------------------------


def test_rebuild_bit_identical_with_double_entry():
    """After a census sync, the host shadow rebuilds the agent arrays
    bit-identically — and both sides of the rebuild accounting (python
    ledger, prometheus counter) move together."""
    eng, _ = make_engine(seed=9)
    for _ in range(6):
        eng.tick()
    eng.sim_census_due = True
    out = eng.tick()
    eng.sim_census_due = False
    census = tuple(np.asarray(a) for a in out["sim_census"])
    slots = eng.agent_slots()
    eng.absorb_census(slots, *census)
    before = sim_snapshot(eng)

    metric_before = metrics.sim_device_rebuilds.labels(
        result="verified")._value.get()
    seeds = engine_seeds(eng)
    eng.rebuild_device_state(seeds)
    errors = eng.verify_device_state(seeds)
    assert errors == []
    assert np.array_equal(sim_snapshot(eng)[0], before[0], equal_nan=True)
    assert np.array_equal(sim_snapshot(eng)[1], before[1], equal_nan=True)
    assert np.array_equal(sim_snapshot(eng)[2], before[2])
    assert np.array_equal(sim_snapshot(eng)[3], before[3], equal_nan=True)
    assert eng.sim_rebuild_counts.get("verified", 0) >= 1
    assert metrics.sim_device_rebuilds.labels(
        result="verified")._value.get() == metric_before + eng.sim_rebuild_counts["verified"]
    # The rebuilt engine keeps stepping the same trajectory.
    eng.tick()
    assert eng.sim_tick == 8


def test_generation_fence_abandons_torn_sim_batch(monkeypatch):
    """REGRESSION (doc/simulation.md): a watchdog-abandoned step must
    never commit a torn sim batch. Bump the generation mid-step (after
    the sim kernel ran, before the commit) — the tick raises, sim_tick
    does not advance, and the supervised rebuild heals the donated
    buffers from the host shadow."""
    import channeld_tpu.ops.engine as engine_mod

    eng, _ = make_engine(seed=13)
    for _ in range(3):
        eng.tick()
    eng.sim_census_due = True
    out = eng.tick()
    eng.sim_census_due = False
    census = tuple(np.asarray(a) for a in out["sim_census"])
    eng.absorb_census(eng.agent_slots(), *census)
    tick_before = eng.sim_tick

    real_step = engine_mod.spatial_step

    def hijacked(*args, **kwargs):
        eng.generation += 1  # the watchdog abandons this step
        return real_step(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "spatial_step", hijacked)
    with pytest.raises(RuntimeError, match="abandoned"):
        eng.tick()
    monkeypatch.setattr(engine_mod, "spatial_step", real_step)

    # Nothing committed: the sim cursor is exactly where it was.
    assert eng.sim_tick == tick_before
    # The abandoned step's donated buffers are healed by the rebuild
    # (the guard's escalation path) and the population is exactly the
    # host shadow's — no torn columns.
    seeds = engine_seeds(eng)
    eng.rebuild_device_state(seeds)
    assert eng.verify_device_state(seeds) == []
    eng.tick()
    assert eng.sim_tick == tick_before + 1


# ---------------------------------------------------------------------------
# the population in the full world
# ---------------------------------------------------------------------------


def test_agents_attach_and_live_in_cell_tables():
    """The authority gives every agent (under the cap) a real entity
    channel owned by the internal server conn, and a row in its cell
    channel's entity table — exactly like a human-spawned entity."""
    ctl, _server, channels = make_world()
    run_ticks(ctl, channels, 3)
    plane = ctl.simplane
    assert plane is not None
    assert plane.authority.pending_count() == 0
    assert len(plane.authority._backed) == 24
    total_rows = 0
    for ch in channels:
        total_rows += sum(
            1 for eid in ch.get_data_message().entities
            if eid >= AGENT_BASE
        )
    assert total_rows == 24
    # The internal conn is authenticated — the reaper must never see it.
    conn = plane.authority.conn
    assert conn is not None and not conn.is_closing()
    ech = get_channel(AGENT_BASE)
    assert ech is not None and ech.get_owner() is conn


def test_agents_cross_cells_via_ordinary_handover():
    """A stampede across the world produces ordinary handover journal
    entries and placement-ledger flips for agents — the same path human
    crossings take (zero loss: every agent still has exactly one cell
    row afterwards)."""
    ctl, _server, channels = make_world(census=2, sim_step_dt=0.5)
    run_ticks(ctl, channels, 2)
    eng = ctl.engine
    # Herd everyone to the far-right cell; crossings are inevitable.
    eng.sim_stampede(eng.grid.num_cells - 1)
    crossings_before = metrics.handover_count._value.get()
    for _ in range(40):
        run_ticks(ctl, channels, 1)
        pos = eng._positions[eng.agent_slots()]
        if (pos[:, 0] > 300.0).all():
            break
    assert metrics.handover_count._value.get() > crossings_before
    # Exactly one cell-table row per agent — no loss, no duplication.
    rows = {}
    for ch in channels:
        for eid in ch.get_data_message().entities:
            if eid >= AGENT_BASE:
                rows[eid] = rows.get(eid, 0) + 1
    assert len(rows) == 24 and set(rows.values()) == {1}
    # And the herd's center of mass moved into the rightmost cell's
    # table (arrived agents go IDLE and may wander back across the
    # x=300 boundary — a majority is the stable assertion).
    right = channels[-1]
    agent_rows_right = sum(
        1 for eid in right.get_data_message().entities if eid >= AGENT_BASE
    )
    assert agent_rows_right >= 16


def test_agents_and_humans_identical_to_query_plane():
    """PR 19 interplay: a standing sensor sees the world identically
    whether a position is occupied by an agent or a human — interest
    sets key on cells, and both kinds of entity live in the same cell
    tables."""
    ctl, server, channels = make_world(agents=8)
    run_ticks(ctl, channels, 2)
    hits = {}
    key = ctl.register_sensor(
        "watch", center=(87.5, 50.0), extent=(10.0, 0.0),
        callback=lambda k, cells: hits.update(cells),
    )
    assert key is not None
    run_ticks(ctl, channels, 2)
    want = dict(ctl.queryplane.sensor_cells(key))
    assert want and hits == want
    # A human entity in the same cell shares the table with any agents
    # there; the sensor's interest set is entity-kind-agnostic.
    from channeld_tpu.models import sim_pb2

    eid = ENTITY_START + 7
    d = sim_pb2.SimEntityChannelData()
    d.state.entityId = eid
    d.state.transform.position.x = 87.5
    d.state.transform.position.z = 50.0
    cell_ch = get_channel(ctl.get_channel_id(SpatialInfo(87.5, 0, 50.0)))
    cell_ch.get_data_message().add_entity(eid, d)
    ctl.track_entity(eid, SpatialInfo(87.5, 0, 50.0))
    run_ticks(ctl, channels, 2)
    assert dict(ctl.queryplane.sensor_cells(key)) == want
    assert cell_ch.id in want


def _fetches_per_tick(ticks, **world):
    """Device->host fetches of each of ``ticks`` controller ticks (the
    guarded step on its worker, as the gateway runs it), and whether
    each carried a census, after three ticks to compile and settle."""
    ctl, _server, channels = make_world(**world)
    run_ticks(ctl, channels, 3)
    ledgers = ctl.simplane.ledgers if ctl.simplane is not None else {}
    fetches, census = [], []
    for _ in range(ticks):
        before = ledgers.get("census_transfers", 0)
        with FetchCounter() as fc:
            run_ticks(ctl, channels, 1)
        fetches.append(fc.count)
        census.append(ledgers.get("census_transfers", 0) > before)
    return ctl, fetches, census


def test_sim_pass_adds_no_fetch_to_a_steady_tick():
    """A steady tick with the sim pass armed makes no device->host
    fetch beyond the no-sim tick's, and a census tick adds its own
    columns and no more (doc/simulation.md). The same driver loop over
    the same world on both sides."""
    assert global_settings.device_guard_enabled
    ticks = 12
    ctl, plain, _ = _fetches_per_tick(ticks, agents=200, sim_enabled=False)
    assert ctl.simplane is None
    assert len(set(plain)) == 1 and plain[0] > 0, plain
    steady = plain[0]

    fresh_runtime()
    register_sim_types()
    ctl, armed, census = _fetches_per_tick(ticks, agents=200, census=5)
    eng = ctl.engine
    assert eng.agent_count() == 200 and eng.run_sim_pass
    assert 2 <= sum(census) < ticks, census
    columns = 4  # position, velocity, state, target
    assert armed == [steady + (columns if c else 0) for c in census], (
        plain, armed, census)


def test_overload_l2_halves_sim_cadence_with_shed_double_entry():
    """At L2+ the population holds still every other scheduled pass —
    counted in agents held still, ledger and metric moving together —
    and resumes full cadence on de-escalation."""
    ctl, _server, channels = make_world(census=100)
    run_ticks(ctl, channels, 2)
    eng = ctl.engine
    base = eng.sim_tick
    governor.level = OverloadLevel.L2
    run_ticks(ctl, channels, 8)
    assert eng.sim_tick - base == 4  # exactly half
    assert governor.shed_counts.get("sim_cadence_defer") == 4 * 24
    assert metrics.overload_sheds.labels(
        reason="sim_cadence_defer")._value.get() == 4 * 24
    governor.level = OverloadLevel.L0
    base = eng.sim_tick
    run_ticks(ctl, channels, 4)
    assert eng.sim_tick - base == 4  # full cadence again


def test_geometry_epoch_rehomes_agents_zero_loss():
    """An apply_grid rebuild (the adaptive-partitioning commit path)
    re-homes every agent onto the new device grid with zero loss or
    duplication, bit-identical to the host shadow."""
    ctl, _server, channels = make_world()
    run_ticks(ctl, channels, 3)
    eng = ctl.engine
    ids_before = set(eng.agent_ids().tolist())
    assert len(ids_before) == 24
    eng.apply_grid(eng.grid, ctl.rebuild_seed_cells())
    seeds = ctl.rebuild_seed_cells()
    assert eng.verify_device_state(seeds) == []
    assert set(eng.agent_ids().tolist()) == ids_before
    run_ticks(ctl, channels, 3)
    assert eng.agent_count() == 24


def test_wal_replay_restores_exact_census(tmp_path):
    """kill -9 matrix: the journaled census restores the exact
    population — ids, positions, velocities, FSM states, waypoints and
    the RNG cursor — double-entry on the replay counter."""
    global_settings.wal_fsync_ms = 1.0
    wal.start(str(tmp_path / "gw.wal"))
    ctl, _server, channels = make_world(census=2)
    run_ticks(ctl, channels, 6)
    eng = ctl.engine
    slots = eng.agent_slots()
    want = {
        "ids": eng.agent_ids(slots).copy(),
        "pos": eng._positions[slots].copy(),
        "vel": eng._vel[slots].copy(),
        "state": eng._sim_state[slots].copy(),
        "target": eng._sim_target[slots].copy(),
        "tick": eng.sim_tick,
    }
    assert ctl.simplane.ledgers["censuses_journaled"] >= 1
    assert wal.flush()

    # kill -9: nothing shut down cleanly; a fresh process replays.
    fresh_runtime()
    register_sim_types()
    report = boot_replay("", str(tmp_path / "gw.wal"))
    assert not report["torn"]
    assert wal.replay_counts.get("sim_census") == len(want["ids"])

    global_settings.sim_enabled = True
    global_settings.sim_agents = 3  # must be ignored: the census wins
    ctl2 = TPUSpatialController()
    ctl2.load_config(
        dict(WorldOffsetX=0, WorldOffsetZ=0, GridWidth=100, GridHeight=100,
             GridCols=4, GridRows=1, ServerCols=1, ServerRows=1,
             ServerInterestBorderSize=1)
    )
    set_spatial_controller(ctl2)
    eng2 = ctl2.engine
    slots2 = eng2.agent_slots()
    assert ctl2.simplane.ledgers.get("agents_restored") == len(want["ids"])
    assert np.array_equal(eng2.agent_ids(slots2), want["ids"])
    assert np.array_equal(eng2._positions[slots2], want["pos"],
                          equal_nan=True)
    assert np.array_equal(eng2._vel[slots2], want["vel"], equal_nan=True)
    assert np.array_equal(eng2._sim_state[slots2], want["state"])
    assert np.array_equal(eng2._sim_target[slots2], want["target"],
                          equal_nan=True)
    assert eng2.sim_tick == want["tick"]
    assert eng2.sim_seed == global_settings.sim_seed


# ---------------------------------------------------------------------------
# chaos points under the device guard
# ---------------------------------------------------------------------------


def test_sim_step_nan_sentinel_heals_population():
    """sim.step_nan rots the agent rows on device; the readback sentinel
    catches the impossible cell baseline through the ORDINARY per-tick
    fetch (no extra transfers), the supervised rebuild re-seeds from the
    host shadow, and the census stays exact."""
    from channeld_tpu.core.device_guard import DeviceState, guard

    global_settings.device_guard_enabled = True
    ctl, _server, channels = make_world(census=1)
    run_ticks(ctl, channels, 3)
    eng = ctl.engine
    ids_before = set(eng.agent_ids().tolist())
    arm({"seed": 4, "faults": [
        {"point": "sim.step_nan", "every_n": 1, "max_fires": 1}]})
    run_ticks(ctl, channels, 3)
    disarm()
    assert guard.recovery_counts.get("corruption", 0) >= 1
    assert guard.state == DeviceState.ACTIVE
    assert ctl.simplane.ledgers.get("chaos_nan") == 1
    assert set(eng.agent_ids().tolist()) == ids_before
    pos = np.asarray(eng._d_positions)[eng.agent_slots()]
    assert np.isfinite(pos).all()
    run_ticks(ctl, channels, 2)  # keeps serving


def test_sim_smoke_soak():
    """Seeded <60s run of the sim soak machinery (scripts/sim_soak.py):
    steady censuses -> stampede -> sim.step_nan guard rebuild ->
    geometry epoch -> WAL replay of an abandoned (never shut down)
    world, with the exact-census invariant (0 lost, 0 duplicated) at
    every phase boundary. The full acceptance soak (SOAK_SIM_r20.json)
    SIGKILLs a real child process instead of the in-process replay."""
    import importlib.util
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "sim_soak", os.path.join(repo, "scripts", "sim_soak.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["sim_soak"] = mod
    spec.loader.exec_module(mod)
    p = mod.SoakParams(agents=32, humans=8, steady_ticks=20,
                       stampede_ticks=20, guard_ticks=8, epoch_ticks=6,
                       census_every=3, subprocess_kill=False)
    report = mod.run_soak(p)
    failed = [c for c in report["invariants"]["checks"] if not c["ok"]]
    assert report["invariants"]["ok"], failed


def test_sim_stampede_chaos_herds_population():
    ctl, _server, channels = make_world(census=4)
    run_ticks(ctl, channels, 1)
    eng = ctl.engine
    arm({"seed": 5, "faults": [
        {"point": "sim.stampede", "every_n": 1, "max_fires": 1}]})
    run_ticks(ctl, channels, 1)
    disarm()
    assert ctl.simplane.ledgers.get("chaos_stampede") == 1
    states = eng._sim_state[eng.agent_slots()]
    assert (states == SIM_SEEK).all()
    run_ticks(ctl, channels, 10)
    # Everyone was pointed at the grid-center cell's center (cell 2 of
    # the 4x1 world: x=250, z=50).
    tgt = eng._sim_target[eng.agent_slots()]
    assert np.allclose(tgt[:, 0], 250.0) and np.allclose(tgt[:, 2], 50.0)
