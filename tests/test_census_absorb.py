"""What a sim census leaves behind (sim/plane.py ``_absorb_census``,
spatial/last_positions.py, sim/authority.py ``commit``).

The census is absorbed as arrays: a fixed number of Python objects,
whatever the population. The plain reference is the per-agent loop the
program had before, kept HERE and nowhere else: one ``SpatialInfo`` per
agent into a dict, a list of rows handed to the authority. One seeded
scenario is driven twice, once over the reference and once over the
program, and everything a census leaves behind is compared read for
read: ``_last_positions`` (``get``, ``in``, after ``pop``, after an
ordinary update, after ``setdefault``), the authority's commits, the
journaled record's bytes and the engine's host shadow.
"""

import gc
import time

import numpy as np
import pytest

from channeld_tpu.core import metrics
from channeld_tpu.core.channel import all_channels, get_channel
from channeld_tpu.core.device_guard import guard
from channeld_tpu.core.message import MessageContext
from channeld_tpu.core.overload import OverloadLevel, governor
from channeld_tpu.core.settings import global_settings
from channeld_tpu.core.subscription import subscribe_to_channel
from channeld_tpu.core.tracing import (
    _on_gc,
    flush_gc_pauses,
    install_gc_callback,
    recorder,
)
from channeld_tpu.core.types import ConnectionType, MessageType
from channeld_tpu.core.wal import reset_wal, wal
from channeld_tpu.models.sim import register_sim_types
from channeld_tpu.protocol import control_pb2
from channeld_tpu.sim.authority import SimAuthority
from channeld_tpu.sim.plane import (
    AGENT_ID_OFFSET,
    SimPlane,
    reset_sim,
)
from channeld_tpu.spatial import tpu_controller
from channeld_tpu.spatial.controller import SpatialInfo, set_spatial_controller
from channeld_tpu.spatial.last_positions import LastPositions
from channeld_tpu.spatial.tpu_controller import TPUSpatialController

from helpers import StubConnection, fresh_runtime, stage_count

ENTITY_START = 0x80000
AGENT_BASE = ENTITY_START + AGENT_ID_OFFSET
WIRE = ENTITY_START + 1      # a wire entity, moved by the ordinary path
NEWCOMER = AGENT_BASE + 9000  # an agent that takes a freed slot in flight
AGENTS = 512


@pytest.fixture(autouse=True)
def runtime():
    yield
    governor.level = OverloadLevel.L0
    reset_wal()
    reset_sim()


# ---------------------------------------------------------------------------
# the plain reference: the census as the program absorbed it before
# ---------------------------------------------------------------------------


class DictLastPositions(dict):
    """``_last_positions`` as it was: a dict, one ``SpatialInfo`` an
    entity."""

    def bind(self, engine) -> None:
        pass

    def absorb_census(self, slots, ids, positions) -> None:
        for eid, (x, y, z) in zip(ids.tolist(), positions.tolist()):
            self[eid] = SpatialInfo(x, y, z)


def reference_absorb_census(self, result: dict, census) -> None:
    """``SimPlane._absorb_census`` with its per-agent loop."""
    from channeld_tpu.core.wal import wal as _wal

    eng = self.engine
    pos, vel, state, target = (np.asarray(a) for a in census)
    slots = eng.agent_slots()
    churn = result.get("churn")
    if churn is not None and churn.entities:
        slots = slots[~np.isin(slots, list(churn.entities))]
    eng.absorb_census(slots, pos, vel, state, target)
    ids = eng.agent_ids(slots)
    self._since_census = 0
    metrics.sim_census_transfers.inc()
    self._count("census_transfers", 1)
    sim_tick = int(result.get("sim_tick", eng.sim_tick))
    if _wal.enabled:
        _wal.log_sim_census(
            sim_tick, eng.sim_seed, ids, pos[slots], vel[slots],
            state[slots], target[slots],
        )
        self._count("censuses_journaled", 1)
    ctl = self.controller
    agent_pos = pos[slots].tolist()
    for i, eid in enumerate(ids):
        px, py, pz = agent_pos[i]
        ctl._last_positions[int(eid)] = SpatialInfo(px, py, pz)
    committed = self.authority.commit(ids, agent_pos)
    self._count("census_commits", committed)
    metrics.sim_agents_num.set(eng.agent_count())


def reference_commit(self, ids, positions) -> int:
    """``SimAuthority.commit`` over the list of every agent's row."""
    from channeld_tpu.models import sim_pb2

    if not self._backed:
        return 0
    ctl = self.controller
    n = 0
    for i, eid in enumerate(ids):
        eid = int(eid)
        if eid not in self._backed:
            continue
        ch = get_channel(eid)
        if ch is None or ch.is_removing():
            self._backed.discard(eid)
            continue
        upd = sim_pb2.SimEntityChannelData()
        upd.state.entityId = eid
        upd.state.transform.position.x = positions[i][0]
        upd.state.transform.position.z = positions[i][2]

        def _apply(c, u=upd):
            owner = c.get_owner()
            c.data.on_update(
                u, c.get_time(), owner.id if owner is not None else 0, ctl)

        ch.execute(_apply)
        n += 1
    self._count("commits", 1)
    self._count("updates", n)
    return n


# ---------------------------------------------------------------------------
# the scenario
# ---------------------------------------------------------------------------


def make_world(agents=AGENTS, channel_agents=0, census=2, capacity=1024):
    fresh_runtime()
    register_sim_types()
    reset_sim()
    global_settings.tpu_entity_capacity = capacity
    global_settings.tpu_query_capacity = 16
    global_settings.sim_enabled = True
    global_settings.sim_agents = agents
    global_settings.sim_channel_agents = channel_agents
    global_settings.sim_census_every_ticks = census
    global_settings.sim_max_speed = 20.0
    global_settings.sim_p_wander = 0.6
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
    for ch in ctl.create_channels(ctx):
        subscribe_to_channel(server, ch, None)
    return ctl


def tick_world(ctl) -> None:
    ctl.tick()
    for ch in list(all_channels().values()):
        if ch.id != 0:
            ch.tick_once(0)


def read(ctl, eid):
    """One id through every read the mapping's users make."""
    last = ctl._last_positions
    info = last.get(eid)
    here = eid in last
    assert here == (info is not None)
    if here:
        assert last[eid] == info
    return None if info is None else (info.x, info.y, info.z)


def observe(ctl) -> dict:
    eng, plane = ctl.engine, ctl.simplane
    watched = [AGENT_BASE + i for i in range(AGENTS)]
    watched += [WIRE, NEWCOMER, AGENT_BASE + AGENTS + 7]
    backed = sorted(plane.authority._backed)
    channel_rows = {}
    for eid in backed:
        ch = get_channel(eid)
        if ch is not None:
            p = ch.get_data_message().state.transform.position
            channel_rows[eid] = (p.x, p.z)
    return {
        "last": {eid: read(ctl, eid) for eid in watched},
        "tracked": len(ctl._last_positions),
        "shadow": [a.tobytes() for a in (eng._positions, eng._vel,
                                         eng._sim_state, eng._sim_target)],
        "plane": dict(plane.ledgers),
        "authority": dict(plane.authority.ledgers),
        "backed": backed, "channel_rows": channel_rows,
        "data_cell": dict(ctl._data_cell),
    }


def drive(case: str, tmp_path, monkeypatch) -> list:
    """The scenario, a few censuses long; what it left behind, step by
    step."""
    journaled = []
    if case == "wal":
        global_settings.wal_fsync_ms = 1.0
        wal.start(str(tmp_path / f"gw-{len(list(tmp_path.iterdir()))}.wal"))
        append = wal.append

        def recording(kind, rec, *a, **kw):
            if kind == "sim_census":
                journaled.append(rec.SerializeToString())
            return append(kind, rec, *a, **kw)

        monkeypatch.setattr(wal, "append", recording)
    ctl = make_world(channel_agents=48 if case == "backed" else 0)
    eng = ctl.engine
    log = []
    ctl.track_entity(WIRE, SpatialInfo(50.0, 0.0, 50.0))
    for tick in range(1, 11):
        if tick == 3:
            # The ordinary update path: a wire entity, a channel-backed
            # (or engine-only) agent and an agent nobody backs each read
            # back what was written, until the next census.
            for eid, x in ((WIRE, 61.25), (AGENT_BASE + 3, 17.125),
                           (AGENT_BASE + 300, 333.0625)):
                old = ctl._last_positions.get(eid)
                ctl.notify(old, SpatialInfo(x, 0.0, 40.0),
                           lambda s, d, e=eid: e)
                assert read(ctl, eid) == (x, 0.0, 40.0)
        if tick == 5:
            # observe_entity keeps what is known (setdefault) and
            # records what is not.
            known = read(ctl, AGENT_BASE + 5)
            ctl.observe_entity(AGENT_BASE + 5, SpatialInfo(1.0, 0.0, 1.0))
            assert read(ctl, AGENT_BASE + 5) == known
        if tick == 6:
            ctl.untrack_entity(AGENT_BASE + 8)  # pop: gone from both stores
            assert read(ctl, AGENT_BASE + 8) is None
        if case == "churn" and tick == 8:
            # A census tick (census every 2 passes): the slot of agent 11
            # changes owner while the step is in flight.
            slot = eng.slot_of_entity(AGENT_BASE + 11)
            step = ctl.begin_tick()
            ctl.untrack_entity(AGENT_BASE + 11)
            ctl.track_entity(NEWCOMER, SpatialInfo(210.5, 0.0, 20.25))
            eng.seed_agents([(NEWCOMER, 210.5, 0.0, 20.25)], eng.sim_seed,
                            eng.sim_params)
            assert eng.slot_of_entity(NEWCOMER) == slot
            assert step.guarded.batch.churn.entities == {slot}
            guard.wait_step(step.guarded)
            ctl.finish_tick(step)
            assert ctl.simplane._since_census == 0  # it was a census
            # The newcomer keeps its host value; the census's row for
            # that slot was another entity's.
            assert read(ctl, NEWCOMER) == (210.5, 0.0, 20.25)
            for ch in list(all_channels().values()):
                if ch.id != 0:
                    ch.tick_once(0)
        else:
            tick_world(ctl)
        log.append(observe(ctl))
    assert ctl.simplane.ledgers["census_transfers"] >= 4
    if case == "backed":
        assert len(ctl.simplane.authority._backed) >= 40
        assert ctl.simplane.authority.ledgers["updates"] > 0
    if case == "wal":
        assert wal.flush()
        assert len(journaled) == ctl.simplane.ledgers["censuses_journaled"]
    log.append({"journaled": journaled})
    return log


@pytest.mark.parametrize("case", ["engine_only", "backed", "wal", "churn"])
def test_a_census_leaves_behind_what_the_per_agent_loop_left(
        case, tmp_path, monkeypatch):
    with monkeypatch.context() as ref:
        ref.setattr(tpu_controller, "LastPositions", DictLastPositions)
        ref.setattr(SimPlane, "_absorb_census", reference_absorb_census)
        ref.setattr(SimAuthority, "commit", reference_commit)
        want = drive(case, tmp_path, ref)
        assert type(tpu_controller.LastPositions()) is DictLastPositions
    reset_wal()
    got = drive(case, tmp_path, monkeypatch)
    assert len(got) == len(want) == 11
    for step, (g, w) in enumerate(zip(got, want)):
        for key in w:
            assert g[key] == w[key], (step, key)


def test_the_mapping_keeps_one_store_an_id():
    """Item assignment drops the row, a census drops the entry, a freed
    slot reads as absent, and iteration names every id once."""
    ctl = make_world(agents=16, capacity=64, census=1)
    last = ctl._last_positions
    assert type(last) is LastPositions
    a, b = AGENT_BASE, AGENT_BASE + 1
    assert not last._infos and len(last) == 16  # rows from activation
    last[a] = SpatialInfo(1.0, 2.0, 3.0)
    assert last[a] == SpatialInfo(1.0, 2.0, 3.0) and len(last) == 16
    assert list(last._infos) == [a]
    assert last.setdefault(b, SpatialInfo(9.0, 9.0, 9.0)) == last[b]
    assert last.pop(b) is not None and b not in last and len(last) == 15
    with pytest.raises(KeyError):
        last[b]
    tick_world(ctl)  # a census: the entry of `a` gives way to its row
    assert not last._infos and a in last
    assert sorted(last) == sorted(
        int(e) for e in ctl.engine.agent_ids())
    # The engine lets go of a slot behind the mapping's back: absent.
    ctl.engine.remove_entity(a)
    assert a not in last and last.get(a) is None


# ---------------------------------------------------------------------------
# no object per agent
# ---------------------------------------------------------------------------


def collections_during(fn) -> int:
    """Generation-0 collections ``fn`` trips with the threshold at 50
    container allocations: one for every 50 objects it leaves alive at
    a time."""
    old = gc.get_threshold()
    gc.collect()
    gc.set_threshold(50, 1_000_000, 1_000_000)
    try:
        before = gc.get_stats()[0]["collections"]
        fn()
        return gc.get_stats()[0]["collections"] - before
    finally:
        gc.set_threshold(*old)


def test_a_census_allocates_no_object_per_agent(monkeypatch):
    ctl = make_world(agents=1024, capacity=2048, census=1000)
    plane, eng = ctl.simplane, ctl.engine
    for _ in range(2):
        tick_world(ctl)
    census = (eng._positions.copy(), eng._vel.copy(), eng._sim_state.copy(),
              eng._sim_target.copy())
    result = {"sim_tick": eng.sim_tick}
    plane._absorb_census(result, census)  # first use of each stage label
    took = collections_during(lambda: plane._absorb_census(result, census))
    assert took <= 2, took
    # The yardstick measures what it should: the per-agent loop leaves
    # 1,024 rows and 1,024 SpatialInfo alive at a time.
    monkeypatch.setattr(SimPlane, "_absorb_census", reference_absorb_census)
    ref = collections_during(lambda: plane._absorb_census(result, census))
    assert ref >= 1024 // 50, ref


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------


def test_gc_pause_ms_and_census_tick_ms_move_with_what_they_count():
    install_gc_callback()
    install_gc_callback()
    assert gc.callbacks.count(_on_gc) == 1
    try:
        pause = {g: metrics.gc_pause_ms.labels(generation=str(g))
                 for g in (0, 1, 2)}
        before = {g: (c._count.get(), c._sum.get()) for g, c in pause.items()}
        junk = [[i] for i in range(20000)]
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)
        # The callback runs inside the collector, maybe under a lock
        # its thread holds: it touches no registry and no ring. The
        # GLOBAL tick carries what it added up.
        assert pause[2]._count.get() == before[2][0]
        flush_gc_pauses()
        gc.collect(2)
        del junk
        flush_gc_pauses()
        moved = {g: c._count.get() - before[g][0] for g, c in pause.items()}
        # Generation 0 is not timed (other collections may run besides
        # the explicit ones, never fewer).
        assert moved[0] == 0 and moved[1] >= 1 and moved[2] >= 2
        assert pause[2]._sum.get() > before[2][1]
        spans = [s for s in recorder.snapshot() if s["name"] == "gc.gen2"]
        assert len(spans) >= 2 and all(s["dur_ns"] > 0 for s in spans)
    finally:
        gc.callbacks.remove(_on_gc)

    ctl = make_world(agents=32, capacity=64, census=2)
    gch = get_channel(0)
    ticks0 = metrics.census_tick_ms._count.get()
    stages0 = {s: stage_count(s) for s in (
        "sim_census", "sim_census.absorb", "sim_census.journal",
        "sim_census.commit")}
    sum0 = metrics.census_tick_ms._sum.get()
    duration = metrics.channel_tick_duration.labels(channel_type="GLOBAL")
    durations = []
    for _ in range(6):
        d0 = duration._sum.get()
        t0 = time.monotonic()
        gch.tick_once(0)
        durations.append((duration._sum.get() - d0, time.monotonic() - t0))
    censuses = ctl.simplane.ledgers["census_transfers"]
    assert censuses == 3
    assert metrics.census_tick_ms._count.get() - ticks0 == censuses
    # Each is the whole duration of its tick, as the ladder read it:
    # more than nothing, no more than those ticks took.
    charged_ms = metrics.census_tick_ms._sum.get() - sum0
    assert 0 < charged_ms <= sum(w for _, w in durations) * 1e3
    census_ticks = [d for i, (d, _) in enumerate(durations) if i % 2 == 1]
    assert charged_ms == pytest.approx(sum(census_ticks) * 1e3, rel=1e-6)
    for stage in ("sim_census", "sim_census.absorb", "sim_census.commit"):
        assert stage_count(stage) - stages0[stage] == censuses, stage
    assert stage_count("sim_census.journal") == stages0["sim_census.journal"]
