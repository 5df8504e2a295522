"""The device's idle time put down to the loop thread's spans, on
hand-made intervals; and each reader PR 25 added, on a hand-made ``ctx``:
a value where its counter or span is there, ``None`` and never 0 where
it is not (a program older than its spans)."""

import glob
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import driver, host_spans

HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
MS = 1_000_000  # ns
LOOP, WORKER = 3, 5  # lines of the host plane

# One GLOBAL tick of 30 ms with the device step inside it, then two
# channel ticks, then nothing: the loop asleep.
HOST = [
    (LOOP, "channeld/tick.GLOBAL", 0, 30 * MS),
    (LOOP, "channeld/device_step", 2 * MS, 20 * MS),
    (LOOP, "channeld/publish_due", 20 * MS, 22 * MS),
    (LOOP, "channeld/tick.SPATIAL", 30 * MS, 40 * MS),
    (LOOP, "channeld/fanout", 32 * MS, 38 * MS),
    (LOOP, "channeld/tick.ENTITY", 40 * MS, 50 * MS),
    (LOOP, "channeld/ingest_inline", 60 * MS, 62 * MS),
    (WORKER, "channeld/step.flush", 3 * MS, 5 * MS),
    (WORKER, "channeld/step.dispatch", 5 * MS, 8 * MS),
    (WORKER, "channeld/step.fetch", 8 * MS, 19 * MS),
]
OPS = [(6 * MS, 10 * MS), (9 * MS, 16 * MS), (35 * MS, 36 * MS)]
WINDOW = (0, 100 * MS)


def test_innermost_gives_each_span_its_self_time():
    segments = host_spans.innermost(
        (name, s, e) for line, name, s, e in HOST if line == LOOP)
    assert segments[:4] == [
        (0, 2 * MS, "channeld/tick.GLOBAL", "channeld/tick.GLOBAL"),
        (2 * MS, 20 * MS, "channeld/device_step", "channeld/tick.GLOBAL"),
        (20 * MS, 22 * MS, "channeld/publish_due", "channeld/tick.GLOBAL"),
        (22 * MS, 30 * MS, "channeld/tick.GLOBAL", "channeld/tick.GLOBAL"),
    ]
    self_ms: dict = {}
    for s, e, inner, _ in segments:
        self_ms[inner] = self_ms.get(inner, 0) + (e - s) / MS
    assert self_ms == {
        "channeld/tick.GLOBAL": 10, "channeld/device_step": 18,
        "channeld/publish_due": 2, "channeld/tick.SPATIAL": 4,
        "channeld/fanout": 6, "channeld/tick.ENTITY": 10,
        "channeld/ingest_inline": 2}
    # Disjoint, sorted, and never longer than what the spans cover.
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))


def test_the_three_shares_add_up_to_the_idle_share():
    out = host_spans.split_idle(OPS, HOST, WINDOW)
    # busy [6,16) and [35,36): 11 ms of 100
    assert out["idle_s"] == pytest.approx(0.089)
    assert (out["global_tick_s"] + out["channel_ticks_s"]
            + out["unspanned_s"]) == pytest.approx(out["idle_s"])
    assert out["global_tick_s"] == pytest.approx(0.020)  # 30 ms less [6,16)
    assert out["channel_ticks_s"] == pytest.approx(0.019)  # 20 ms less 1
    assert out["unspanned_s"] == pytest.approx(0.050)  # [50,100)
    assert sum(out["by_span"].values()) == pytest.approx(out["idle_s"])


def test_nested_spans_give_self_time_and_another_threads_do_not_count():
    out = host_spans.split_idle(OPS, HOST, WINDOW)
    assert out["loop_line"] == LOOP
    by = out["by_span"]
    # device_step is [2,20); the device ran in [6,16): 8 ms of it idle.
    assert by["channeld/device_step"] == pytest.approx(0.008)
    # tick.GLOBAL's own time is [0,2) and [22,30), not its whole 30 ms.
    assert by["channeld/tick.GLOBAL"] == pytest.approx(0.010)
    assert by["channeld/fanout"] == pytest.approx(0.005)  # 6 ms less [35,36)
    # ingest_inline ran on the loop thread inside no tick: named in
    # by_span, unspanned among the three shares.
    assert by["channeld/ingest_inline"] == pytest.approx(0.002)
    assert by[host_spans.OUTSIDE] == pytest.approx(0.048)
    # The worker's spans lie over the same instants and take nothing.
    assert not [name for name in by if "step." in name]
    # The longest gap, [36,100), with the seconds of it under each span.
    longest = out["gaps"][0]
    assert longest["seconds"] == pytest.approx(0.064)
    assert longest["at_s"] == pytest.approx(0.036)
    assert longest["by_span"] == {
        host_spans.OUTSIDE: pytest.approx(0.048),
        "channeld/tick.ENTITY": pytest.approx(0.010),
        "channeld/fanout": pytest.approx(0.002),
        "channeld/tick.SPATIAL": pytest.approx(0.002),
        "channeld/ingest_inline": pytest.approx(0.002)}
    assert list(longest["by_span"])[:2] == [host_spans.OUTSIDE,
                                            "channeld/tick.ENTITY"]
    assert [g["seconds"] for g in out["gaps"]] == sorted(
        (g["seconds"] for g in out["gaps"]), reverse=True)


def test_a_trace_without_tick_spans_gives_nothing():
    assert host_spans.split_idle(OPS, [], WINDOW) is None
    only_worker = [s for s in HOST if s[0] == WORKER]
    assert host_spans.split_idle(OPS, only_worker, WINDOW) is None


def test_executions_between_a_dispatch_and_the_fetch_that_follows():
    clock = host_spans.steps_inside(
        [(6 * MS, 10 * MS),      # inside [5, 19]
         (18 * MS, 20 * MS),     # outlives the fetch
         (1 * MS, 2 * MS)],      # before any dispatch
        HOST)
    # The second is cut off by the end of the last fetch, the third began
    # before the first dispatch: neither has a pair to lie between.
    assert clock == {"program": "jit_spatial_step", "executions": 3,
                     "bracketed": 1, "inside_dispatch_to_fetch": 1}


def planes(with_spans=True):
    host = [("python3", []), ("python3", []), ("python3", []),
            ("python3", [(n, s, e - s) for line, n, s, e in HOST
                         if line == LOOP and with_spans]
             + [("PjitFunction(spatial_step)", 5 * MS, MS)]),
            ("tf_pool", []),
            ("python3", [(n, s, e - s) for line, n, s, e in HOST
                         if line == WORKER and with_spans])]
    modules = [("jit_spatial_step(77)", 6 * MS, 10 * MS),
               ("jit__set_rows(5)", 35 * MS, MS)]
    return [("/host:CPU", host),
            ("/device:TPU:0", [
                ("XLA Ops", [("fusion.1", s, e - s) for s, e in OPS]),
                ("XLA Modules", modules)]),
            ("Task Environment", [("", [("end", 99 * MS, MS)])])]


def test_reduce_planes_names_the_gaps_and_checks_the_clock():
    out = host_spans.reduce_planes(planes())
    assert out["host_spans"] == len(HOST)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["idle_s"] == pytest.approx(0.089)
    assert out["clock"] == {"program": "jit_spatial_step", "executions": 1,
                            "bracketed": 1, "inside_dispatch_to_fetch": 1}
    assert out["gaps"][0]["between"] == "jit__set_rows -> window end"
    assert out["gaps"][1]["between"] == "jit_spatial_step -> jit__set_rows"
    json.dumps(out)  # plain numbers and strings all the way down


def test_a_program_older_than_its_spans_gives_no_share():
    assert host_spans.reduce_planes(planes(with_spans=False)) == {
        "host_spans": 0}
    assert host_spans.reduce_planes(planes()[:1]) == {"host_spans": 0}


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(HERE, "data", "*.xplane.pb"))))
def test_a_recorded_trace_of_before_the_spans_reads_as_none(path, tmp_path):
    """The traces kept in ``data/`` were recorded before the program had
    its annotations: the helper must say so, not fail."""
    done = subprocess.run(
        [sys.executable, host_spans.__file__, path], capture_output=True,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.splitlines()[-1]) == {"host_spans": 0}
    assert not os.path.exists(os.path.join(
        os.path.dirname(path), "idle_by_host_span.json"))


# ---- the readers -----------------------------------------------------------


def reader(name):
    return driver.load_file(os.path.join(BASE, "layer_metrics", name + ".py"),
                            "layer_metric_" + name)


def sample(name, value, **labels):
    return {(name, tuple(sorted(labels.items()))): value}


def stage(name, total_ms, count):
    return {**sample("tick_stage_ms_sum", total_ms, stage=name),
            **sample("tick_stage_ms_count", count, stage=name)}


def late(metric, channel_type, total_ms, count):
    return {**sample(metric + "_sum", total_ms, channel_type=channel_type),
            **sample(metric + "_count", count, channel_type=channel_type)}


NEW = {
    **stage("device_step", 3280.0, 200), **stage("step.flush", 100.0, 200),
    **stage("step.dispatch", 500.0, 200), **stage("step.fetch", 2400.0, 200),
    **stage("step.census_fetch", 120.0, 4), **stage("sim_census", 180.0, 4),
    **stage("publish_due", 60.0, 200), **stage("ingest_inline", 300.0, 20000),
    **stage("ingest", 0.0, 0), **stage("stash_retry", 100.0, 3),
    **late("tick_late_ms", "GLOBAL", 4600.0, 200),
    **late("tick_late_ms", "SPATIAL", 45000.0, 3000),
    **late("tick_late_ms", "ENTITY", 15000.0, 1000),
    **late("fanout_window_lag_ms", "SPATIAL", 90000.0, 300),
    **late("fanout_window_lag_ms", "ENTITY", 10.0, 5000),
    **sample("channel_tick_duration_sum", 6.0, channel_type="GLOBAL"),
    **sample("channel_tick_duration_sum", 9.0, channel_type="SPATIAL"),
    **sample("channel_tick_duration_count", 200, channel_type="GLOBAL"),
    **sample("channel_tick_duration_count", 3000, channel_type="SPATIAL"),
}
# What the program exposed before PR 25: the old stages, and ingest at 0.
OLD = {**stage("device_step", 3280.0, 200), **stage("ingest", 0.0, 0),
       **stage("handover", 290.0, 200)}
EXPECTED = {
    "global_tick_late_ms": 23.0,
    "channel_tick_late_ms": 15.0,       # (45000 + 15000) / (3000 + 1000)
    "loop_tick_busy_pct": 75.0,         # 15 s of 20
    "device_flush_dispatch_ms": 3.0,    # (100 + 500) / 200
    "device_fetch_ms": 12.0,
    "census_stall_ms": 75.0,            # (120 + 180) / 4
    "publish_due_ms": 0.3,
    "fanout_window_lag_ms": 300.0,      # SPATIAL alone
    "ingest_busy_pct": 2.0,             # 400 ms of 20 s
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_ctx(name):
    read = reader(name).read
    ctx = {"metrics": NEW, "wall_s": 20.0, "trace": None, "base": BASE}
    assert read(ctx) == pytest.approx(EXPECTED[name])
    # The parent's /metrics has none of the new families and stages: the
    # metric is left out of the line. Never 0.
    if name != "loop_tick_busy_pct":  # that counter the program always had
        assert read(dict(ctx, metrics=OLD)) is None
    assert read(dict(ctx, metrics={})) is None


IDLE = {"idle_global_tick_pct": ("global_tick_s", 20.0),
        "idle_channel_ticks_pct": ("channel_ticks_s", 50.0),
        "idle_unspanned_pct": ("unspanned_s", 15.0)}


@pytest.mark.parametrize("name", sorted(IDLE))
def test_idle_share_readers(name, tmp_path, monkeypatch):
    read = reader(name).read
    part, want = IDLE[name]
    # Not a traced run, or a traced run that left no trace file: None.
    assert read({"trace": None, "base": str(tmp_path)}) is None
    traced = {"trace": {"window_s": 3.0, "busy_s": 0.45},
              "base": str(tmp_path)}
    assert read(traced) is None
    # The helper's answer for the run's trace, the newest under out/.
    where = tmp_path / "out" / "cell" / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(b"")
    split = {"idle_s": 2.4, "window_s": 2.85, "global_tick_s": 0.6,
             "channel_ticks_s": 1.5, "unspanned_s": 0.3}
    monkeypatch.setitem(host_spans._read, str(where / "vm.xplane.pb"), split)
    # The profiler was on for 3.0 s and the events span 2.85: the rest is
    # idle under no span, so the three add up to device_idle_pct (85).
    assert read(traced) == pytest.approx(want)
    assert sum(reader(n).read(traced) for n in IDLE) == pytest.approx(
        100.0 * (1 - 0.45 / 3.0))
    # A gateway that made no channeld/ event: None for each.
    monkeypatch.setitem(host_spans._read, str(where / "vm.xplane.pb"),
                        {"host_spans": 0})
    assert read(traced) is None
