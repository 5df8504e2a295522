"""One run of one cell: boot the gateway, have the cell's flow build its
deployment over the wire, warm up, measure for the window, drain, and
make the result the contract's last line carries from what the flow
held against its reference.

Everything that belongs to one configuration, one mix, one generator,
one kernel, one per-layer metric or one kind of deployment (a flow) is
data or a file of its own, found from ``BENCHMARK.json`` by name
(``load_cell``, ``load_flow``). This module is what every deployment
shares: the gateway, the master peer and GLOBAL, the clocks of the run,
the gateway's own counters and trace, and the result.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import time

from . import stats, wire
from .gateway import REPO, BenchFailure, Gateway, delta, total
from .workers import run_worker

TRACE_S = 3.0  # the traced part of a --trace 1 window


def say(msg: str) -> None:
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------


def load_cell(root: str, workload: str) -> dict:
    """Everything ``BENCHMARK.json`` under ``root`` says of one cell: its
    configuration's file, its mix's file, and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r}; there are: "
                           f"{', '.join(sorted(cells))}")
    cell = cells[workload]
    base = os.path.join(root, bench["paths"][0])
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(base, "peaks.json")) as f:
        peaks = json.load(f)

    def of_cell(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"name": workload, "chips": cell["chips"], "config": config,
            "mix": mix, "base": base, "root": root, "peaks": peaks,
            "end_to_end": of_cell(bench["end_to_end"]),
            "per_layer": of_cell(bench["per_layer"])}


def load_file(path: str, name: str):
    """A per-layer metric's reader or a kernel's counts: a module of its
    own, loaded from its file."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_flow(cell: dict):
    """The module that builds and accounts for the cell's kind of
    deployment: ``flows/<flow>.py`` for the ``flow`` its configuration
    names, ``spatial`` where it names none.

    A flow gives ``WORKERS`` (its peer kinds, ``harness/workers.py:Worker``
    subclasses, by name); ``prepare(cell, seed, seconds)``, before the
    gateway boots, with ``specs``, a ``(kind, spec)`` for each worker
    process; ``build(cell, prepared, gw, master, peers, workers)`` once
    GLOBAL exists, which makes the world and returns it; ``after_window(
    world, workers)`` once the drain is over; and ``account(cell, run)``
    (``assemble`` says what it returns)."""
    name = cell["config"].get("flow", "spatial")
    where = os.path.join(cell["base"], "flows")
    path = os.path.join(where, name + ".py")
    if not os.path.isfile(path):
        there = sorted(f[:-3] for f in os.listdir(where) if f.endswith(".py"))
        raise BenchFailure(f"no flow {name!r}; there are: {', '.join(there)}")
    return load_file(path, "flow_" + name)


def roofline_pct(ctx: dict, kernel: str):
    """Share of its roofline that ``kernel`` reached in the traced
    window: the least time the chip could take for its operations and
    bytes (``kernels/<kernel>.py``) over the device time of its program's
    executions. None when the trace holds none."""
    counts = load_file(os.path.join(ctx["base"], "kernels", kernel + ".py"),
                       "kernel_" + kernel)
    seen = (ctx["trace"] or {}).get("modules", {}).get(counts.PROGRAM)
    if not seen or not seen["seconds"]:
        return None
    peak = ctx["peak"]
    least = max(counts.ops(ctx["shapes"]) / peak["flops_per_s"],
                counts.bytes(ctx["shapes"]) / peak["bytes_per_s"])
    return 100.0 * least * seen["count"] / seen["seconds"]


def stage_ms(ctx: dict, *stages: str) -> float:
    """Milliseconds the gateway's tick spent in ``stages`` over the
    window, from ``tick_stage_ms_sum``."""
    return sum(total(ctx["metrics"], "tick_stage_ms_sum", stage=s)
               for s in stages)


def stage_count(ctx: dict, stage: str) -> float:
    return total(ctx["metrics"], "tick_stage_ms_count", stage=stage)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def build_native() -> None:
    """The native codec, built from source where it is missing or older
    than its source: which ingest path a gateway runs must not depend on
    a library that lay in the tree."""
    native = os.path.join(REPO, "channeld_tpu", "native")
    built = glob.glob(os.path.join(native, "_codec*.so"))
    source = os.path.getmtime(os.path.join(native, "codec.cc"))
    if built and all(os.path.getmtime(p) >= source for p in built):
        return
    for stale in built:
        os.remove(stale)
    done = subprocess.run(
        ["sh", os.path.join(REPO, "scripts", "build_native.sh")], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise BenchFailure(f"native codec build failed:\n{done.stderr[-2000:]}")


def cache_entries() -> int:
    """Compiled programs in the persistent cache the gateway uses."""
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    return len(glob.glob(os.path.join(where, "*-cache")))


class Workers:
    """The spawned peers and the pipes to them."""

    def __init__(self, specs: list):
        ctx = multiprocessing.get_context("spawn")
        self.procs, self.pipes, self.kinds = [], [], []
        for kind, spec in specs:
            ours, theirs = ctx.Pipe()
            proc = ctx.Process(target=run_worker, args=(kind, theirs, spec),
                               daemon=True)
            proc.start()
            theirs.close()
            self.procs.append(proc)
            self.pipes.append(ours)
            self.kinds.append(kind)

    def start(self, kinds, name: str, *args) -> list:
        """Begin one phase in every worker of ``kinds``; who was asked."""
        chosen = [i for i, k in enumerate(self.kinds) if k in kinds]
        for i in chosen:
            self.pipes[i].send((name, *args))
        return chosen

    def collect(self, chosen: list, name: str, timeout: float) -> list:
        """The answers of the workers asked, in their order."""
        out = []
        end = time.monotonic() + timeout
        for i in chosen:
            who = f"{self.kinds[i]} {i}"
            if not self.pipes[i].poll(max(0.0, end - time.monotonic())):
                raise BenchFailure(f"{who}: no answer to {name!r} in "
                                   f"{timeout:.0f}s")
            try:
                status, answer = self.pipes[i].recv()
            except EOFError:
                raise BenchFailure(f"{who} died in {name!r}") from None
            if status != "ok":
                raise BenchFailure(f"{who} failed in {name!r}: {answer}")
            out.append(answer)
        return out

    def call(self, kind: str, name: str, *args, timeout: float = 300.0) -> list:
        """One phase in every worker of ``kind`` at once; their answers."""
        return self.collect(self.start((kind,), name, *args), name, timeout)

    def close(self) -> None:
        for pipe in self.pipes:
            try:
                pipe.send(("quit",))
            except (OSError, ValueError):
                pass
        for proc in self.procs:
            proc.join(10)
            if proc.is_alive():
                proc.kill()
                proc.join()


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             started: float, need_platform: str = "tpu", control: str = "",
             fault: str = "") -> dict:
    """Drive one run; returns the result the last line carries."""
    flow = load_flow(cell)
    config, mix = cell["config"], cell["mix"]
    out_dir = os.path.join(cell["base"], "out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)  # the last run's logs
    os.makedirs(out_dir)
    build_native()
    warmup, drain = float(mix["warmup_s"]), float(mix["drain_s"])
    prepared = flow.prepare(cell, seed, seconds)

    say(f"{cell['name']}: booting the gateway")
    gw = Gateway(config["gateway_argv"], out_dir, control, fault,
                 settings=config.get("settings"))
    workers = None
    peers = wire.Peers()  # the master's
    try:
        workers = Workers([  # they import and plan while it boots
            (kind, dict(spec, repo=REPO, flow=flow.__file__,
                        sport=gw.sport, cport=gw.cport))
            for kind, spec in prepared["specs"]])
        gw.wait_listening(1100.0)
        engine = gw.introspect().get("engine") or {}
        say(f"listening after {time.monotonic() - gw.started:.1f}s on "
            f"{engine.get('platform')} {engine.get('device_kind')!r}")
        if need_platform and engine.get("platform") != need_platform:
            raise BenchFailure(
                f"the gateway holds platform {engine.get('platform')!r}, "
                f"not {need_platform!r}: nothing is measured")
        if need_platform and engine.get("device_count") != cell["chips"]:
            raise BenchFailure(
                f"the gateway holds {engine.get('device_count')} devices, the "
                f"cell asks for {cell['chips']}")

        master = wire.connect(gw.sport, "bench-master", peers)
        master.queue(0, wire.CREATE_CHANNEL,
                     wire.control_pb2.CreateChannelMessage(
                         channelType=wire.GLOBAL).SerializeToString())
        peers.pump(0.3)
        world = flow.build(cell, prepared, gw, master, peers, workers)
        ladder = _settle(gw, peers)
        say(f"ladder before the window: {ladder}")

        # ---- warm-up, window, drain ----
        t0 = time.monotonic() + 0.5
        w0, w1 = t0 + warmup, t0 + warmup + seconds
        end = w1 + drain
        running = workers.start(set(workers.kinds), "go", t0, w1, end)
        setup_s = w0 - started
        say(f"set-up took {setup_s:.1f}s; warm-up {warmup:g}s, window "
            f"{seconds:g}s, drain {drain:g}s")
        _sleep_until(w0, peers)
        m0, c0, at0 = gw.metrics(), cache_entries(), time.monotonic()
        traced_s = None
        trace_dir = os.path.join(out_dir, "trace")
        if trace:
            _sleep_until(w0 + 1.0, peers)
            answer = gw.ask(f"trace_start {trace_dir}")
            if not answer.get("ok"):
                raise BenchFailure(f"trace_start: {answer}")
            _sleep_until(time.monotonic() + TRACE_S, peers)
            traced_s = gw.ask("trace_stop").get("traced_s")
        # No scrape inside the window: rendering /metrics holds the
        # gateway's interpreter for milliseconds.
        _sleep_until(w1, peers)
        m1, c1, at1 = gw.metrics(), cache_entries(), time.monotonic()
        levels = [int(total(m, "overload_level")) for m in (m0, m1)]
        pressures = [round(total(m, "overload_pressure"), 3) for m in (m0, m1)]

        answers: dict = {}  # each kind's answers, in the workers' order
        for i, answer in zip(running, workers.collect(
                running, "go", end + 60.0 - time.monotonic())):
            answers.setdefault(workers.kinds[i], []).append(answer)
        flow.after_window(world, workers)
        memory = gw.ask("memory")
        state = gw.introspect().get("device")
        m2 = gw.metrics()
        workers.close()
        workers = None
        peers.close()
        say("draining the gateway")
        gw.drain()
    finally:
        if workers is not None:
            workers.close()
        peers.close()
        gw.close()
        for path in sorted(glob.glob(os.path.join(out_dir, "profiles", "*")),
                           key=os.path.getmtime)[:-4]:
            os.remove(path)

    return assemble(cell, flow, dict(
        world, answers=answers, t0=t0, w0=w0, w1=w1, end=end,
        seconds=seconds, setup_s=setup_s, window=delta(m1, m0),
        wall_s=at1 - at0, at_end=m2, compiles=c1 - c0, levels=levels,
        pressures=pressures, ladder=ladder, engine=engine, memory=memory,
        device_state=state, trace_dir=trace_dir if trace else None,
        traced_s=traced_s, chip_needed=bool(need_platform)))


def assemble(cell: dict, flow, run: dict) -> dict:
    """The result, from what the flow accounts for and what the gateway
    recorded. The flow's ``account(cell, run)`` returns the due and read
    times (NaN: never read) of the deliveries due in the window, the
    count ``reflected_in_window`` (read inside it, whenever due),
    ``attempted``, ``failed``, its ``checks``, its ``info`` and what it
    adds to the readers' ``ctx``."""
    out = flow.account(cell, run)
    # device_faults first: the ledger keeps the head of a refused run's
    # checks.
    checks = {"device_faults": int(
        total(run["at_end"], "device_recoveries_total")
        + total(run["at_end"], "device_step_failures_total")
        + (run["device_state"] != "ACTIVE")), **out["checks"]}

    engine, window = run["engine"], run["window"]
    lat = stats.latency_ms(out["due"], out["read"], run["end"])
    e2e = {
        "delivery_p50_ms": stats.percentile(lat, 50),
        "delivery_p95_ms": stats.percentile(lat, 95),
        "deliveries_per_s": out["reflected_in_window"] / run["seconds"],
        "setup_s": run["setup_s"],
    }
    device = {"platform": engine.get("platform"),
              "kind": engine.get("device_kind"),
              "count": engine.get("device_count"),
              "memory_peak_bytes": run["memory"].get("peak_bytes_in_use", 0)}
    result = {"correct": not any(checks.values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    info = dict(out["info"],
                ladder=run["ladder"],
                ladder_at_window_ends=run["levels"],
                pressure_at_window_ends=run["pressures"],
                sheds_in_window=total(window, "overload_sheds_total"),
                global_ticks_in_window=total(
                    window, "tick_stage_ms_count", stage="device_step"),
                use_pallas=engine.get("use_pallas"),
                native_codec=engine.get("native_codec"))
    if run["trace_dir"] is None:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if e2e.get(m["name"]) is not None}
    else:
        reduced = reduce_trace(run["trace_dir"])
        info["trace_planes"] = reduced.pop("planes", None)
        kind = engine.get("device_kind")
        if run["chip_needed"] and kind not in cell["peaks"]:
            raise BenchFailure(f"no peaks for device kind {kind!r} in "
                               "peaks.json")
        if reduced.get("device_planes"):
            # The window is what the profiler was on for, by the child's
            # clock: the first and the last event span less where ticks
            # are rare.
            reduced["window_s"] = max(run["traced_s"] or 0.0,
                                      reduced["window_s"])
        ctx = dict(out["ctx"], base=cell["base"], metrics=window,
                   wall_s=run["wall_s"], compiles=run["compiles"],
                   trace=reduced if reduced.get("device_planes") else None,
                   peak=cell["peaks"].get(kind))
        result["metrics"] = {}
        for m in cell["per_layer"]:
            reader = load_file(os.path.join(
                cell["base"], "layer_metrics", m["name"] + ".py"),
                "layer_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:  # nothing to read: left out, never 0
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if ctx["trace"]:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        info["end_to_end_traced"] = e2e
    result["device"] = device
    result["info"] = info
    # Each number compared, beside its limit: the last key of the line.
    result["checks"] = {name: {"value": int(value), "limit": 0}
                        for name, value in checks.items()}
    return result


def reduce_trace(trace_dir: str) -> dict:
    """The trace's numbers, from a process of its own that may import
    jax: the gateway has gone, and the reader is held to the CPU."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise BenchFailure(f"the gateway wrote no trace under {trace_dir}")
    done = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "trace.py"),
         found[0]], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if done.returncode != 0:
        raise BenchFailure(f"reading the trace failed:\n{done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def _sleep_until(when: float, peers) -> None:
    while True:
        left = when - time.monotonic()
        if left <= 0:
            return
        peers.pump(min(left, 0.5))


def wait_metric(gw, peers, what: str, pred, timeout: float) -> dict:
    """Poll ``/metrics`` (paced: a tight scrape loop is load)."""
    end = time.monotonic() + timeout
    while True:
        samples = gw.metrics()
        if pred(samples):
            return samples
        if time.monotonic() >= end:
            raise BenchFailure(f"timed out after {timeout:.0f}s waiting for "
                               f"{what}")
        peers.pump(1.0)


def _settle(gw, peers, timeout: float = 60.0) -> dict:
    """Wait for the overload ladder to rest at L0 (three paced scrapes in
    a row). A ladder that will not come down is recorded, and the run goes
    on: it will count its failures."""
    t0, calm, level = time.monotonic(), 0, -1
    while calm < 3 and time.monotonic() - t0 < timeout:
        peers.pump(1.0)
        now = gw.metrics()
        level = int(total(now, "overload_level"))
        calm = calm + 1 if level == 0 else 0
    return {"rested": calm >= 3, "level": level,
            "waited_s": round(time.monotonic() - t0, 1)}
