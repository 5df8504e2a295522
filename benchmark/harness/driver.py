"""One run of one cell: boot the gateway, build the world over the wire,
warm up, measure for the window, drain, hold every answer against the
reference, and make the result the contract's last line carries.

Everything that belongs to one configuration, one mix, one generator,
one kernel or one per-layer metric is data or a file of its own, found
from ``BENCHMARK.json`` by name (``load_cell``). This module is what is
left: the flow that all of them share.
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

import numpy as np

from . import stats, wire
from .gateway import REPO, BenchFailure, Gateway, delta, total
from .reference import Grid
from .workers import CELL_PATH, plan, run_worker

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


def _split(items: list, parts: int) -> list:
    return [items[i::parts] for i in range(parts)]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             started: float, need_platform: str = "tpu", control: str = "",
             fault: str = "") -> dict:
    """Drive one run; returns the result the last line carries."""
    config, mix = cell["config"], cell["mix"]
    pop = config["populations"]
    out_dir = os.path.join(cell["base"], "out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)  # the last run's logs
    os.makedirs(out_dir)
    build_native()
    scc = os.path.join(REPO, config["world"]["scc"])
    grid = Grid.load(scc, config["world"].get("cell_start", 0x10000),
                     config["world"].get("entity_start", 0x80000))
    warmup, drain = float(mix["warmup_s"]), float(mix["drain_s"])
    servers = [f"bench-spatial-{i}" for i in range(grid.num_servers)]
    spec = {
        "repo": REPO, "scc": scc, "cell_start": grid.cell_start,
        "entity_start": grid.entity_start, "mix": mix, "seed": seed,
        "entities": pop["wire_entities"], "clients_total": pop["clients"],
        "radius": pop["client_radius"], "all_servers": servers,
        "seconds_total": warmup + seconds + drain + 1.0,
        "window": (warmup, warmup + seconds),
    }
    the_plan = plan(spec)
    centres = the_plan["centres"]
    say(f"layout {the_plan['layouts_drawn']} of seed {seed} offers "
        f"{the_plan['deliveries_per_update']:.4f} deliveries an update")

    say(f"{cell['name']}: booting the gateway")
    gw = Gateway(config["gateway_argv"], out_dir, control, fault,
                 settings=config.get("settings"))
    workers = master = None
    try:
        n_send, n_recv = config["workers"]["senders"], config["workers"]["receivers"]
        specs = [("sender", dict(spec, sport=gw.sport, servers=part))
                 for part in _split(servers, n_send)]
        specs += [("receiver", dict(spec, cport=gw.cport, clients=part))
                  for part in _split(list(range(pop["clients"])), n_recv)]
        workers = Workers(specs)  # they import and plan while it boots
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

        master = wire.connect(gw.sport, "bench-master")
        master.queue(0, wire.CREATE_CHANNEL,
                     wire.control_pb2.CreateChannelMessage(
                         channelType=wire.GLOBAL).SerializeToString())
        wire.pump([master], 0.3)
        claimed = {}
        for answer in workers.call("sender", "claim"):
            claimed.update(answer)
        blocks = {}
        for c in range(grid.num_cells):
            blocks.setdefault(grid.server_of_cell(c), set()).add(c)
        want_blocks = sorted(map(sorted, blocks.values()))
        checks = {"server_blocks_mismatched": sum(
            a != b for a, b in zip(sorted(claimed.values()), want_blocks))
            + abs(len(claimed) - len(want_blocks))}
        owner_of = {c: servers.index(name)
                    for name, cells in claimed.items() for c in cells}
        workers.call("sender", "ready", owner_of)
        agents = pop.get("sim_agents", 0)
        if agents:
            say(f"waiting for {agents} agents")
            _wait_metric(gw, [master], "sim_agents_num",
                         lambda s: total(s, "sim_agents_num") >= agents, 600.0)
        say(f"creating {pop['wire_entities']} wire entities, connecting "
            f"{pop['clients']} clients")
        workers.call("sender", "spawn")
        conn_ids = workers.call("receiver", "connect", timeout=600.0)
        parts = _split(list(range(pop["clients"])), n_recv)
        for part, ids in zip(parts, conn_ids):
            for i, conn_id in zip(part, ids):
                cx, cz, _ = centres[i]
                master.queue(grid.cell_start, wire.INTEREST,
                             wire.sphere_interest(conn_id, cx, cz,
                                                  pop["client_radius"]))
            wire.pump([master], 0.05)
        want_cells = [sorted(cells) for _, _, cells in centres]

        def interest_mismatched() -> int:
            got = workers.call("receiver", "subs")
            return sum(got[w][j] != want_cells[i]
                       for w, part in enumerate(parts)
                       for j, i in enumerate(part))

        resident = pop["wire_entities"] + agents
        _wait_metric(gw, [master], "tpu_entities",
                     lambda s: total(s, "tpu_entities") >= resident, 120.0)
        end = time.monotonic() + 90.0
        while interest_mismatched() and time.monotonic() < end:
            wire.pump([master], 1.0)
        ladder = _settle(gw, [master])
        say(f"ladder before the window: {ladder}")

        # ---- warm-up, window, drain ----
        t0 = time.monotonic() + 0.5
        w0, w1 = t0 + warmup, t0 + warmup + seconds
        end = w1 + drain
        running = workers.start(("sender", "receiver"), "go", t0, w1, end)
        setup_s = w0 - started
        say(f"set-up took {setup_s:.1f}s; warm-up {warmup:g}s, window "
            f"{seconds:g}s, drain {drain:g}s")
        _sleep_until(w0, [master])
        m0, c0, at0 = gw.metrics(), cache_entries(), time.monotonic()
        traced_s = None
        trace_dir = os.path.join(out_dir, "trace")
        if trace:
            _sleep_until(w0 + 1.0, [master])
            answer = gw.ask(f"trace_start {trace_dir}")
            if not answer.get("ok"):
                raise BenchFailure(f"trace_start: {answer}")
            _sleep_until(time.monotonic() + TRACE_S, [master])
            traced_s = gw.ask("trace_stop").get("traced_s")
        # No scrape inside the window: rendering /metrics holds the
        # gateway's interpreter for milliseconds.
        _sleep_until(w1, [master])
        m1, c1, at1 = gw.metrics(), cache_entries(), time.monotonic()
        levels = [int(total(m, "overload_level")) for m in (m0, m1)]
        pressures = [round(total(m, "overload_pressure"), 3) for m in (m0, m1)]

        answers = workers.collect(running, "go",
                                  end + 60.0 - time.monotonic())
        sent = [a for i, a in zip(running, answers)
                if workers.kinds[i] == "sender"]
        received = [a for i, a in zip(running, answers)
                    if workers.kinds[i] == "receiver"]
        checks["interest_mismatched"] = interest_mismatched()
        memory = gw.ask("memory")
        state = gw.introspect().get("device")
        m2 = gw.metrics()
        workers.close()
        workers = None
        master.close()
        master = None
        say("draining the gateway")
        gw.drain()
    finally:
        if workers is not None:
            workers.close()
        if master is not None:
            master.close()
        gw.close()
        for path in sorted(glob.glob(os.path.join(out_dir, "profiles", "*")),
                           key=os.path.getmtime)[:-4]:
            os.remove(path)

    return account(cell, {
        "plan": the_plan, "grid": grid, "owner_of": owner_of, "checks": checks,
        "t0": t0, "w0": w0, "w1": w1, "end": end, "seconds": seconds,
        "setup_s": setup_s, "sent": sent, "received": received,
        "window": delta(m1, m0), "wall_s": at1 - at0, "at_end": m2,
        "compiles": c1 - c0, "levels": levels, "pressures": pressures,
        "ladder": ladder,
        "engine": engine, "memory": memory, "device_state": state,
        "trace_dir": trace_dir if trace else None, "traced_s": traced_s,
        "chip_needed": bool(need_platform)})


def account(cell: dict, run: dict) -> dict:
    """From what the peers and the gateway recorded to the result: every
    answer held against the reference, and the metrics."""
    plan_, t0, w0, w1 = run["plan"], run["t0"], run["w0"], run["w1"]
    horizon, owner_of, checks = run["end"], run["owner_of"], run["checks"]
    sent, received = run["sent"], run["received"]
    config, mix = cell["config"], cell["mix"]

    # ---- deliveries ----
    cols = {c: np.concatenate([r[c] for r in received])
            for c in ("client", "k", "n", "due", "read")}
    due, read = cols["due"], cols["read"]
    in_window = (due >= w0) & (due < w1)
    lat = stats.latency_ms(due[in_window], read[in_window], horizon)
    unreflected = int(np.isnan(read[in_window]).sum())
    reflected_in_window = int(((read >= w0) & (read < w1)).sum())
    cross_at = plan_["cells"] != plan_["prev"]
    unreflected_sample = [
        {"client": int(cols["client"][i]), "n": int(cols["n"][i]),
         "k": int(cols["k"][i]),
         "crossing": bool(cross_at[cols["k"][i], cols["n"][i]])}
        for i in np.nonzero(in_window & np.isnan(read))[0][:8]]
    lags = np.concatenate([r["lags"] for r in received])
    lags = lags[(lags[:, 1] >= w0) & (lags[:, 1] < w1)]
    half = (w0 + w1) / 2.0
    cell_rows_ms = lags[lags[:, 0] == CELL_PATH, 2] * 1000.0  # each row read
    lag_by_path = {}  # how stale each path's rows ran, and whether it grew
    for path, name in enumerate(("cell", "entity", "handover")):
        for part, rows in (("first_half", lags[:, 1] < half),
                           ("second_half", lags[:, 1] >= half)):
            v = lags[(lags[:, 0] == path) & rows, 2] * 1000.0
            if len(v):
                lag_by_path[f"{name}.{part}"] = {
                    "rows": len(v), "p50_ms": round(stats.percentile(v, 50), 1),
                    "p95_ms": round(stats.percentile(v, 95), 1)}

    # ---- handovers ----
    k, n, src, dst = stats.crossings(plan_["start_cells"], plan_["cells"])
    cross_due = t0 + plan_["due"][k, n]
    log = np.concatenate([s["handovers"] for s in sent])
    seen: dict = {}
    reads_of: dict = {}
    for t, server, en, s, d in log[np.argsort(log[:, 0], kind="stable")]:
        key = (int(server), int(en), int(s), int(d))
        seen[key] = seen.get(key, 0) + 1
        reads_of.setdefault(key, []).append(t)
    sends = np.concatenate([s["sent"] for s in sent])
    sent_at = {(int(a), int(b)): t for a, b, t in sends[:, :3]}
    times: dict = {}  # crossings that must be complete: due before the close
    owners: dict = {}
    ho_ms, ho_failed, ho_early, nth = [], 0, 0, {}
    for kk, nn, s, d, due_t in zip(k.tolist(), n.tolist(), src.tolist(),
                                   dst.tolist(), cross_due.tolist()):
        pair = (nn, s, d)
        nth[pair] = nth.get(pair, 0) + 1
        if due_t >= w1:
            continue  # the drain's own crossings need not complete
        times[pair] = nth[pair]
        owners[pair] = {owner_of[s], owner_of[d]}
        reads = reads_of.get((owner_of[d], nn, s, d), ())
        done = reads[nth[pair] - 1] if len(reads) >= nth[pair] else None
        if done is not None and done < sent_at.get((kk, nn), 0.0):
            ho_early += 1  # read before the update that crossed was sent
        if due_t >= w0:
            ho_ms.append(((horizon if done is None else done) - due_t)
                         * 1000.0)
            ho_failed += done is None
    checks.update(stats.handover_account(times, owners, seen))
    # A crossing of the drain may be read or not; it is never unpredicted,
    # and is duplicated only when read more often than it happens at all.
    checks["handovers_unpredicted"] = sum(
        got for key, got in seen.items() if key[1:] not in nth)
    checks["handovers_duplicated"] = sum(
        max(0, got - nth[key[1:]]) for key, got in seen.items()
        if key[1:] in nth)
    checks["handovers_early"] = ho_early
    checks["deliveries_unreflected"] = unreflected
    checks["rows_wrong"] = sum(r["wrong_rows"] for r in received)
    checks["device_faults"] = int(
        total(run["at_end"], "device_recoveries_total")
        + total(run["at_end"], "device_step_failures_total")
        + (run["device_state"] != "ACTIVE"))

    # ---- the generator's own account ----
    send_due = t0 + plan_["due"][sends[:, 0].astype(int),
                                 sends[:, 1].astype(int)]
    sent_in_window = (send_due >= w0) & (send_due < w1)
    late = (sends[:, 2] - sends[:, 3] > mix["frame_ms"] / 1000.0)
    generator = {
        "updates_in_window": int(sent_in_window.sum()),
        "late": int((late & sent_in_window).sum()),
        "unsent": sum(s["unsent"] for s in sent),
        "rows_read": sum(r["rows"] for r in received),
        "entity_subs_at_end": sum(r["entity_subs"] for r in received),
    }
    checks["updates_unsent"] = generator["unsent"]

    # ---- the result ----
    engine, window = run["engine"], run["window"]
    e2e = {
        "delivery_p50_ms": stats.percentile(lat, 50),
        "delivery_p95_ms": stats.percentile(lat, 95),
        "deliveries_per_s": reflected_in_window / run["seconds"],
        "setup_s": run["setup_s"],
    }
    device = {"platform": engine.get("platform"),
              "kind": engine.get("device_kind"),
              "count": engine.get("device_count"),
              "memory_peak_bytes": run["memory"].get("peak_bytes_in_use", 0)}
    result = {"correct": not any(checks.values()),
              "attempted": int(in_window.sum()) + len(ho_ms),
              "failed": unreflected + ho_failed}
    info = {"crossings_in_window": len(ho_ms),
            "handover_p95_ms": stats.percentile(ho_ms, 95) if ho_ms else None,
            "deliveries_in_window": int(in_window.sum()),
            "layouts_drawn": plan_["layouts_drawn"],
            "offered_per_update": plan_["deliveries_per_update"],
            "ladder": run["ladder"],
            "ladder_at_window_ends": run["levels"],
            "pressure_at_window_ends": run["pressures"],
            "generator": generator,
            "unreflected_sample": unreflected_sample,
            "new_row_lag_by_path": lag_by_path,
            "sheds_in_window": total(window, "overload_sheds_total"),
            "global_ticks_in_window": total(
                window, "tick_stage_ms_count", stage="device_step"),
            "use_pallas": engine.get("use_pallas"),
            "native_codec": engine.get("native_codec")}
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
        dev, pop = config["device"], config["populations"]
        ctx = {
            "base": cell["base"], "metrics": window, "wall_s": run["wall_s"],
            "trace": reduced if reduced.get("device_planes") else None,
            "peak": cell["peaks"].get(kind), "generator": generator,
            "compiles": run["compiles"], "cell_rows_ms": cell_rows_ms,
            "handover_ms": np.array(ho_ms),
            # What a kernel needs is reckoned over the rows that are live,
            # not over the arrays' width: the entities on the device, the
            # clients' queries, and the spatial subscriptions (each
            # client's cells, and one owner a cell). Padding is no work.
            "shapes": {"entities": pop["wire_entities"]
                       + pop.get("sim_agents", 0),
                       "queries": pop["clients"],
                       "subs": sum(len(c) for _, _, c in plan_["centres"])
                       + run["grid"].num_cells,
                       "max_handovers": dev["max_handovers"],
                       "query_rows_max": dev["query_rows_max"],
                       "cells": run["grid"].num_cells},
        }
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


def _sleep_until(when: float, peers: list) -> None:
    while True:
        left = when - time.monotonic()
        if left <= 0:
            return
        wire.pump(peers, min(left, 0.5))


def _wait_metric(gw, peers, what: str, pred, timeout: float) -> dict:
    """Poll ``/metrics`` (paced: a tight scrape loop is load)."""
    end = time.monotonic() + timeout
    while True:
        samples = gw.metrics()
        if pred(samples):
            return samples
        if time.monotonic() >= end:
            raise BenchFailure(f"timed out after {timeout:.0f}s waiting for "
                               f"{what}")
        wire.pump(peers, 1.0)


def _settle(gw, peers, timeout: float = 60.0) -> dict:
    """Wait for the overload ladder to rest at L0 (three paced scrapes in
    a row). A ladder that will not come down is recorded, and the run goes
    on: it will count its failures."""
    t0, calm, level = time.monotonic(), 0, -1
    while calm < 3 and time.monotonic() - t0 < timeout:
        wire.pump(peers, 1.0)
        now = gw.metrics()
        level = int(total(now, "overload_level"))
        calm = calm + 1 if level == 0 else 0
    return {"rested": calm >= 3, "level": level,
            "waited_s": round(time.monotonic() - t0, 1)}
