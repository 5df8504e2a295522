"""The spatial world: a static grid of cells owned in blocks by spatial
servers, wire entities those servers walk on the mix's schedule, and
clients that each watch the cells one standing sphere covers. The
configuration's file names no ``flow`` for it: it is the default.

``prepare`` draws the layout from the seed; ``build`` claims the blocks,
creates the entities, connects the clients and sets their interest;
``account`` holds every delivery, handover and row against the plain
reference (``harness/reference.py``). The peers are
``harness/workers.py``'s ``Sender`` and ``Receiver``.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.harness import stats, wire
from benchmark.harness.driver import say, wait_metric
from benchmark.harness.gateway import REPO, total
from benchmark.harness.reference import Grid
from benchmark.harness.workers import CELL_PATH, Receiver, Sender, plan

WORKERS = {"sender": Sender, "receiver": Receiver}


def _split(items: list, parts: int) -> list:
    return [items[i::parts] for i in range(parts)]


def prepare(cell: dict, seed: int, seconds: float) -> dict:
    """The layout and schedule from the seed, and the workers' specs."""
    config, mix = cell["config"], cell["mix"]
    pop = config["populations"]
    warmup, drain = float(mix["warmup_s"]), float(mix["drain_s"])
    scc = os.path.join(REPO, config["world"]["scc"])
    grid = Grid.load(scc, config["world"].get("cell_start", 0x10000),
                     config["world"].get("entity_start", 0x80000))
    servers = [f"bench-spatial-{i}" for i in range(grid.num_servers)]
    spec = {
        "scc": scc, "cell_start": grid.cell_start,
        "entity_start": grid.entity_start, "mix": mix, "seed": seed,
        "entities": pop["wire_entities"], "clients_total": pop["clients"],
        "radius": pop["client_radius"], "all_servers": servers,
        "seconds_total": warmup + seconds + drain + 1.0,
        "window": (warmup, warmup + seconds),
    }
    the_plan = plan(spec)
    say(f"layout {the_plan['layouts_drawn']} of seed {seed} offers "
        f"{the_plan['deliveries_per_update']:.4f} deliveries an update")
    workers = config["workers"]
    specs = [("sender", dict(spec, servers=part))
             for part in _split(servers, workers["senders"])]
    specs += [("receiver", dict(spec, clients=part))
              for part in _split(list(range(pop["clients"])),
                                 workers["receivers"])]
    return {"plan": the_plan, "grid": grid, "servers": servers,
            "specs": specs}


def _interest_mismatched(the_plan: dict, workers) -> int:
    """Clients whose spatial subscriptions are not the cells their
    sphere covers."""
    want = [sorted(cells) for _, _, cells in the_plan["centres"]]
    got = workers.call("receiver", "subs")
    parts = _split(list(range(len(want))), len(got))
    return sum(got[w][j] != want[i]
               for w, part in enumerate(parts)
               for j, i in enumerate(part))


def build(cell: dict, planned: dict, gw, master, peers, workers) -> dict:
    """Claim the servers' blocks, create the wire entities, connect the
    clients and give each its sphere; wait until the device holds every
    entity and every client's subscriptions are its cells."""
    pop = cell["config"]["populations"]
    grid, servers, the_plan = (planned["grid"], planned["servers"],
                               planned["plan"])
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
        wait_metric(gw, peers, "sim_agents_num",
                    lambda s: total(s, "sim_agents_num") >= agents, 600.0)
    say(f"creating {pop['wire_entities']} wire entities, connecting "
        f"{pop['clients']} clients")
    workers.call("sender", "spawn")
    conn_ids = workers.call("receiver", "connect", timeout=600.0)
    parts = _split(list(range(pop["clients"])), len(conn_ids))
    for part, ids in zip(parts, conn_ids):
        for i, conn_id in zip(part, ids):
            cx, cz, _ = the_plan["centres"][i]
            master.queue(grid.cell_start, wire.INTEREST,
                         wire.sphere_interest(conn_id, cx, cz,
                                              pop["client_radius"]))
        peers.pump(0.05)
    resident = pop["wire_entities"] + agents
    wait_metric(gw, peers, "tpu_entities",
                lambda s: total(s, "tpu_entities") >= resident, 120.0)
    end = time.monotonic() + 90.0
    while (_interest_mismatched(the_plan, workers)
           and time.monotonic() < end):
        peers.pump(1.0)
    return {"plan": the_plan, "grid": grid, "owner_of": owner_of,
            "checks": checks}


def after_window(world: dict, workers) -> None:
    """Once the drain is over, while the clients are still connected."""
    world["checks"]["interest_mismatched"] = _interest_mismatched(
        world["plan"], workers)


def account(cell: dict, run: dict) -> dict:
    """Every answer held against the reference: each delivery, each
    handover and each row, and the generator's own account."""
    plan_, t0, w0, w1 = run["plan"], run["t0"], run["w0"], run["w1"]
    horizon, owner_of = run["end"], run["owner_of"]
    sent, received = run["answers"]["sender"], run["answers"]["receiver"]
    config, mix = cell["config"], cell["mix"]

    # ---- deliveries ----
    cols = {c: np.concatenate([r[c] for r in received])
            for c in ("client", "k", "n", "due", "read")}
    due, read = cols["due"], cols["read"]
    in_window = (due >= w0) & (due < w1)
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

    # updates_unsent first, after the driver's device_faults: the ledger
    # keeps the head of a refused run's checks.
    checks = {"updates_unsent": generator["unsent"], **run["checks"]}
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

    dev, pop = config["device"], config["populations"]
    grid = run["grid"]
    return {
        "due": due[in_window], "read": read[in_window],
        "reflected_in_window": reflected_in_window,
        "attempted": int(in_window.sum()) + len(ho_ms),
        "failed": unreflected + ho_failed,
        "checks": checks,
        "info": {"crossings_in_window": len(ho_ms),
                 "handover_p95_ms": (stats.percentile(ho_ms, 95)
                                     if ho_ms else None),
                 "deliveries_in_window": int(in_window.sum()),
                 "layouts_drawn": plan_["layouts_drawn"],
                 "offered_per_update": plan_["deliveries_per_update"],
                 "generator": generator,
                 "unreflected_sample": unreflected_sample,
                 "new_row_lag_by_path": lag_by_path},
        "ctx": {
            "generator": generator, "cell_rows_ms": cell_rows_ms,
            "handover_ms": np.array(ho_ms),
            # What a kernel needs is reckoned over the rows that are live,
            # not over the arrays' width: the entities on the device, the
            # clients' queries, and the spatial subscriptions (each
            # client's cells, and one owner a cell). Padding is no work.
            "shapes": {"entities": pop["wire_entities"]
                       + pop.get("sim_agents", 0),
                       "queries": pop["clients"],
                       "subs": sum(len(c) for _, _, c in plan_["centres"])
                       + grid.num_cells,
                       "max_handovers": dev["max_handovers"],
                       "query_rows_max": dev["query_rows_max"],
                       "cells": grid.num_cells},
        },
    }
