"""The five BASELINE.json benchmark configs, end to end.

1. chat-rooms demo, GLOBAL channel only, 64 sim-clients (no spatial)
2. tanks world, spatial_static_2x2, 256 sim-clients
3. tps world, spatial_static_4x1, 2K sim-clients with cone interest
4. 50K synthetic moving entities @30Hz, radius AOI (device decision plane)
5. seamless open-world: 8 spatial blocks x 12.5K entities (100K total),
   dynamic handover across the grid (device decision plane)

Configs 1-3 drive a live gateway over real sockets (host plane under
client load); configs 4-5 measure the device decision plane the gateway
consumes (bench.py measures config 4's big sibling at 100K).

Run from the repo root:  python scripts/run_benchmarks.py [--quick]
Prints one JSON line per config.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_gateway(extra_args, log_path):
    proc = subprocess.Popen(
        [sys.executable, "-m", "channeld_tpu", "-dev",
         "-cfsm", "config/client_authoritative_fsm.json", "-cwm", "false",
         "-imports", "channeld_tpu.models.sim,channeld_tpu.models.chat",
         *extra_args],
        cwd=REPO, stdout=open(log_path, "w"), stderr=subprocess.STDOUT,
    )
    time.sleep(2.0)
    return proc


def run_sim_clients(n, behavior, duration, addr="127.0.0.1:12108"):
    out = subprocess.run(
        [sys.executable, "examples/sim_clients.py", "--addr", addr,
         "-n", str(n), "--behavior", behavior, "--duration", str(duration)],
        cwd=REPO, capture_output=True, text=True,
        # 2000 GIL-bound client threads need tens of seconds just to
        # connect and wind down; scale the guard with the fleet size.
        timeout=duration + 60 + n * 0.06,
    )
    line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    sent = received = 0
    for tok in line.replace(",", " ").split():
        if tok.startswith("(") and tok.endswith("/s)"):
            pass
    import re

    m = re.search(r"sent (\d+) updates \((\d+)/s\), received (\d+) fan-outs \((\d+)/s\)", line)
    if m:
        sent, sent_rate, received, recv_rate = map(int, m.groups())
        return {"sent": sent, "sent_per_sec": sent_rate,
                "received": received, "received_per_sec": recv_rate}
    return {"raw": line}


def config_1_chat(duration):
    proc = run_gateway([], "/tmp/bench_cfg1.log")
    try:
        stats = run_sim_clients(64, "chat", duration)
    finally:
        proc.terminate()
    return {"config": "1-chat-rooms-64-clients", **stats}


def config_2_tanks(duration):
    proc = run_gateway(["-scc", "config/spatial_static_2x2.json"], "/tmp/bench_cfg2.log")
    try:
        stats = run_sim_clients(256, "tanks", duration)
    finally:
        proc.terminate()
    return {"config": "2-tanks-2x2-256-clients", **stats}


def config_3_tps(duration, clients=2000):
    proc = run_gateway(["-scc", "config/spatial_static_4x1.json"], "/tmp/bench_cfg3.log")
    try:
        stats = run_sim_clients(clients, "tanks", duration)
    finally:
        proc.terminate()
    return {"config": f"3-tps-4x1-{clients}-clients", **stats}


def _device_decision_bench(n_entities, steps, handover_heavy=False):
    import numpy as np

    from bench import _require_tpu

    _require_tpu()
    import jax
    import jax.numpy as jnp

    from channeld_tpu.ops.spatial_ops import GridSpec, QuerySet, spatial_step

    grid = GridSpec(-15000.0, -15000.0, 2000.0, 2000.0, 15, 15)
    rng = np.random.default_rng(1)
    positions = jnp.asarray(
        rng.uniform(-14000, 14000, (n_entities, 3)).astype(np.float32)
    )
    speed = 3000.0 if handover_heavy else 600.0
    velocities = jnp.asarray(
        rng.normal(0, speed, (n_entities, 3)).astype(np.float32)
    )
    valid = jnp.ones(n_entities, bool)
    queries = QuerySet(
        jnp.ones(1024, jnp.int32),
        jnp.asarray(rng.uniform(-14000, 14000, (1024, 2)).astype(np.float32)),
        jnp.full((1024, 2), 3000.0, jnp.float32),
        jnp.tile(jnp.array([[1.0, 0.0]], jnp.float32), (1024, 1)),
        jnp.zeros(1024, jnp.float32),
    )
    subs = (
        jnp.zeros(n_entities, jnp.int32),
        jnp.full(n_entities, 50, jnp.int32),
        jnp.ones(n_entities, bool),
    )

    def step_fn(positions, velocities, prev, last, now):
        new_pos = jnp.clip(positions + velocities * 0.033, -14999.0, 14999.0)
        out = spatial_step(grid, new_pos, prev, valid, queries,
                           (last, subs[1], subs[2]), 8192, now)
        return new_pos, velocities, out

    compiled = jax.jit(step_fn, donate_argnums=(2,)).lower(
        positions, velocities, jnp.full(n_entities, -1, jnp.int32),
        subs[0], jnp.int32(0),
    ).compile()

    prev = jnp.full(n_entities, -1, jnp.int32)
    last = subs[0]
    for i in range(5):
        positions, velocities, out = compiled(positions, velocities, prev, last,
                                              jnp.int32(i * 33))
        prev, last = out["cell_of"], out["new_last_fanout_ms"]
    jax.block_until_ready(out["cell_of"])

    from collections import deque

    inflight = deque()
    handovers = 0
    t0 = time.perf_counter()
    for i in range(steps):
        positions, velocities, out = compiled(positions, velocities, prev, last,
                                              jnp.int32((i + 5) * 33))
        prev, last = out["cell_of"], out["new_last_fanout_ms"]
        out["consume"].copy_to_host_async()
        inflight.append(out)
        if len(inflight) > 32:
            import numpy as np2

            handovers += int(np2.asarray(inflight.popleft()["consume"])[0])
    while inflight:
        import numpy as np2

        handovers += int(np2.asarray(inflight.popleft()["consume"])[0])
    dt = time.perf_counter() - t0
    row = {
        "steps_per_sec": round(steps / dt, 1),
        "entity_updates_per_sec": round(steps / dt * n_entities),
        "handovers_per_step": round(handovers / steps, 1),
        "hz_target_met": steps / dt >= 30,
    }
    return row


def config_4_synthetic(steps):
    return {"config": "4-synthetic-50k-30hz",
            **_device_decision_bench(50_000, steps)}


def config_5_open_world(steps):
    return {"config": "5-open-world-100k-handover",
            **_device_decision_bench(100_000, steps, handover_heavy=True)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="short durations")
    p.add_argument("--configs", default="1,2,4,5",
                   help="comma-separated config numbers (3 = 2K clients, slow)")
    args = p.parse_args()
    duration = 5 if args.quick else 15
    steps = 100 if args.quick else 300

    runners = {
        "1": lambda: config_1_chat(duration),
        "2": lambda: config_2_tanks(duration),
        "3": lambda: config_3_tps(duration),
        "4": lambda: config_4_synthetic(steps),
        "5": lambda: config_5_open_world(steps),
    }
    for key in args.configs.split(","):
        result = runners[key.strip()]()
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
