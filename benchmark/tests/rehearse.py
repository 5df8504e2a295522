"""The whole flow at a tiny size on the CPU, as a function the tests and
a builder at a shell can call. A rehearsal's result names the CPU and can
never pass as a chip run: ``run.py`` is not involved, and it refuses
anything but ``tpu``.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py [world|tanks] [trace] [bf16|<fault>]
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

ARGV = ["-dev", "-scc", "benchmark/configs/files/spatial_tpu_4x4.json",
        # lofi on the CPU: one core cannot hold 20 ms ticks (verify skill)
        "-chs", "benchmark/configs/files/channel_settings_lofi.json",
        "-cfsm", "benchmark/configs/files/client_authoritative_fsm.json",
        "-imports", "channeld_tpu.models.sim", "-cwm", "false",
        "-loglevel", "0"]
DEVICE = {"precision": "float32", "entity_capacity": 131072,
          "query_capacity": 4096, "sub_capacity": 65536,
          "max_handovers": 4096, "query_rows_max": 8192}
TINY = {
    "tiny-world": {
        "gateway_argv": ARGV + ["-sim", "true", "-sim-agents", "48"],
        "populations": {"sim_agents": 48, "wire_entities": 24, "clients": 4,
                        "client_radius": 40.0},
    },
    "tiny-tanks": {
        "gateway_argv": ARGV,
        "populations": {"sim_agents": 0, "wire_entities": 16, "clients": 4,
                        "client_radius": 25.0},
    },
}
MIX = {"generator": "walk", "speed": 12.0, "rate": 2, "frame_ms": 50,
       "warmup_s": 2.0, "drain_s": 4.0,
       # a few clients and entities: layouts differ widely, so take most
       "offered": {"deliveries_per_update": 0.95, "within": 0.3}}


def make_root(where: str) -> str:
    """A benchmark root of its own under ``where``: the repo's
    ``BENCHMARK.json`` and ``benchmark/`` copied, and the tiny
    configurations, their mix, their cells and one more per-layer metric
    added as new files and new entries, with no file edited."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(where, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.join(where, "benchmark")
    with open(os.path.join(base, "traffic", "amble.json"), "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(base, "layer_metrics", "channel_ticks_hz.py"),
              "w") as f:
        f.write("from benchmark.harness.gateway import total\n\n\n"
                "def read(ctx):\n"
                "    return total(ctx['metrics'], 'tick_stage_ms_count', "
                "stage='messages') / ctx['wall_s']\n")
    cells = []
    for name, sizes in TINY.items():
        config = dict(sizes, source="a rehearsal", device=DEVICE,
                      world={"scc": ARGV[2]},
                      workers={"senders": 2, "receivers": 2})
        path = os.path.join("benchmark", "configs", name + ".json")
        with open(os.path.join(where, path), "w") as f:
            json.dump(config, f)
        bench["configs"].append({"name": name, "source": "a rehearsal",
                                 "file": path, "reduced": [], "why": "tiny"})
        cells.append(name + ".amble")
        bench["workloads"].append({"name": cells[-1], "config": name,
                                   "traffic": "amble", "chips": 1,
                                   "why": "tiny"})
    for metric in bench["per_layer"]:
        # tiny-tanks runs no sim: it reports all but the sim's kernel
        metric["workloads"] += [c for c in cells
                                if c != "tiny-tanks.amble"
                                or metric["name"] != "sim_step_roofline"]
    bench["per_layer"].append({
        "name": "channel_ticks_hz", "unit": "ticks/s", "better": "higher",
        "source": "program_counter", "layer": "channel tick and fan-out",
        "moves": "delivery_p95_ms", "workloads": cells})
    with open(os.path.join(where, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return where


def rehearse(where: str, workload: str, seed: int = 7, seconds: float = 6.0,
             trace: bool = False, control: str = "", fault: str = "") -> dict:
    from benchmark.harness import driver

    cell = driver.load_cell(make_root(where), workload)
    return driver.run_cell(cell, seed, seconds, trace, time.monotonic(),
                           need_platform="", control=control, fault=fault)


if __name__ == "__main__":
    import tempfile

    words = sys.argv[1:]
    which = "tanks" if "tanks" in words else "world"
    planted = [w for w in words if w not in ("world", "tanks", "trace")]
    with tempfile.TemporaryDirectory() as tmp:
        out = rehearse(
            tmp, f"tiny-{which}.amble", trace="trace" in words,
            control="bf16" if "bf16" in planted else "",
            fault=next((w for w in planted if w != "bf16"), ""))
        print(json.dumps(out, indent=1))
