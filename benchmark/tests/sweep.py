"""Find a cell's knee on the chip: the cell's own files with a few sizes
changed, one short run each, the results under ``chiprun_out/``.

    chiprun -- python3 benchmark/tests/sweep.py <tag> <cell> <seconds> '<variants>'

``variants`` is a JSON list of ``{"name": .., "mix": {..},
"populations": {..}, "workers": {..}, "settings": {..}, "chs": {"5": {..}},
"argv_drop": [..], "trace": 0|1, "control": ""}``: each key overrides
that part of the cell's configuration or mix for one run. A sweep is a
builder's tool; what it finds goes into the cell's files by hand and
into PERF.md.
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


def variant_root(where: str, cell_name: str, v: dict) -> str:
    shutil.rmtree(where, ignore_errors=True)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(where, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), where)
    with open(os.path.join(where, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_path = os.path.join(where, entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    for key in ("populations", "workers", "device"):
        config[key].update(v.get(key, {}))
    config.setdefault("settings", {}).update(v.get("settings", {}))
    argv = config["gateway_argv"]
    for flag in v.get("argv_drop", []):  # a flag and its value
        i = argv.index(flag)
        del argv[i:i + 2]
    argv += v.get("argv_add", [])
    if "chs" in v:
        i = argv.index("-chs") + 1
        with open(os.path.join(REPO, argv[i])) as f:
            chs = json.load(f)
        for kind, settings in v["chs"].items():
            chs[kind] = dict(chs.get(kind, chs["1"]), **settings)
        path = os.path.join(where, "chs.json")
        with open(path, "w") as f:
            json.dump(chs, f)
        argv[i] = os.path.relpath(path, REPO)
    with open(config_path, "w") as f:
        json.dump(config, f)
    mix_path = os.path.join(where, "benchmark", "traffic",
                            cell["traffic"] + ".json")
    with open(mix_path) as f:
        mix = json.load(f)
    mix.update(v.get("mix", {}))
    with open(mix_path, "w") as f:
        json.dump(mix, f)
    return where


def main() -> int:
    tag, cell_name, seconds, variants = sys.argv[1:5]
    from benchmark.harness import driver
    from benchmark.harness.gateway import BenchFailure

    out = os.path.join(REPO, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    for i, v in enumerate(json.loads(variants)):
        root = variant_root(os.path.join(REPO, "benchmark", "out", "sweep",
                                         v["name"]), cell_name, v)
        started = time.monotonic()
        try:
            cell = driver.load_cell(root, cell_name)
            result = driver.run_cell(cell, v.get("seed", 5000 + i),
                                     float(v.get("seconds", seconds)),
                                     bool(v.get("trace", 0)), started,
                                     control=v.get("control", ""))
        except BenchFailure as e:
            result = {"failure": str(e)[-3000:]}
        result["variant"] = v
        with open(os.path.join(out, v["name"] + ".json"), "w") as f:
            json.dump(result, f, indent=1)
        log = os.path.join(root, "benchmark", "out", cell_name, "gateway.log")
        if os.path.exists(log):
            with open(log, errors="replace") as f, \
                    open(os.path.join(out, v["name"] + ".gwlog"), "w") as g:
                g.write(f.read()[-30000:])
        brief = {k: result.get(k) for k in ("correct", "attempted", "failed",
                                            "failure")}
        brief["metrics"] = {k: round(m["value"], 3) for k, m in
                            result.get("metrics", {}).items()}
        print(v["name"], json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
