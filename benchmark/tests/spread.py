"""Runs of one cell through the benchmark's own command, and the spread
the bounds are set from.

    chiprun -- python3 benchmark/tests/spread.py <tag> <cell> <seconds> <trace> <seed> [<seed> ...]
    python3 benchmark/tests/spread.py --table chiprun_out/<tag>/<cell>.t0.jsonl [...]

Each run is ``python3 benchmark/run.py`` as the driver calls it; its last
line goes to ``chiprun_out/<tag>/<cell>.t<trace>.jsonl`` with its seed. A
spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else float("nan")


def table(paths: list) -> None:
    for path in paths:
        with open(path) as f:
            runs = [json.loads(line) for line in f if line.strip()]
        good = [r for r in runs if r.get("metrics")]
        print(f"{path}: {len(runs)} runs, correct in "
              f"{sum(bool(r.get('correct')) for r in runs)}, seeds "
              f"{[r.get('seed') for r in runs]}")
        for name in (good[0]["metrics"] if good else []):
            values = [r["metrics"][name]["value"] for r in good
                      if name in r["metrics"]]
            line = (f"  {name:28s} n={len(values)} median "
                    f"{statistics.median(values):.4f}")
            # The first run of a call compiles: leave it out of set-up's spread.
            if name == "setup_s":
                values = values[1:]
            if len(values) >= 2:
                line += (f" spread {100 * spread(values):.2f}% min "
                         f"{min(values):.4f} max {max(values):.4f}")
            print(line)


def main() -> int:
    if sys.argv[1] == "--table":
        table(sys.argv[2:])
        return 0
    tag, cell, seconds, trace, *seeds = sys.argv[1:]
    out = os.path.join(REPO, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"{cell}.t{trace}.jsonl")
    for seed in seeds:
        done = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--workload", cell, "--seed", seed, "--seconds", seconds,
             "--trace", trace], cwd=REPO, capture_output=True, text=True)
        lines = done.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1])
        except (IndexError, ValueError):
            result = {"rc": done.returncode, "stderr": done.stderr[-3000:]}
        result["seed"] = int(seed)
        try:  # the run's extra account, for the builder
            with open(os.path.join(REPO, "benchmark", "out", cell,
                                   "result.json")) as f:
                result["info"] = json.load(f).get("info")
        except OSError:
            pass
        with open(path, "a") as f:
            f.write(json.dumps(result) + "\n")
        print(f"seed {seed}: rc {done.returncode} correct "
              f"{result.get('correct')}", flush=True)
        if not result.get("correct"):
            print(done.stderr[-1500:], flush=True)
    table([path])
    return 0


if __name__ == "__main__":
    sys.exit(main())
