#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the gateway through its normal entry point on the accelerator,
drives the cell's deployment over TCP from worker processes, measures
for ``--seconds`` and prints, last on stdout, one JSON line: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``checks``, each number compared beside its
limit. Without an accelerator it measures nothing and exits non-zero.
This process never imports jax: one process per chip, and the gateway
child is that process. See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

STARTED = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--control", default="", help="run a control on the "
                    "chip: 'bf16' (never part of a cell's own runs)")
    args = ap.parse_args()
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and platforms.split(",")[0] != "tpu":
        print(f"benchmark: JAX_PLATFORMS={platforms} keeps JAX off the "
              "accelerator; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    try:
        import channeld_tpu.protocol.wire_pb2  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not here ({e}); nothing was run",
              file=sys.stderr)
        return 2
    from benchmark.harness import driver
    from benchmark.harness.gateway import BenchFailure

    try:
        cell = driver.load_cell(ROOT, args.workload)
        result = driver.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), STARTED,
                                 control=args.control)
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark: the parent imported jax", file=sys.stderr)
        return 1
    info = result.pop("info")
    with open(os.path.join(cell["base"], "out", cell["name"], "result.json"),
              "w") as f:
        json.dump(dict(result, info=info, seed=args.seed), f, indent=1)
    print(f"benchmark: {json.dumps(info)}", file=sys.stderr)
    for name, check in result["checks"].items():
        print(f"benchmark: {name} = {check['value']} (limit "
              f"{check['limit']})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
