"""The gateway child: the program's normal entry point, plus a side
thread that does for the benchmark what only the process holding the
chip can do.

    python benchmark/harness/gateway_child.py <control fd> <reply fd> \
        [--settings JSON] [--control bf16] [--fault NAME] -- <gateway argv...>

``channeld_tpu.__main__.main()`` runs on the main thread with the
configuration's argv, untouched. ``--settings`` carries the
configuration's ``settings``: fields of the program's own
``global_settings`` that it has no flag for, set before it starts, as a
deployment that embeds the gateway sets them. The side thread reads one command a
line from the control pipe and answers each with one line of JSON:

``trace_start <dir>``  start a ``jax.profiler`` trace into ``<dir>``
``trace_stop``         stop it; the answer, with the seconds that were traced,
                       comes when the file is written
``memory``             ``memory_stats()`` of the fullest device

``--control`` and ``--fault`` plant, from outside, what ``correct`` has
to catch (benchmark/tests, and the control runs on the chip). No cell
passes either; the program has no such switch.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _serve(control_fd: int, reply_fd: int) -> None:
    traced: dict = {}

    def trace_start(path: str) -> dict:
        import jax  # the gateway imported it long before any command

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device and XLA host lines only
        options.host_tracer_level = 1
        jax.profiler.start_trace(path, profiler_options=options)
        traced["from"] = time.monotonic()
        return {"ok": True}

    def trace_stop() -> dict:
        import jax

        seconds = time.monotonic() - traced["from"]
        jax.profiler.stop_trace()
        return {"ok": True, "traced_s": seconds}

    def memory() -> dict:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {"ok": True, "peak_bytes_in_use": max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats)}

    commands = {"trace_start": trace_start, "trace_stop": trace_stop,
                "memory": memory}
    with os.fdopen(control_fd, "r") as control, \
            os.fdopen(reply_fd, "w") as reply:
        for line in control:
            name, _, arg = line.strip().partition(" ")
            try:
                answer = commands[name](*([arg] if arg else []))
            except Exception as e:  # the run goes on; the parent decides
                answer = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            reply.write(json.dumps(answer) + "\n")
            reply.flush()


def _to_bf16(v: float) -> float:
    """``v`` rounded to bfloat16 (nearest even), as a Python float."""
    import numpy as np

    bits = int(np.float32(v).view(np.uint32))
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return float(np.uint32(bits).view(np.float32))


def plant(control: str, fault: str) -> None:
    """Patch the engine before the gateway builds one."""
    from channeld_tpu.ops import engine as eng

    cls = eng.SpatialEngine
    if control == "bf16":
        # The nearest precision below the configuration's float32: every
        # position and query shape reaches the device rounded to bfloat16.
        add, update, query = cls.add_entity, cls.update_entity, cls.set_query

        def add_entity(self, entity_id, x, y, z):
            return add(self, entity_id, _to_bf16(x), y, _to_bf16(z))

        def update_entity(self, entity_id, x, y, z):
            return update(self, entity_id, _to_bf16(x), y, _to_bf16(z))

        def set_query(self, conn_id, kind, center_xz, extent_xz=(0.0, 0.0),
                      *args, **kwargs):
            return query(self, conn_id, kind,
                         tuple(map(_to_bf16, center_xz)),
                         tuple(map(_to_bf16, extent_xz)), *args, **kwargs)

        cls.add_entity, cls.update_entity = add_entity, update_entity
        cls.set_query = set_query
    elif control:
        raise SystemExit(f"gateway_child: unknown control {control!r}")
    if fault == "state_unchanged":
        # A step that returns its state unchanged: no crossing is reported.
        cls.handover_list = lambda self, result: []
    elif fault == "half_batch":
        # Half of the batch left out: odd entity ids never reach the device.
        update = cls.update_entity

        def update_entity(self, entity_id, x, y, z):
            if entity_id % 2 == 0 or self.is_agent(entity_id):
                update(self, entity_id, x, y, z)

        cls.update_entity = update_entity
    elif fault == "answer_altered":
        # An answer altered where it is produced: every fourth crossing
        # names the cell before its true destination.
        rows = cls.handover_list

        def handover_list(self, result):
            out = rows(self, result)
            return [(e, s, d - 1 if i % 4 == 0 and d - 1 != s and d > 0
                     else d) for i, (e, s, d) in enumerate(out)]

        cls.handover_list = handover_list
    elif fault:
        raise SystemExit(f"gateway_child: unknown fault {fault!r}")


def main() -> None:
    args = sys.argv[1:]
    split = args.index("--")
    own, gateway_argv = args[:split], args[split + 1:]
    control_fd, reply_fd = int(own[0]), int(own[1])
    opts = dict(zip(own[2::2], own[3::2]))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo)
    sys.argv = ["channeld_tpu", *gateway_argv]
    if opts.get("--settings"):
        from channeld_tpu.core.settings import global_settings

        for field, value in json.loads(opts["--settings"]).items():
            if not hasattr(global_settings, field):
                raise SystemExit(f"gateway_child: the program has no setting "
                                 f"{field!r}")
            setattr(global_settings, field, value)
    if opts.get("--control") or opts.get("--fault"):
        plant(opts.get("--control", ""), opts.get("--fault", ""))
    threading.Thread(target=_serve, args=(control_fd, reply_fd),
                     name="benchmark-side", daemon=True).start()
    from channeld_tpu.__main__ import main as gateway_main

    gateway_main()


if __name__ == "__main__":
    main()
