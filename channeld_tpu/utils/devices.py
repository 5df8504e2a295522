"""Device helpers shared by every process entry point.

The platform is chosen by ``JAX_PLATFORMS`` and by nothing in code: unset,
JAX takes the accelerator; ``JAX_PLATFORMS=cpu`` (tests, soaks) takes the
CPU. What this module adds is where compiled programs are kept and a
description of what the process ended up holding.
"""

from __future__ import annotations

import os
import sys

# <checkout>/.jax_cache: fixed relative to the package, so every process
# of one checkout (gateway, benches, a restarted gateway) shares it no
# matter its working directory.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """Where this checkout's processes keep compiled programs."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def place_compile_cache() -> None:
    """Turn on JAX's persistent compile cache; call first thing in a
    process that may compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here. The minimum compile time drops to 0 so the
    sub-second scatter programs of the engine's host->device flush are
    kept too: a restarted gateway then compiles nothing it has run before.

    Before ``jax`` is imported the settings go through the environment,
    which JAX reads at import and children inherit: a gateway that never
    builds a device engine never pays the import. A process that has
    imported it already is told through ``jax.config``.
    """
    settings = {"jax_persistent_cache_min_compile_time_secs": 0}
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        settings["jax_compilation_cache_dir"] = _DEFAULT_CACHE_DIR
    jax = sys.modules.get("jax")
    for name, value in settings.items():
        if jax is None:
            os.environ[name.upper()] = str(value)
        else:
            jax.config.update(name, value)


def describe_devices(mesh=None) -> dict:
    """What this process computes on, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }
