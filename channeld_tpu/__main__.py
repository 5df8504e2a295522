"""Run the channeld-tpu gateway: ``python -m channeld_tpu [flags]``.

Flag surface matches the reference (ref: cmd/main.go, settings.go:144-235).
"""

import asyncio
import sys


def main() -> None:
    from .utils.devices import place_compile_cache

    place_compile_cache()
    from .core.server import run_server

    try:
        asyncio.run(run_server(sys.argv[1:]))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
