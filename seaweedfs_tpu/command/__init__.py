"""CLI entrypoints (`weed-tpu ...`), mirroring the reference's command registry
(`weed/command/command.go`)."""

import time

# when this process entered the CLI: `python -m seaweedfs_tpu.command.main` and
# the console script both import this package immediately before `main`'s
# first line. The admin shell's first root span counts `startup_s` from here.
started = time.perf_counter()
