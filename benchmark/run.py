"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, warms, measures, prints one JSON object as the last
line of its standard output and exits. This process never starts a jax
backend: the `server` child holds the chip. `--size tiny` rehearses a cell on
the CPU and can never end `correct`.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import cellrun, cluster  # noqa: E402


def print_checks(result: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    lines = []
    for name, c in result["checks"].items():
        if c["limit"] is None:
            lines.append(f"  {name} = {c['value']}")
        else:
            rel = ">=" if name.endswith("_min") else "<="
            lines.append(f"  {name} = {c['value']} (must be {rel} {c['limit']})")
    print(f"[bench] correct={result['correct']}; compared:\n" + "\n".join(lines),
          file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--size", choices=("real", "tiny"), default="real",
                   help="tiny: only to rehearse on the CPU")
    opts = p.parse_args(argv)
    if not os.path.exists(os.path.join(cluster.ROOT, "seaweedfs_tpu", "command", "main.py")):
        print("[bench] this is not a checkout of the repo: no seaweedfs_tpu",
              file=sys.stderr)
        return 2
    # this process only ever reads trace files with jax; the children must
    # not inherit a platform it chose for itself
    own = "JAX_PLATFORMS" not in os.environ
    if own:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        run = cellrun.Run(cellrun.load_spec(), opts.workload, opts.seed,
                          opts.seconds, bool(opts.trace), opts.size, T_START)
        result = run.execute(own_jax_platforms=own)
    except cellrun.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    except cluster.RunError as e:
        print(f"[bench] the run could not go on: {e}", file=sys.stderr)
        return 4
    sys.stdout.flush()
    print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
