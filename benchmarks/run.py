"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each run, and the one process that holds the chip: origin,
scheduler and seed are children that stay off JAX. All data comes from
``--seed``. It fails, and prints no result, on a host without a TPU or with
fewer chips than the cell asks for. The last line of standard output is the
result object and nothing else; everything else goes to standard error.
"""

from __future__ import annotations

import time

T_START = time.monotonic()                   # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str] | None = None, *, expect_platform: str = "tpu") -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from benchmarks import harness
    try:
        result = harness.run_cell(
            args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), expect_platform=expect_platform,
            t_start=T_START)
    except harness.BenchFailure as exc:
        print(f"bench: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
