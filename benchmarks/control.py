"""The control of ``correct``, and the faults, at a cell's own size.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds <s>
        [--fault alter_answer|drop_half]

Without ``--fault`` it puts the plain reference in the program's place with
one guarantee of the configuration broken (``sources.ReferenceSource``: the
origin's bytes placed on the device directly, one seeded bit of each file
flipped on the way, as a wire that verifies nothing would deliver them) and
drives the cell's own driver and comparison over it. With ``--fault`` it
runs the real swarm with that fault planted in the program
(``benchmarks/faults.py``). Either way every seed has to come out NOT
correct: the exit code is 0 when all did, 1 when a run still read correct.
One process per seed, one after the other (a chip belongs to one process).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def one(workload: str, seed: int, seconds: float, fault: str | None,
        platform: str) -> int:
    from benchmarks import harness
    try:
        result = harness.run_cell(
            workload, seed=seed, seconds=seconds, trace=False,
            expect_platform=platform, control=fault is None, fault=fault)
    except harness.BenchFailure as exc:
        print(f"control: no result: {exc}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps({"seed": seed, "fault": fault or "broken_reference",
                      "correct": result["correct"],
                      "compared": result["compared"]}), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--fault", default=None)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--one", type=int, default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.one is not None:
        return one(args.workload, args.one, args.seconds, args.fault,
                   args.platform)
    still_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--one", str(seed), "--seconds",
             str(args.seconds), "--platform", args.platform]
            + (["--fault", args.fault] if args.fault else []),
            stdout=subprocess.PIPE, text=True)
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else ""
        print(line or f"seed {seed}: no result (rc={proc.returncode})",
              flush=True)
        if proc.returncode != 0 or not line or json.loads(line)["correct"]:
            still_correct += 1
    print(f"control: {still_correct} run(s) failed to come out not correct")
    return 1 if still_correct else 0


if __name__ == "__main__":
    sys.exit(main())
