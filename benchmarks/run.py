"""One cell, one run, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (see ``benchmarks/README.md``). A
measuring run needs the TPU chips the cell asks for and fails without them;
``--rehearse`` runs the same control flow at toy widths on the CPU and prints
no result line. The last line of standard output is the result.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402  (starts the set-up clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the CPU; prints no result line")
    args = ap.parse_args(argv)
    try:
        import deepspeed_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmarks: the program under test is not importable from "
              f"{ROOT}: {e}", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        cell = harness.apply_rehearsal(cell)
    import importlib

    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    result = runner.run(cell, args)
    if args.rehearse:
        harness.say(rehearsal="done; a rehearsal is never a result",
                    correct=result["correct"], problems=result["problems"],
                    metric_names=sorted(result["metrics"]))
        return 0 if result["correct"] else 1
    harness.emit_result(result)
    return 0            # the line says whether the run was correct


if __name__ == "__main__":
    sys.exit(main())
