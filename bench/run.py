"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for (``BENCHMARK.json``). With ``--trace 0`` the last line of standard
output reports the cell's end-to-end metrics; with ``--trace 1`` a profiled
run of the same window reports its per-layer metrics, the device's busy and
window seconds, and a breakdown of device time and idle gaps. The numbers
compared against the plain reference are the last lines of standard error.

Exits non-zero, and prints no result, without a TPU, with fewer chips than
the cell asks for, or where the program under test is missing.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip", file=sys.stderr)
        return 2
    harness.report_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
