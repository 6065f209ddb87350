"""benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The one command of BENCHMARK.json. Reads the cell from BENCHMARK.json and the
data files beside this one, runs it on the TPU JAX finds (and fails without
one), and prints one JSON object as the last line of standard output.
``--variant`` and ``--rehearse`` are for the tests under benchmark/tests and
the readings the limits were set from; the driver passes neither.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", default="",
                    choices=("", "control", "control:fp8", "control:int8", "bf16",
                             "unchanged", "half_batch"),
                    help="tests only: the lower-precision control, or the "
                         "timed path broken underneath")
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, tiny widths, interpreted kernels")
    ap.add_argument("--dump_trace", action="store_true",
                    help="builder only: leave a summary of the trace under "
                         "chiprun_out/")
    ns = ap.parse_args()
    import harness  # beside this file, which is sys.path[0]

    return harness.main(ns, T0)


if __name__ == "__main__":
    sys.exit(main())
