#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. With no TPU, or fewer chips than the cell needs, it exits 1 and prints
no result: nothing here falls back to another device.
"""

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from benchmark import harness

    devices = harness.tpu_devices(ROOT / "BENCHMARK.json", args.workload)
    if devices is None:
        return 1
    harness.run_cell(ROOT / "BENCHMARK.json", args.workload, args.seed, args.seconds,
                     bool(args.trace), devices, t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
