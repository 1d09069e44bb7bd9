#!/usr/bin/env python3
"""Readings for a cell's limits, on the chip, several seeds in one process:

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 --seconds 12 \\
        [--control] [--out chiprun_out/calib.jsonl]

Each seed is a whole short run of the cell (``harness.run_cell``: the same
build, warm-up, window, drain and comparison as a benchmark run). With
``--control`` the reference is also computed in the next precision below
the configuration's at the same positions of the same sequences, the
tokens it puts first take the served tokens' place, and the same decision
is made over them: it has to come out as not correct, or this exits 1.
The benchmark's own runs never run the control. Not part of the
contract's command; a cell's ``limits`` file records the readings this
printed.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from benchmark import harness

    devices = harness.tpu_devices(ROOT / "BENCHMARK.json", args.workload)
    if devices is None:
        return 1
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(ROOT / "BENCHMARK.json", args.workload, seed, args.seconds,
                             False, devices, control=args.control)
        row = {"workload": args.workload, "seed": seed, "correct": r["correct"],
               **r.get("numbers", {}), "compared": r["compared"],
               "control": r.get("control"), "device": r["device"]["kind"]}
        if args.control and r["control"]["correct"]:
            passed.append(seed)
        print("CALIB " + json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    if passed:
        print(f"the control came out as correct on seeds {passed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
