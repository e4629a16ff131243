#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, in one process.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3 --controls 3 [--out file.json]

For every seed: the program's first steps against the plain reference
(the lower readings); for the first ``--controls`` seeds also the control
and the planted faults against the reference (the upper readings).  The
kind's module does the work (``readings`` in ``chipbench/kinds/``).
"""
import argparse
import json
import sys

from run import find_devices, load_cell, load_module, say


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = find_devices(cell["chips"])
    kind = load_module("kinds", cell["traffic"]["kind"])
    out = kind.readings(dict(cell, devices=devices, say=say),
                        [int(s) for s in args.seeds.split(",")],
                        args.controls)
    text = json.dumps({str(k): v for k, v in out.items()}, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    sys.exit(main())
