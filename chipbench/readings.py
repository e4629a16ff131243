#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, in one process.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3 --controls 3
        [--out file.json] [--program-until 300] [--until 450]

For every seed: the program's first steps against the plain reference
(the lower readings); for the first ``--controls`` seeds also the control
and the planted faults against the reference (the upper readings); for
each, the verdict under the cell's own limits.  ``--out`` is written anew
after every reading.  ``--program-until`` and ``--until`` are seconds
from the start after which no further seed's program, and no further
reading, is begun.  The kind's module does the work (``readings`` in
``chipbench/kinds/``).
"""
import argparse
import json
import sys

from run import T0, find_devices, load_cell, load_module, say


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    ap.add_argument("--program-until", type=float)
    ap.add_argument("--until", type=float)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = find_devices(cell["chips"])
    kind = load_module("kinds", cell["traffic"]["kind"])

    def flush(out):
        if args.out:
            with open(args.out, "w") as f:
                json.dump({str(k): v for k, v in out.items()}, f, indent=1)

    out = kind.readings(dict(cell, devices=devices, say=say, t0=T0),
                        [int(s) for s in args.seeds.split(",")],
                        args.controls, flush, args.program_until,
                        args.until)
    out = {str(k): v for k, v in out.items()}
    for seed, entry in out.items():
        if seed != "names":
            del entry["leaves"]  # the file has them
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    sys.exit(main())
