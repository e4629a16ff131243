#!/usr/bin/env python3
"""The readings a cell's limits are set from, for a cell whose state the
chip holds only once.

    python3 tools/cell_readings.py --workload <cell> --seeds 1,2,3 --controls 3
        [--out file.json] [--until 1500]

``chipbench/readings.py`` keeps a template of the program's state beside
the copy each seed trains (two copies of weights and optimizer slots,
and the step's temporaries on top): at 667 M parameters that is 10.7 GB
before the step reserves its 4.8 (PERF.md, section 7).  This tool takes
the same readings through the same functions of the cell's kind
(``start``, ``first_steps``, ``reference_side``, ``compare``) with one
copy: a seed's state is the one the seed before it left, the weights
placed anew and every optimizer slot set to nought in place.  For every
seed the program's first steps against the plain reference; for the
first ``--controls`` seeds the reference in float8 and with half of
each batch left out, against the reference; for each the verdict under
the cell's own limits.  ``--out`` is written anew after every reading;
no reading begins later than ``--until`` seconds after the start.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    ap.add_argument("--until", type=float)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from chipbench import compare
    from chipbench import run as cb

    cell = dict(cb.load_cell(args.workload), say=cb.say, t0=cb.T0)
    cell["devices"] = cb.find_devices(cell["chips"])
    traffic = cell["traffic"]
    kind = cb.load_module("kinds", traffic["kind"])
    built = kind.build(cell)
    state = (built.pop("params"), built.pop("opt_state"))
    wipe = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree),
                   donate_argnums=0)
    out = {"names": built["names"]}

    def late():
        return args.until is not None \
            and time.perf_counter() - cb.T0 > args.until

    def read(seed, who, side, ref):
        values = compare.numbers(side, ref)[0]
        entry = out.setdefault(str(seed), {"correct": {}})
        entry[who] = values
        entry["correct"][who] = compare.verdict(values, traffic["limits"])[0]
        cb.say(f"seed {seed} {who}: correct {entry['correct'][who]} "
               + json.dumps(values))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    kept = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        if kept and late():
            cb.say(f"no time for the program on seed {seed} and after")
            break
        loop, feed, w0, pool = kind.start(
            cell, built, seed, state[0], wipe(state[1]),
            traffic["check_steps"])
        try:
            kept[seed] = (kind.first_steps(cell, built, loop, w0), w0, pool)
        finally:
            feed.close()
        cb.say(f"seed {seed}: program losses "
               + " ".join(f"{v:.4f}" for v in loop.losses))
        state = (loop.params, loop.opt_state)
        loop.params = loop.opt_state = None
    built.pop("step")
    del state
    below = {"bfloat16": "float8", "float32": "float8"}[
        cell["config"]["compute_dtype"]]
    faults = {"control_" + below: {"precision": below},
              "fault_half_batch": {"rows": traffic["batch_per_chip"] // 2}}
    refs = {}
    for seed, (prog, w0, pool) in kept.items():
        if refs and late():
            cb.say(f"no time for the reference on seed {seed} and after")
            break
        refs[seed] = kind.reference_side(cell, built, w0, pool)
        read(seed, "program", prog, refs[seed])
    for who, fault in faults.items():
        for seed in list(refs)[:args.controls]:
            if late():
                cb.say(f"no time for {who} on seed {seed} and after")
                break
            _, w0, pool = kept[seed]
            read(seed, who,
                 kind.reference_side(cell, built, w0, pool, **fault),
                 refs[seed])
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    sys.exit(main())
