#!/usr/bin/env python3
"""Cut a profiler trace of a training run down to a recording small
enough to keep under ``tests/data/``.

    python tools/cut_device_trace.py <trace.xplane.pb> <step_hlo.txt[.gz]> \
        <out.json.gz> [--steps 2]

Keeps the first ``--steps`` runs of the traced program: of every device
plane the ``XLA Ops`` and ``XLA Modules`` events in that window, of the
host planes the ``mx_*`` spans up to its end, each with its stats and
its times as the profiler wrote them.  An instruction, in an event and
in the compiled text alike, is cut to what ``mx.profiler.dumps()``
reads: its name, ``ROOT``, its opcode, ``calls=``, the custom call's
target and ``metadata={op_name=...}``; shapes, layouts and operands are
left out.  Under ``known`` the file holds what the reader gave on the
uncut trace of the same window, so a test can hold the cut to it.

``tests/data/vgg16_train_2steps_trace.json.gz`` was made with this from
``mx.profiler`` around ``vgg16_train``'s own step and feed on a v5e.
"""
import argparse
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

_KEEP = (re.compile(r"calls=%?[\w.\-]+"),
         re.compile(r'custom_call_target="[^"]+"'),
         re.compile(r'metadata=\{op_name="[^"]*"'))


def cut_instruction(text):
    """``[ROOT ]%name = opcode()`` and the attributes the reader reads."""
    from mxnet_tpu import profiler

    m = profiler._INSTRUCTION.match(text)
    if not m:
        return text
    kept = [k.search(text) for k in _KEEP]
    tail = "".join(", " + k.group(0) + ("}" if k.group(0).startswith(
        "metadata") else "") for k in kept if k)
    return (f"  {'ROOT ' if m.group(1) else ''}%{m.group(2)} = "
            f"{profiler._opcode(text)}(){tail}")


def cut_hlo(text):
    from mxnet_tpu import profiler

    out = []
    for line in text.splitlines():
        if line.startswith("HloModule"):
            out.append(line.split(",")[0])
        elif profiler._COMPUTATION.match(line):
            name = profiler._COMPUTATION.match(line).group(1)
            out.append(("ENTRY " if "ENTRY" in line else "")
                       + f"%{name} () -> () {{")
        elif line.strip() == "}":
            out.append("}")
        elif profiler._INSTRUCTION.match(line):
            out.append(cut_instruction(line))
    return "\n".join(out) + "\n"


def main(argv=None):
    import jax

    from mxnet_tpu import profiler

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("hlo")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    opener = gzip.open if args.hlo.endswith(".gz") else open
    with opener(args.hlo, "rt") as f:
        hlo = f.read()
    data = jax.profiler.ProfileData.from_file(args.xplane)

    def events(line):
        return [[e.name, e.start_ns, e.duration_ns,
                 {k: v for k, v in e.stats
                  if isinstance(v, (int, float, str))}]
                for e in line.events]

    planes, t0, t1 = [], None, None
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: events(ln) for ln in plane.lines
                 if ln.name in (profiler._OPS_LINE,
                                profiler._MODULES_LINE)}
        modules = lines.get(profiler._MODULES_LINE, [])[:args.steps]
        if not modules:
            continue
        t0 = modules[0][1]
        t1 = modules[-1][1] + modules[-1][2]
        planes.append({"name": plane.name, "lines": [
            {"name": profiler._MODULES_LINE, "events": modules},
            {"name": profiler._OPS_LINE, "events": [
                [cut_instruction(n), s, d, {}]
                for n, s, d, _ in lines[profiler._OPS_LINE]
                if t0 <= s < t1]}]})
    if t0 is None:
        raise SystemExit("the trace holds no device plane with modules")
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            kept = [{"name": ln.name, "events": [
                e for e in events(ln)
                if e[0].startswith("mx_") and e[1] < t1]}
                for ln in plane.lines]
            planes.append({"name": plane.name, "lines": [
                ln for ln in kept if ln["events"]]})

    # what the reader gives on the uncut trace of the same window
    whole = profiler._load_xplane(args.xplane)
    for lines in whole["devices"].values():
        lines["ops"] = [e for e in lines["ops"]
                        if t0 * 1e-9 <= e[1] < t1 * 1e-9]
        lines["modules"] = lines["modules"][:args.steps]
    whole["host"] = [e for e in whole["host"] if e[1] < t1 * 1e-9]
    known = profiler._reduce_device(whole, [hlo])
    with gzip.open(args.out, "wt") as f:
        json.dump({"what": __doc__.split("\n\n")[2], "planes": planes,
                   "hlo_text": cut_hlo(hlo), "known": known}, f)
    print(f"{args.out}: {os.path.getsize(args.out):,} bytes, "
          f"{sum(len(ln['events']) for p in planes for ln in p['lines'])}"
          " events")


if __name__ == "__main__":
    main()
