"""From a profiler trace to the figures the per-layer metrics read.

Two stages, so that the arithmetic can be checked on a small recorded
trace without the profiler's file format:

1. :func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
   wrote (with ``jax.profiler.ProfileData``, nothing but JAX) into plain
   lists: for every device plane (``/device:TPU:<n>``) the events of its
   ``XLA Ops`` line (what the core ran, one after another) and of its
   ``Async XLA Ops`` line (copies and collectives in flight, start to
   done), and the benchmark's own host annotations (``cb_*``).  Times
   are seconds from the trace's origin, on one clock for host and
   devices.
2. :func:`reduce` classes the events and does the arithmetic.

How an event is classed.  On a v5e the trace carries no HLO category:
an event's name is its HLO instruction (``%fusion.470 = bf16[...]
fusion(...), kind=kOutput, calls=%fused_computation.12, ...``).  The
instruction's opcode gives the class, and for a ``fusion`` the body it
calls is looked up in the compiled step's text
(``compiled.as_text()``): a fusion whose body holds a ``convolution``
or a ``dot`` is a ``conv_dot`` event.  A ``custom-call`` is a
``conv_dot`` event where its target, its ``kernel_name`` or the
instruction's own name without its number (``%bnreluconv_bwd.1``, which
is how a ``pl.pallas_call(name=...)`` shows on the chip) is listed by a
file of ``chipbench/kernels/`` (every ``*.json`` there, each
``{"kernels": [...]}``: a configuration that brings a kernel brings a
file), so that a kernel swapped in for a convolution or a dot leaves
something that bounds the share.  Classes: ``conv_dot``, ``collective``,
``copy``, ``custom_call``, ``other``.

Where the time lies.  The compiled text's ``metadata={op_name=...}``
carries the scopes the program opened (``jax.named_scope``):
``mx_forward``, ``mx_loss``, ``mx_guard``, ``mx_exchange``,
``mx_optimizer``; the backward pass is ``transpose(jvp(mx_forward))``.
``by_phase_s`` counts every event of the busiest device under ONE phase,
that of its instruction or, for a fusion without a scope of its own, of
its body's root.  The phases add up to the operations' seconds; a
phase's time per step is read from here (``forward_ms.train``,
``backward_ms.train``).  XLA fuses across scopes (a weight's update rides
in the fusion that makes its gradient), so a phase holds what rides with
it.  ``by_block_s`` splits every phase by block, ``{phase: {block:
seconds}}``: within forward and backward the innermost scope after the
phase's that is no wrapper (``jvp(``, ``transpose(``, ``jit(``) and not
the operation itself is the gluon block, which the program opens under
the block's own ``name``; an event that names none goes under ``""``, so
a phase's blocks add up to the phase (:func:`block_seconds` reads it;
``pool_roofline.train``).  ``by_phase_class_s`` splits every phase by
class in the same form: XLA rewrites collectives and drops their scope
as it does (on four chips the gradients' combined ``all-reduce`` carries
no ``op_name``), so ``exchange_ms.train`` takes the collectives of every
phase with what else ``mx_exchange`` holds.  :func:`phase_and_block` and
:func:`phase_table` are the benchmark's own copy, on purpose (the
yardstick lies where the program's PRs cannot move it), of
``mxnet_tpu/profiler.py::_phase_and_block`` and ``_scope_table``;
``tests/chipbench/test_units.py`` holds the two to the same table on the
program's own recording.

By kernel.  ``by_kernel_s`` holds the seconds of every ``custom-call``
event of the busiest device under the instruction's own name without its
number (``%flash_attention_fwd.3`` -> ``flash_attention_fwd``; XLA's own,
such as ``%custom-call.9``, go under ``custom-call``), whether or not a
file of ``kernels/`` lists it: a metric that reads one kernel's time
reads it from here.
"""
import functools
import glob
import gzip
import json
import os
import re

from chipbench.hlo_collectives import COLLECTIVES

_HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "cb_"
PHASES = ("forward", "backward", "loss", "guard", "exchange", "optimizer",
          "unscoped")
#: device gaps shorter than this are the core's own turn-around between
#: two operations and are not attributed to the host
MIN_GAP_S = 2e-6

_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_ROOT = re.compile(r"^\s*ROOT\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE_PART = re.compile(r"mx_(forward|loss|guard|exchange|optimizer)\b")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_NUMBER = re.compile(r"\.\d+$")
_KERNEL = re.compile(r'kernel_name["\\:= ]+([\w.\-]+)')


# ------------------------------------------------------------ stage one
def load_xplane(path):
    """``{"devices": {plane: {"ops": [...], "async": [...]}}, "host":
    [...]}`` with events ``[name, start_s, duration_s]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    key = "ops" if line.name == OPS_LINE else "async"
                    lines[key] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
            if lines.get("ops"):
                out["devices"][plane.name] = {
                    "ops": lines["ops"], "async": lines.get("async", [])}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)]
    return out


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path):
    """Events as :func:`load_xplane` gives them, from a recorded
    ``.json`` or ``.json.gz`` (``chipbench/testdata``)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------ stage two
def op_name(event_name):
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name.strip().lstrip("%")


def unnumbered(name):
    """An instruction's name without its number: ``bnreluconv_bwd.1`` ->
    ``bnreluconv_bwd``, which is a ``pl.pallas_call``'s ``name=``."""
    return _NUMBER.sub("", name)


def opcode(instruction):
    """The opcode of an HLO instruction line as written (``fusion``,
    ``all-reduce-start``); ``""`` where the text holds none."""
    head = instruction.split(", metadata=")[0]
    eq = head.find(" = ")
    m = _OPCODE.search(head, eq if eq >= 0 else 0)
    return m.group(1) if m else ""


@functools.lru_cache(maxsize=None)
def conv_kernels():
    """The names listed by every file of ``chipbench/kernels/``."""
    names = set()
    for path in sorted(glob.glob(os.path.join(_HERE, "kernels", "*.json"))):
        with open(path) as f:
            names.update(json.load(f)["kernels"])
    return frozenset(names)


def class_table(hlo_text):
    """``{op name: class}`` for every instruction of the compiled step's
    text that is not ``other``."""
    bodies, current = {}, None
    instructions = []
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            bodies[current] = False
            continue
        if line.strip() == "}":
            current = None
            continue
        if " = " not in line:
            continue
        code = opcode(line)
        if current is not None and code in ("convolution", "dot"):
            bodies[current] = True
        instructions.append((line, code))
    kernels = conv_kernels()
    table = {}
    for line, code in instructions:
        table_class = _class_of(line, code, bodies, kernels)
        if table_class != "other":
            table[op_name(line)] = table_class
    return table


def _base(code):
    for suffix in ("-start", "-done"):
        if code.endswith(suffix):
            return code[:-len(suffix)]
    return code


def _class_of(line, code, bodies, kernels):
    base = _base(code)
    if base in COLLECTIVES:
        return "collective"
    if base in ("convolution", "dot"):
        return "conv_dot"
    if base == "copy":
        return "copy"
    if base == "fusion":
        m = _CALLS.search(line)
        return "conv_dot" if m and bodies.get(m.group(1)) else "other"
    if base == "custom-call":
        names = _TARGET.findall(line) + _KERNEL.findall(line) \
            + [unnumbered(op_name(line))]
        return "conv_dot" if kernels.intersection(names) else "custom_call"
    return "other"


def classify(event_name, table):
    """The class of a traced event: by the compiled text's table where
    the instruction is in it, else by the event's own text."""
    name = op_name(event_name)
    if name in table:
        return table[name]
    return _class_of(event_name, opcode(event_name), {}, conv_kernels())


# ----------------------------------------------------------------- phases
@functools.lru_cache(maxsize=None)
def phase_and_block(scope):
    """``(phase, block)`` of an ``op_name``.  The phase is its innermost
    ``mx_*`` scope (the transpose of forward or loss is backward),
    ``unscoped`` where it holds none.  Within forward and backward the
    block is the innermost part after the phase's that is neither a
    wrapper (it holds a ``(``) nor the last part, the operation itself;
    ``""`` where there is none."""
    parts = [q for q in scope.split(";")[0].split("/") if q]
    phase, at = "unscoped", None
    for i, part in enumerate(parts):
        m = _PHASE_PART.search(part)
        if m:
            phase, at = m.group(1), i
            if part.startswith("transpose(") and phase in ("forward",
                                                           "loss"):
                phase = "backward"
    block = ""
    if phase in ("forward", "backward"):
        inner = [q for q in parts[at + 1:-1] if "(" not in q]
        block = inner[-1] if inner else ""
    return phase, block


def phase_of(scope):
    """The phase alone of an ``op_name``."""
    return phase_and_block(scope)[0]


def phase_table(hlo_text):
    """``{instruction: op_name}`` from a compiled step's text.  An
    instruction without metadata of its own that calls a computation (a
    fusion) takes the ``op_name`` of that computation's root."""
    own, calls, roots, current = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            continue
        m = _NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        scope = _OP_NAME.search(line)
        if scope:
            own[name] = scope.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if _ROOT.match(line) and current is not None:
            roots[current] = name
    table = dict(own)
    for name, body in calls.items():
        table[name] = own.get(name) or own.get(roots.get(body), "")
    return table


def _phase_and_block_of_event(event_name, scopes):
    in_text = _OP_NAME.search(event_name)
    return phase_and_block(in_text.group(1) if in_text
                           else scopes.get(op_name(event_name), ""))


def block_seconds(by_block_s, blocks, phases=None):
    """Seconds of the ``blocks`` in ``by_block_s``, over ``phases`` or
    over all of them."""
    return sum(seconds for phase, of_phase in by_block_s.items()
               if phases is None or phase in phases
               for block, seconds in of_phase.items() if block in blocks)


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The part of merged intervals ``a`` that merged ``b`` leaves."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(events, hlo_text=""):
    """The figures of one traced window.  Per device and averaged over
    the devices: seconds busy (the union of the ``XLA Ops`` events), the
    window (first operation's start to the last one's end), seconds by
    class, and the collective seconds during which no operation of
    another class ran on that device.  ``device_ops``, ``idle_gaps``,
    ``by_phase_s``, ``by_block_s``, ``by_phase_class_s`` and ``by_kernel_s``
    are of the busiest device."""
    table = class_table(hlo_text) if hlo_text else {}
    scopes = phase_table(hlo_text) if hlo_text else {}
    host = [(n, s, s + d) for n, s, d in events["host"]
            if n != "cb_traced_window"]
    per_device = {}
    for plane, lines in sorted(events["devices"].items()):
        ops = [(n, s, s + d, classify(n, table))
               for n, s, d in lines["ops"]]
        asyncs = [(n, s, s + d, classify(n, table))
                  for n, s, d in lines["async"]]
        busy = union((s, e) for _, s, e, _ in ops)
        window = (busy[0][0], busy[-1][1])
        by_class, by_op, by_phase, by_kernel = {}, {}, {}, {}
        by_block, by_phase_class = {}, {}
        for n, s, e, c in ops:
            by_class[c] = by_class.get(c, 0.0) + (e - s)
            name = op_name(n)
            key = f"{c}:{name}"
            by_op[key] = by_op.get(key, 0.0) + (e - s)
            phase, block = _phase_and_block_of_event(n, scopes)
            by_phase[phase] = by_phase.get(phase, 0.0) + (e - s)
            for split, key in ((by_block, block), (by_phase_class, c)):
                of_phase = split.setdefault(phase, {})
                of_phase[key] = of_phase.get(key, 0.0) + (e - s)
            if _base(opcode(n)) == "custom-call":
                kernel = unnumbered(name)
                by_kernel[kernel] = by_kernel.get(kernel, 0.0) + (e - s)
        coll = union((s, e) for _, s, e, c in ops + asyncs
                     if c == "collective")
        rest = union((s, e) for _, s, e, c in ops if c != "collective")
        gaps = subtract([window], busy)
        per_device[plane] = {
            "busy_s": length(busy), "window_s": window[1] - window[0],
            "by_class_s": by_class, "by_op_s": by_op,
            "by_phase_s": by_phase, "by_block_s": by_block,
            "by_phase_class_s": by_phase_class, "by_kernel_s": by_kernel,
            "collective_s": length(coll),
            "collective_exposed_s": length(subtract(coll, rest)),
            "gaps": gaps,
        }
    if not per_device:
        raise ValueError("the trace holds no device operation")
    n = len(per_device)
    busiest = max(per_device.values(), key=lambda d: d["busy_s"])
    classes = sorted({c for d in per_device.values()
                      for c in d["by_class_s"]})
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "window_s": sum(d["window_s"] for d in per_device.values()) / n,
        "busiest_busy_s": busiest["busy_s"],
        "busiest_window_s": busiest["window_s"],
        "by_class_s": {c: sum(d["by_class_s"].get(c, 0.0)
                              for d in per_device.values()) / n
                       for c in classes},
        "collective_s": sum(d["collective_s"]
                            for d in per_device.values()) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"]
                                    for d in per_device.values()) / n,
        "by_phase_s": busiest["by_phase_s"],
        "by_block_s": busiest["by_block_s"],
        "by_phase_class_s": busiest["by_phase_class_s"],
        "by_kernel_s": busiest["by_kernel_s"],
        "device_ops": _top_ops(busiest),
        "idle_gaps": _gaps_by_host_span(busiest["gaps"], host),
    }


def _top_ops(device, n_classes=5, n_total=10):
    classes = sorted(device["by_class_s"].items(), key=lambda kv: -kv[1])
    out = [[f"class:{c}", s] for c, s in classes[:n_classes]]
    ops = sorted(device["by_op_s"].items(), key=lambda kv: -kv[1])
    return out + [[f"op:{k}", s] for k, s in ops[:n_total - len(out)]]


def _gaps_by_host_span(gaps, host, limit=10):
    """Idle seconds of the device by what the host was doing: each gap
    goes to the benchmark's span that covers most of it
    (``cb_feed_wait``, ``cb_dispatch``, ``cb_loss_read``), or to
    ``between_spans``."""
    total = {}
    for s, e in gaps:
        if e - s < MIN_GAP_S:
            name = "under_2us_between_ops"
        else:
            best, name = 0.0, "between_spans"
            for hn, hs, he in host:
                cover = min(e, he) - max(s, hs)
                if cover > best:
                    best, name = cover, hn
        total[name] = total.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:limit]]
