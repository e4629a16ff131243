"""Collective launches, tensors and bytes of a compiled step, read from
its HLO text.

A copy of ``mxnet_tpu/parallel/zero.py::collective_bytes`` and its line
parser as PR 21 repaired them (their counts matched the chip's compiled
text exactly), kept here so that no later PR can change the yardstick.
"""
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
             "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

# ``%name = <result shape> <kind>[-start](operands...``: the result shape
# is whatever sits between the first " = " and the op kind.  XLA combines
# collectives into one tuple-shaped op whose shape text carries
# ``/*index=5*/`` comments, and the TPU compiler adds tiling such as
# ``{0:T(1024)S(1)}``.  An op kind is preceded by a blank; a reference to
# a collective (``get-tuple-element(%all-reduce.3)``) by ``%``, and
# ``-done`` ops do not match.
_COLLECTIVE_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s("
    + "|".join(COLLECTIVES) + r")(-start)?\(")
_SHAPE = re.compile(
    "(" + "|".join(sorted(_DT_BYTES, key=len, reverse=True))
    + r")\[([\d,]*)\]")


def _collective_shapes(line):
    """``(kind, [(dtype, elements), ...])`` of the results of an HLO line
    that launches a collective, else None.  An async ``all-gather-start``
    or ``collective-permute-start`` returns (operands..., results...,
    scalar contexts), of which only the results are kept; an
    ``all-reduce-start`` returns its results alone."""
    m = _COLLECTIVE_LINE.match(line)
    if not m:
        return None
    shapes_text, kind, start = m.groups()
    shapes = []
    for sm in _SHAPE.finditer(shapes_text):
        n = 1
        for d in sm.group(2).split(","):
            if d:
                n *= int(d)
        shapes.append((sm.group(1), n))
    if start and kind != "all-reduce":
        shapes = [sh for sh in shapes if sh[1] > 1] or shapes
        shapes = shapes[len(shapes) // 2:]
    return kind, shapes


def collective_bytes(hlo_text):
    """``{"bytes", "counts", "tensors"}`` by kind of collective, and
    ``"total_bytes"``: result bytes per device and step, launches, and
    the arrays those launches carry."""
    out = {k: 0 for k in COLLECTIVES}
    counts = dict(out)
    tensors = dict(out)
    for line in hlo_text.splitlines():
        parsed = _collective_shapes(line)
        if parsed is None:
            continue
        kind, shapes = parsed
        out[kind] += sum(n * _DT_BYTES[dt] for dt, n in shapes)
        counts[kind] += 1
        tensors[kind] += len(shapes)
    return {"bytes": out, "counts": counts, "tensors": tensors,
            "total_bytes": sum(out.values())}
