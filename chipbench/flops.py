"""Operations and least bytes of one training step, from a configuration's
layer table (``layers`` in ``chipbench/configs/<name>.json``).

A row is one convolution or dense layer, a list under ``layer_columns`` or
an object with the same keys: ``name``, ``kernel``, ``stride``,
``groups``, ``cin``, ``cout``, the square sides ``h_in`` and ``h_out`` (a
dense layer is a 1x1 convolution on a 1x1 image) or, over a sequence,
``positions`` in their place, ``count`` (how often it occurs) and
``needs_input_grad`` (the first layer's backward pass makes no gradient
of its input).  A row of the batch is an image or a sequence.
Multiply-adds of one product = rows x positions out x cout x cin /
groups x kernel^2.  A training step runs three products per layer: the
forward one, the gradient of the input and the gradient of the weights;
nothing is counted twice for recomputation, and the elementwise work of
normalisation, activations, loss and optimizer is not counted at all:
these are the operations the model requires of a matrix unit.

The least bytes are counted per layer and pass, each array once, in the
compute type: the forward pass reads the input and the weights and
writes the output; the backward pass reads the input, the output's
gradient and the weights and writes the weights' gradient and, where it
is needed, the input's.  (Counted per product, the output's gradient
would be read twice, and a kernel that makes both gradients in one pass
would read over 100%.)  The roofline time of a pass is the larger of
operations / peak operations per second and bytes / peak bytes per
second; a layer's is the sum over its two passes.

Work that is no such product (pooling, attention's scores) is a row
whose work is given outright: ``{"name", "count", "blocks": [...],
"per_row": {"forward": {"macs", "bytes"}, "backward": {"macs",
"bytes"}}}``.  ``per_row`` holds the multiply-adds and the least bytes of
ONE occurrence for ONE row of the batch, counted by whoever writes the
configuration and held to a hand count by a test of that configuration;
``blocks`` names the gluon blocks or kernels whose traced events do the
work (``by_block_s``, ``by_kernel_s`` of ``chipbench/trace_reduce.py``).
Such a row holds no weights.  :func:`passes` yields it like the others
and :func:`step_flops` counts its multiply-adds; :func:`step_roofline_s`
keeps to the products (it is what ``conv_roofline.train`` sets against
the time of the events that hold a convolution or a dot), and
:func:`rows_roofline_s` gives the least time of the rows it is asked for,
of either form.  Work whose amount the data decides (the tokens routed
to the experts a chip holds) is entered at its expectation, a product
row with the expected ``positions`` or a ``per_row`` row, and listed under
the configuration's ``assumed``; a row scaled by a counter of the run
waits for a program that hands such a counter out of its step.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind):
    """The published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


def rows(config):
    """Every row of the table as an object."""
    cols = config.get("layer_columns", [])
    return [dict(r) if isinstance(r, dict) else dict(zip(cols, r))
            for r in config["layers"]]


def is_product(r):
    """Whether the row ``r`` is a convolution or dense layer, and not one
    whose work is given outright."""
    return "per_row" not in r


def row_weights(r):
    """Elements of the weights of one occurrence of the row ``r``."""
    if not is_product(r):
        return 0
    return r["cout"] * (r["cin"] // r["groups"]) * r["kernel"] ** 2


def passes(config, batch, keep=lambda r: True):
    """[(layer, pass, count, flops, bytes)] of one step at ``batch`` rows:
    ``flops`` and ``bytes`` are of ONE occurrence of the layer.  ``keep``
    chooses among the rows."""
    width = _DTYPE_BYTES[config["compute_dtype"]]
    out = []
    for r in rows(config):
        if not keep(r):
            continue
        if not is_product(r):
            out += [(r["name"], p, r["count"],
                     2 * batch * r["per_row"][p]["macs"],
                     batch * r["per_row"][p]["bytes"])
                    for p in ("forward", "backward")]
            continue
        if r.get("positions") is not None:
            p_in = p_out = r["positions"]
        else:
            p_in, p_out = r["h_in"] ** 2, r["h_out"] ** 2
        macs = batch * p_out * row_weights(r)
        x = batch * p_in * r["cin"] * width
        y = batch * p_out * r["cout"] * width
        w = row_weights(r) * width
        grads = 2 if r["needs_input_grad"] else 1
        out.append((r["name"], "forward", r["count"], 2 * macs, x + w + y))
        out.append((r["name"], "backward", r["count"], 2 * macs * grads,
                    x + y + w + w + (x if grads == 2 else 0)))
    return out


def forward_macs_per_image(config):
    return sum(c * f // 2 for _, p, c, f, _ in passes(config, 1)
               if p == "forward")


def step_flops(config, batch):
    """Model FLOPs of one training step (forward and backward of every
    row of the table)."""
    return sum(c * f for _, _, c, f, _ in passes(config, batch))


def _roofline_s(some_passes, peak):
    by_flops = by_bytes = 0.0
    for _, _, c, f, b in some_passes:
        tf = f / peak["bf16_flops_per_s"]
        tb = b / peak["hbm_bytes_per_s"]
        if tf >= tb:
            by_flops += c * tf
        else:
            by_bytes += c * tb
    return by_flops + by_bytes, by_flops, by_bytes


def step_roofline_s(config, batch, peak):
    """``(seconds, seconds bound by operations, seconds bound by bytes)``:
    the least time the chip could take for the passes of the step's
    convolutions and dense layers."""
    return _roofline_s(passes(config, batch, is_product), peak)


def rows_roofline_s(config, batch, peak, names):
    """The same three for the rows called ``names``, of either form."""
    return _roofline_s(
        passes(config, batch, lambda r: r["name"] in names), peak)
