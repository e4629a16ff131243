"""What the three Pallas kernel families share about their target: the
one probe that says whether this process compiles for a TPU, the one
record of a kernel that declined a shape before lowering, and the one
record of an op that took its lane-dense, W-paired arm (ops/conv.py).

A kernel runs compiled on a TPU and in interpret mode elsewhere; only
a caller's ``interpret=`` argument (tests) changes that.  A shape a
kernel cannot hold (tile alignment, VMEM plan, segment count) is
decided here in Python, counted, and named — it never reaches the
compiler as an error and never falls back in silence.
"""
from __future__ import annotations

import collections
import threading

import jax

_lock = threading.Lock()
_DECLINED = collections.Counter()
_PACKED = collections.Counter()
_SEEN = set()


def on_tpu():
    """Does this process's default device compile for a TPU?  The
    answer is the device's own; a backend that cannot start raises
    here instead of reading as "not a TPU"."""
    return jax.local_devices()[0].platform == "tpu"


def declined(op, reason, ran, **fields):
    """Count one kernel that declined its input before lowering and,
    when a run log is armed, write one ``autotune`` event naming the
    reason and the arm that ``ran`` instead.  The event is
    deduplicated per (op, reason, fields): an eager loop re-decides
    per call, and N identical records explain nothing the first did
    not.  The count is not deduplicated."""
    from .. import telemetry

    with _lock:
        _DECLINED[op] += 1
    dedup = (op, reason, tuple(sorted((k, str(v))
                                      for k, v in fields.items())))
    if dedup in _SEEN or telemetry.current() is None:
        return  # unarmed: nothing recorded, don't latch the dedup
    telemetry.count(f"kernel_declined.{op}")
    telemetry.event("autotune", op=op, winner=ran, cached=False,
                    reason=str(reason),
                    **{k: str(v) for k, v in fields.items()})
    with _lock:
        _SEEN.add(dedup)


def declined_counts():
    """``{op: times a kernel declined}`` since the process started."""
    with _lock:
        return dict(_DECLINED)


def packed(op):
    """Count one call site of ``op`` that took its W-paired arm.  The
    choice is made while a program is traced, so this counts call
    sites of traced programs (and calls, run eagerly); where a run log
    is armed it counts there too."""
    from .. import telemetry

    with _lock:
        _PACKED[op] += 1
    telemetry.count(f"kernel_packed.{op}")


def packed_counts():
    """``{op: call sites that ran W-paired}`` since the process
    started."""
    with _lock:
        return dict(_PACKED)
