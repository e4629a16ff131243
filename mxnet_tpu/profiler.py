"""Profiler with the reference API, emitting Chrome-trace JSON.

Reference parity: python/mxnet/profiler.py:33-151 (set_config /
set_state / dump / dumps / pause / resume) and the user-scope objects
Domain/Task/Frame/Event/Counter/Marker (:225-497), backed in the
reference by the C++ Profiler with lock-free per-thread stat buffers
(src/profiler/profiler.h:251) dumped as Chrome tracing JSON
(src/profiler/aggregate_stats.cc).

TPU-native design: there is no engine thread pool to instrument — ops
dispatch asynchronously into the XLA runtime.  The profiler therefore
records two complementary layers:

  * host-side events — every ``nd`` op dispatch (the analog of the
    reference's per-op ProfileOperator begin/end), user scopes
    (Task/Frame/Event), counters and instant markers — buffered
    in-process and dumped as a Chrome trace (``chrome://tracing`` /
    Perfetto).
  * device-side tracing — ``jax.profiler`` XPlane capture for
    TensorBoard, toggled by the same set_state('run'/'stop') when
    ``set_config(profile_device=True, tensorboard_logdir=...)``.

Aggregate statistics (``dumps(format='table')``) mirror the reference's
aggregate_stats table: per-op call counts and total/min/max/mean host
dispatch time.  For a jitted step that is one row, so after a run with
``profile_device=True`` ``dumps()`` also reads the device back from the
``.xplane.pb`` the run wrote (:func:`device_report`): seconds by the
phase scopes the train step names its operations by (``mx_forward``,
its transpose for backward, ``mx_loss``, ``mx_guard``, ``mx_exchange``,
``mx_optimizer``), the costliest gluon blocks, Pallas kernels by their
``name=``, and the device's idle gaps by the ``mx_*`` host span
(``telemetry.tracing.region``) that covers most of each.

Without any session, the compiled train step keeps a record of each
call (:class:`StepWatch`, a ring of the newest 4096): when the call
began, when it had handed the step to the runtime, when a watcher
thread saw the step's loss ready on the device, and the seconds of
garbage collection in between, all on ``time.perf_counter()``.
:func:`step_records` returns them.  Between two calls in a row, the
device waited on the host for ``max(0, t_return - previous t_done)``
and worked alone from ``max(previous t_done, t_return)`` to
``t_done``; ``dumps()`` ends with the five slowest such steps ("Slowest
steps"), split so, which is where a stalling job looks first.
"""
from __future__ import annotations

import atexit
import bisect
import collections
import gc
import glob
import json
import os
import queue
import re
import tempfile
import threading
import time
import weakref

from .base import MXNetError

__all__ = [
    "set_config", "profiler_set_config", "set_state", "profiler_set_state",
    "dump", "dump_profile", "dumps", "device_report", "pause", "resume",
    "op_scope", "counting", "count", "step_counters", "step_records",
    "now_us", "run_generation", "record_span", "record_counter",
    "record_instant", "record_meta", "events_snapshot",
    "Domain", "Task", "Frame", "Event", "Counter", "Marker",
]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": False,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": True,
    "aggregate_stats": False,
    "continuous_dump": False,
    "dump_period": 1.0,
    "profile_device": False,
    "tensorboard_logdir": None,
}
_state = "stop"
_paused = False
_events = []  # chrome trace event dicts
_agg = {}  # name -> [count, total_us, min_us, max_us]
_jax_trace_active = False
_device_logdir = None  # where the last profile_device run wrote its trace
_device_run_started = 0.0  # wall time: older traces there are not ours
_programs = {}  # key -> compiled text (or a function giving it) of the
#                 programs that noted themselves during that run
_device_read = None  # (trace path, report): that run's trace, read once
_run_gen = 0  # run-window starts; external lanes key metadata off it
_t0 = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def now_us():
    """Microseconds on the profiler's trace clock — external lanes
    (telemetry.RunLog) must timestamp on THIS clock so their spans line
    up with the op events in one Perfetto timeline."""
    return _now_us()


def run_generation():
    """Counts run-window starts.  Lane owners (telemetry) key their
    per-trace metadata ('thread_name') off this so a second run window
    after a finished dump gets its lane named again, not skipped."""
    return _run_gen


def is_running():
    return _state == "run" and not _paused


def set_config(**kwargs):
    """Reference: profiler.py:33 — configure before set_state('run').

    Accepted kwargs mirror the reference (filename, profile_all,
    profile_symbolic, profile_imperative, profile_memory, profile_api,
    aggregate_stats, continuous_dump, dump_period) plus the TPU
    extensions profile_device / tensorboard_logdir.
    """
    if _state == "run":
        # reference parity (profiler.py:33 backed by the C++ check):
        # reconfiguring mid-collection (e.g. switching `filename`)
        # would silently split/lose events — refuse, like the C side
        raise MXNetError(
            "profiler.set_config cannot be called while the profiler "
            "is running; set_state('stop') first")
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise MXNetError(f"unknown profiler config keys: {sorted(unknown)}")
    if kwargs.get("profile_all"):
        _config.update(profile_symbolic=True, profile_imperative=True,
                       profile_memory=True, profile_api=True)
    _config.update(kwargs)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Deprecated reference alias (profiler.py:70)."""
    set_config(profile_symbolic=(mode in ("symbolic", "all")),
               profile_imperative=(mode in ("imperative", "all")),
               filename=filename)


def set_state(state="stop", profile_process="worker"):
    """Reference: profiler.py:89 — 'run' starts collection, 'stop' ends.

    Stopping with continuous_dump set dumps automatically (the reference
    dumps from the C++ side on WorkerProfile teardown).
    """
    global _state, _paused, _jax_trace_active, _run_gen
    global _device_logdir, _device_run_started, _device_read
    if state not in ("run", "stop"):
        raise MXNetError(f"invalid profiler state {state!r}")
    prev = _state
    _state = state
    _paused = False
    if state == "run" and prev != "run":
        _run_gen += 1
        _record_instant("profiler_start", "profiler")
        # the device section of dumps() is this run's or none
        _device_logdir = _device_read = None
        _programs.clear()
        if _config["profile_device"] and not _jax_trace_active:
            import jax

            logdir = _config["tensorboard_logdir"] or os.path.join(
                tempfile.gettempdir(), "mxnet_tpu_trace")
            # python frames off, host spans at their coarsest level:
            # at jax's defaults the device feed's host-side layout
            # change alone writes millions of events a second, and
            # nothing here reads them
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            _device_logdir, _device_run_started = logdir, time.time()
            jax.profiler.start_trace(logdir, profiler_options=options)
            _jax_trace_active = True
    elif state == "stop" and prev == "run":
        if _jax_trace_active:
            import jax

            jax.profiler.stop_trace()
            _jax_trace_active = False
        if _config["continuous_dump"]:
            dump()


def profiler_set_state(state="stop"):
    """Deprecated reference alias (profiler.py:109)."""
    set_state(state)


def pause(profile_process="worker"):
    """Reference: profiler.py:193."""
    global _paused
    _paused = True


def resume(profile_process="worker"):
    """Reference: profiler.py:209."""
    global _paused
    _paused = False


def _record(name, cat, ph, ts_us, dur_us=None, args=None, tid=None):
    ev = {
        "name": name, "cat": cat, "ph": ph, "ts": ts_us,
        "pid": os.getpid(),
        "tid": tid if tid is not None else threading.get_ident() % 100000,
    }
    if dur_us is not None:
        ev["dur"] = dur_us
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def _record_instant(name, cat, args=None):
    _record(name, cat, "i", _now_us(), args=args)


def record_op(name, dur_us, cat="operator", args=None):
    """Record one complete op-dispatch event (internal hook; the analog
    of the reference's ProfileOperator, src/profiler/profiler.h:77)."""
    _record(name, cat, "X", _now_us() - dur_us, dur_us, args=args)
    if _config["aggregate_stats"]:
        with _lock:
            ent = _agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            ent[0] += 1
            ent[1] += dur_us
            ent[2] = min(ent[2], dur_us)
            ent[3] = max(ent[3], dur_us)


def events_snapshot():
    """A copy of the buffered Chrome-trace events collected so far.

    The public hook the aggregate-opstats layer
    (:mod:`mxnet_tpu.telemetry.opstats`) folds per-op tables from:
    unlike :func:`dump`, it neither drains the buffer nor stops
    collection, so a mid-run aggregate costs one list copy."""
    with _lock:
        return list(_events)


def record_span(name, cat, start_us, dur_us, args=None, tid=None):
    """Public lane hook: one complete 'X' span on the trace clock
    (``now_us``).  Used by telemetry.RunLog to put step/feed-wait/
    checkpoint spans on the same Perfetto timeline as the op events.
    Respects the run/pause window like every other event."""
    if is_running():
        _record(name, cat, "X", start_us, dur_us, args=args, tid=tid)


def record_counter(name, value, cat="counter", tid=None):
    """Public lane hook: one 'C' counter sample (throughput, loss)."""
    if is_running():
        _record(name, cat, "C", _now_us(), args={name: value}, tid=tid)


def record_instant(name, cat, args=None, tid=None):
    """Public lane hook: one instant event."""
    if is_running():
        _record(name, cat, "i", _now_us(), args=args, tid=tid)


def record_meta(name, args, tid=None):
    """Metadata event ('M') — names a tid lane in Perfetto.  Not gated
    on is_running: lane names must land even when emitted just before
    the run window opens."""
    _record(name, "__metadata", "M", 0, args=args, tid=tid)


def op_scope(name):
    """Public dispatcher hook: a context manager timing one op dispatch,
    or None when op profiling is off (the hot-path fast exit)."""
    if is_running() and _config["profile_imperative"]:
        return _OpScope(name)
    return None


class _OpScope:
    """Context manager used by the nd dispatcher to time op dispatch."""

    __slots__ = ("name", "_start", "_bytes")

    def __init__(self, name):
        self.name = name
        self._bytes = None

    def set_result(self, out):
        """Attach the output size so the aggregate opstats table can
        report bytes per op; only ever paid while profiling is on."""
        total = 0
        outs = out if isinstance(out, (list, tuple)) else (out,)
        for o in outs:
            data = getattr(o, "_data", o)
            n = getattr(data, "nbytes", None)
            if n is not None:
                total += int(n)
        self._bytes = total or None

    def __enter__(self):
        self._start = _now_us()
        return self

    def __exit__(self, *exc):
        args = {"bytes": self._bytes} if self._bytes is not None \
            else None
        record_op(self.name, _now_us() - self._start, args=args)
        return False


def dump(finished=True, profile_process="worker"):
    """Reference: profiler.py:122 — write the Chrome trace JSON file.

    ``finished=True`` means profiling is COMPLETE: the buffer is
    flushed and collection stops (reference semantics — the C++ side
    tears down WorkerProfile).  ``finished=False`` writes a snapshot
    of everything collected so far and KEEPS collecting — the buffer
    is retained so the next dump carries the full timeline (periodic
    mid-run dumps watch a live training job without truncating it)."""
    global _state, _paused
    path = _config["filename"]
    with _lock:
        events = list(_events)
        if finished:
            _events.clear()
    if finished and _state == "run":
        global _jax_trace_active
        _state = "stop"
        _paused = False
        if _jax_trace_active:
            import jax

            jax.profiler.stop_trace()
            _jax_trace_active = False
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


def dump_profile():
    """Deprecated reference alias (profiler.py:143)."""
    dump(True)


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Reference: profiler.py:151 — return aggregate stats as a string.

    Requires set_config(aggregate_stats=True).  sort_by in
    {'total','avg','min','max','count'}.

    ``format="json"`` gives an object ``{"ops": [...], "device":
    ...}``.  After a stopped run that had ``profile_device=True`` the
    device section is :func:`device_report`'s (further tables under
    the op table); else it is null.  Once a compiled train step has run,
    the table ends with its five slowest steps (:func:`step_records`,
    no session needed).
    """
    if format not in ("table", "json"):
        raise MXNetError(f"invalid format {format!r}")
    key_idx = {"count": 0, "total": 1, "min": 2, "max": 3, "avg": 4}
    if sort_by not in key_idx:
        raise MXNetError(f"invalid sort_by {sort_by!r}")
    with _lock:
        rows = [
            (name, c, tot, mn if c else 0.0, mx, (tot / c) if c else 0.0)
            for name, (c, tot, mn, mx) in _agg.items()
        ]
        if reset:
            _agg.clear()
    rows.sort(key=lambda r: r[1 + key_idx[sort_by]], reverse=not ascending)
    device = device_report()
    if format == "json":
        ops = [{"name": n, "count": c, "total_us": t, "min_us": mn,
                "max_us": mx, "avg_us": av}
               for n, c, t, mn, mx, av in rows]
        return json.dumps({"ops": ops, "device": device})
    lines = [f"{'Name':<40s}{'Calls':>8s}{'Total(us)':>14s}"
             f"{'Min(us)':>12s}{'Max(us)':>12s}{'Avg(us)':>12s}"]
    for n, c, t, mn, mx, av in rows:
        lines.append(f"{n:<40.40s}{c:>8d}{t:>14.1f}{mn:>12.1f}"
                     f"{mx:>12.1f}{av:>12.1f}")
    if device is not None:
        lines.append(_device_table(device))
    slowest = _slowest_steps()
    if slowest:
        lines.append(_steps_table(slowest))
    return "\n".join(lines)


# ------------------------------------------------ the device, read back
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
#: device gaps shorter than this are the core's own turn-around between
#: two operations and go to no host span
_MIN_GAP_S = 2e-6
_PHASES = ("forward", "backward", "loss", "guard", "exchange",
           "optimizer", "unscoped")
_PASSES = {"forward", "backward", "loss", "unscoped"}
_PHASE_PART = re.compile(r"mx_(forward|loss|guard|exchange|optimizer)\b")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_COLLECTIVE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start|-done)?\(")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _load_xplane(path):
    """The ``.xplane.pb`` of ``jax.profiler`` as plain lists: for every
    device plane the events of its ``XLA Ops`` line (what the core
    ran, one after another; an event's text is its HLO instruction)
    and of its ``XLA Modules`` line (which program ran when), and from
    the host planes the ``mx_*`` spans; every event ``[text, start_s,
    duration_s]``, on one clock."""
    import jax

    def events(line, keep=lambda name: True):
        return [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                for e in line.events if keep(e.name)]

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = events(lines[_OPS_LINE]) if _OPS_LINE in lines else []
            if ops:
                out["devices"][plane.name] = {
                    "ops": ops,
                    "modules": events(lines[_MODULES_LINE])
                    if _MODULES_LINE in lines else []}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += events(
                    line, lambda name: name.startswith("mx_"))
    return out


def _instruction_name(text):
    m = _INSTRUCTION.match(text)
    return m.group(2) if m else text.strip().lstrip("%")


def _opcode(text):
    head = text.split(", metadata=")[0]
    eq = head.find(" = ")
    m = _OPCODE.search(head, eq if eq >= 0 else 0)
    return m.group(1) if m else _instruction_name(text).split(".")[0]


def _scope_table(hlo_text):
    """``{instruction: (op_name, phases its body holds)}`` from a
    compiled step's text.  An instruction without metadata of its own
    that calls a computation (a fusion) takes the scope of that
    computation's root instruction; the second entry lists the phases
    of every instruction of the called computation, so that a fusion
    of the backward pass into which XLA put the optimizer's update
    says so.

    A collective without metadata goes under ``mx_exchange``, and so
    does an unnamed fusion that holds one: the compiler's passes that
    rewrite a collective (a ``psum_scatter`` decomposed into an
    all-reduce and a slice or fused as ``all-reduce-scatter``,
    neighbouring all-reduces combined into one launch, a
    collective-permute that realigns rows) drop the ``op_name`` the
    program gave it, and a compiled step moves data between chips for
    nothing but its exchange."""
    own, calls, roots, held, wire, current = {}, {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            held[current] = set()
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        scope = _OP_NAME.search(line)
        if scope:
            own[name] = scope.group(1)
        else:
            rewritten = _COLLECTIVE.search(line)
            if rewritten:
                own[name] = "mx_exchange/" + rewritten.group(1)
                if current is not None:
                    wire[current] = own[name]
        if name in own and current is not None:
            held[current].add(_phase_and_block(own[name])[0])
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and current is not None:
            roots[current] = name
    table = {name: (scope, ()) for name, scope in own.items()}
    for name, body in calls.items():
        scope = own.get(name) or own.get(roots.get(body)) \
            or wire.get(body, "")
        table[name] = (scope, tuple(sorted(
            held.get(body, set()) - {"unscoped"})))
    return table


def _phase_and_block(op_name):
    """``(phase, block)`` of an operation from the scopes in its
    ``op_name``: the innermost ``mx_*`` scope names the phase (the
    transpose of forward or loss is backward), and within forward and
    backward the innermost scope after it is the gluon block."""
    parts = [q for q in op_name.split(";")[0].split("/") if q]
    phase = at = None
    for i, part in enumerate(parts):
        m = _PHASE_PART.search(part)
        if m:
            phase, at = m.group(1), i
            if part.startswith("transpose(") and phase in ("forward",
                                                           "loss"):
                phase = "backward"
    if phase is None:
        return "unscoped", None
    block = None
    if phase in ("forward", "backward"):
        inner = [q for q in parts[at + 1:-1] if "(" not in q]
        block = inner[-1] if inner else None
    return phase, block


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _gaps_by_span(gaps, host):
    """Idle seconds of the device by what the host was doing: each gap
    of ``_MIN_GAP_S`` or more goes to the ``mx_*`` span that covers
    most of it, or to ``between_spans``."""
    total = {}
    for s, e in gaps:
        if e - s < _MIN_GAP_S:
            name = "under_2us_between_ops"
        else:
            best, name = 0.0, "between_spans"
            for hn, hs, hd in host:
                cover = min(e, hs + hd) - max(s, hs)
                if cover > best:
                    best, name = cover, hn
        total[name] = total.get(name, 0.0) + (e - s)
    return total


def _tables_by_module(hlo_texts):
    """``{module name: scope table}``; an instruction's name means
    something only within its own program."""
    tables = {}
    for text in hlo_texts:
        m = _MODULE.match(text)
        tables[m.group(1) if m else ""] = _scope_table(text)
    return tables


def _reduce_device(events, hlo_texts=()):
    """The device section of :func:`dumps` from :func:`_load_xplane`'s
    events, for the busiest device."""
    tables = _tables_by_module(hlo_texts)
    if not events["devices"]:
        return None
    plane, lines = max(
        events["devices"].items(),
        key=lambda kv: sum(d for _, _, d in kv[1]["ops"]))
    ops = lines["ops"]
    # which program ran when ("jit__scoped_step(<fingerprint>)")
    modules = sorted((s, s + d, name.split("(")[0])
                     for name, s, d in lines["modules"])
    starts = [m[0] for m in modules]
    busy = _union((s, s + d) for _, s, d in ops)
    window = (busy[0][0], busy[-1][1])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    phases = dict.fromkeys(_PHASES, 0.0)
    blocks, kernels, unscoped, mixed = {}, {}, {}, {}
    for text, start, dur in ops:
        name = _instruction_name(text)
        k = bisect.bisect_right(starts, start) - 1
        module = modules[k][2] if k >= 0 and start < modules[k][1] else ""
        scope, held = tables.get(module, tables.get("", {})).get(
            name, ("", ()))
        in_text = _OP_NAME.search(text)
        if in_text:
            scope = in_text.group(1)
        phase, block = _phase_and_block(scope)
        phases[phase] += dur
        if block is not None:
            blocks[phase, block] = blocks.get((phase, block), 0.0) + dur
        for other in held:
            # forward, backward and loss share fusions as a matter of
            # course (a mask kept for the backward pass); what tells
            # is a phase of the update riding in one of theirs
            if other != phase and not {other, phase} <= _PASSES:
                mixed[phase, other] = mixed.get((phase, other), 0.0) + dur
        if phase == "unscoped":
            code = _opcode(text)
            unscoped[code] = unscoped.get(code, 0.0) + dur
        if 'custom_call_target="tpu_custom_call"' in text:
            # a Pallas kernel: its instruction carries its name=
            kernel = re.sub(r"\.\d+$", "", name)
            kernels[kernel] = kernels.get(kernel, 0.0) + dur
    total = sum(phases.values())

    def ranked(d, n=None):
        return sorted(d.items(), key=lambda kv: -kv[1])[:n]

    return {
        "device": plane,
        # how often a program ran on the device in the traced window
        # (the host's mx_step spans run ahead of it)
        "runs": len(modules),
        "busy_s": sum(e - s for s, e in busy),
        "window_s": window[1] - window[0],
        "phases": {p: {"seconds": v,
                       "share": v / total if total else 0.0}
                   for p, v in phases.items()},
        "fused": [{"phase": p, "holds": o, "seconds": v}
                  for (p, o), v in ranked(mixed)],
        "blocks": [{"phase": p, "block": b, "seconds": v}
                   for (p, b), v in ranked(blocks, 10)],
        "kernels": [{"name": k, "seconds": v}
                    for k, v in ranked(kernels)],
        "unscoped": [{"opcode": k, "seconds": v}
                     for k, v in ranked(unscoped, 10)],
        "idle": [{"span": k, "seconds": v} for k, v in
                 ranked(_gaps_by_span(gaps, events["host"]))],
    }


def note_program(key, text_fn):
    """A compiled program that runs under a ``profile_device`` run
    names itself (``parallel.make_train_step``'s step does, once a run
    for each shape, and only while ``_jax_trace_active``):
    ``text_fn()`` gives its compiled text, from which
    :func:`device_report` reads the scope of every traced instruction,
    and is called only when the report is asked for."""
    _programs[key] = text_fn


# ------------------------------------------- a step's own record and counters
_counting = threading.local()  # .box: {name: (how, value)} while a forward
#                                pass that a compiled step traces collects
_RING_STEPS = 4096
_step_counters = collections.deque(maxlen=_RING_STEPS)  # the newest steps'
#                                records (_Step), their counters still arrays
#                                on the device
_stamped = threading.Condition()  # a watcher stamped a step's completion
_gc = [0.0, 0.0]  # seconds of garbage collection so far; when the running
#                   collection started
_watches = weakref.WeakSet()  # StepWatch objects whose thread runs
_watching = False  # _end_watches is registered with atexit


class _Step:
    """One call of a compiled train step, on ``time.perf_counter()``.
    ``gc_s`` holds the collector's running total at ``t_return`` until
    the watcher stamps ``t_done`` and makes it the difference."""

    __slots__ = ("n", "t_enter", "t_return", "t_done", "gc_s", "counters",
                 "watch")

    def __init__(self, n=None, t_enter=None, t_return=None, counters=None,
                 watch=None):
        self.n, self.t_enter, self.t_return = n, t_enter, t_return
        self.counters, self.watch = counters, watch
        self.t_done = None
        self.gc_s = _gc[0]


def _on_gc(phase, info):
    now = time.perf_counter()
    if phase == "start":
        _gc[1] = now
    else:
        _gc[0] += now - _gc[1]


def _watch_steps(ready):
    """Stamp when each step's loss is ready on the device, off the
    calling path (the idiom of ``io.device_feed._watch``): ``ready``
    gives ``(record, loss)`` in call order and ``None`` at its end; the
    loss is dropped as soon as it is stamped, and no other array is
    held."""
    for rec, loss in iter(ready.get, None):
        try:
            loss.block_until_ready()
        except RuntimeError:
            pass  # deleted, donated or failed: the step is over either way
        del loss
        with _stamped:
            rec.gc_s = _gc[0] - rec.gc_s
            rec.t_done = time.perf_counter()
            _stamped.notify_all()
        del rec


def _end_watches():
    for watch in list(_watches):
        watch.close()


class StepWatch:
    """The record of one compiled train step (``parallel.HostStep``):
    :meth:`note` leaves a record of every call in the ring that
    :func:`step_records` and :func:`step_counters` read, and a thread of
    its own, started at the first call, stamps when each call's loss is
    ready.  :meth:`close` (the step's finalizer, and at interpreter
    exit) ends the thread: a daemon thread left in a native wait at
    finalization aborts the process."""

    #: how long a close waits for the thread to take its sentinel
    JOIN_S = 10.0

    def __init__(self):
        self._ready = self._thread = None
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def note(self, n, t_enter, t_return, loss, counters=None):
        """Call ``n`` entered at ``t_enter`` and handed its step to the
        runtime at ``t_return``; ``loss`` is that step's loss array,
        ``counters`` what it counted (``opt_state["_counters"]``)."""
        rec = _Step(n, t_enter, t_return, counters, self)
        _step_counters.append(rec)
        if self._thread is None:
            self._start()
        self._ready.put((rec, loss))

    def _start(self):
        global _watching
        if not _watching:
            # registered after jax's own exit hooks, so it runs first
            atexit.register(_end_watches)
            _watching = True
        self._ready = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=_watch_steps, args=(self._ready,), name="HostStep-done",
            daemon=True)
        self._thread.start()
        _watches.add(self)

    def close(self):
        thread = self._thread
        if thread is None or not thread.is_alive():
            return
        self._ready.put(None)
        if thread is not threading.current_thread():
            thread.join(self.JOIN_S)


class counting:
    """Collects, as ``{name: (how, value)}``, what the blocks of a forward
    pass :func:`count` while it is traced.  The compiled train step opens
    one round its forward pass and hands the totals out through its own
    state (``opt_state["_counters"]``); a stack that rematerialises a
    block opens one inside the rematerialised function and hands its
    totals to the outer one with :func:`count_all`."""

    def __enter__(self):
        self._outer = getattr(_counting, "box", None)
        _counting.box = {}
        return _counting.box

    def __exit__(self, *exc):
        _counting.box = self._outer


def count(name, value, how="sum"):
    """Add ``value`` to the counter ``name`` of the forward pass being
    traced (``how`` ``"sum"``, or ``"max"`` for a high-water mark);
    nothing outside :class:`counting`."""
    box = getattr(_counting, "box", None)
    if box is None:
        return
    if name in box:
        import jax.numpy as jnp

        old = box[name][1]
        value = jnp.maximum(old, value) if how == "max" else old + value
    box[name] = (how, value)


def count_all(counted):
    for name, (how, value) in counted.items():
        count(name, value, how)


def note_step_counters(counters):
    """A step's counters alone, as the arrays the step returned, in a
    record without times (:class:`StepWatch` notes the train step's
    own).  The newest ``_RING_STEPS`` records are kept."""
    _step_counters.append(_Step(counters=counters))


def step_counters(last=None):
    """``{name: float}`` of what the blocks of the newest compiled train
    step counted (a mixture of experts' ``moe_assignments``,
    ``moe_assignments_held``, ``moe_rows_max``, ``moe_dropped``), ``{}``
    before the first; with ``last=n`` the list of the newest ``n`` steps'
    (fewer where fewer ran), oldest first.  Steps of a net that counts
    nothing are not among them.  Waits for those steps."""
    counted = [r.counters for r in list(_step_counters)
               if r.counters is not None]
    steps = [{k: float(v) for k, v in c.items()}
             for c in counted[-(last or 1):]]
    if last is None:
        return steps[-1] if steps else {}
    return steps


def step_records(last=None):
    """The newest compiled train steps' records (all that the ring keeps,
    or the newest ``last``), oldest first, each ``{"n": the how-manieth
    call of its step, "t_enter": when the call began, "t_return": when it
    had handed the step to the runtime, "t_done": when the step's loss
    was ready on the device, "gc_s": seconds of garbage collection
    between t_return and t_done}``, the times on ``time.perf_counter()``
    (the clock of :func:`now_us`).  Waits for the steps it returns."""
    recs = [r for r in list(_step_counters) if r.t_enter is not None]
    recs = recs[max(0, len(recs) - last):] if last is not None else recs
    with _stamped:
        _stamped.wait_for(lambda: all(r.t_done is not None for r in recs))
    return [{"n": r.n, "t_enter": r.t_enter, "t_return": r.t_return,
             "t_done": r.t_done, "gc_s": r.gc_s} for r in recs]


def _slowest_steps(n=5):
    """The ``n`` longest completion intervals among the stamped records,
    as ``(interval, step, host late, device, call, gc)`` in seconds:
    between two calls of one step in a row, the device waited on the
    host for ``host late`` and ran ``device`` on its own."""
    recs = [r for r in list(_step_counters) if r.t_done is not None]
    rows = [(b.t_done - a.t_done, b.n, max(0.0, b.t_return - a.t_done),
             b.t_done - max(a.t_done, b.t_return), b.t_return - b.t_enter,
             b.gc_s)
            for a, b in zip(recs, recs[1:])
            if b.watch is a.watch and b.n == a.n + 1]
    return sorted(rows, key=lambda r: -r[0])[:n]


def _steps_table(rows):
    lines = ["", "Slowest steps (ms)", f"{'Step':>8s}{'Interval':>12s}"
             f"{'Host late':>12s}{'Device':>12s}{'Call':>12s}{'GC':>12s}"]
    lines += [f"{step:>8d}" + "".join(f"{v * 1e3:>12.3f}" for v in (
        interval, late, device, call, gc_s))
        for interval, step, late, device, call, gc_s in rows]
    return "\n".join(lines)


def device_report():
    """What the device did in the last run, if that had
    ``profile_device=True``, read from the ``.xplane.pb`` the run wrote
    (``jax.profiler.ProfileData``), or None where there is none:
    seconds and share by phase, the ten costliest gluon blocks within
    forward and backward, Pallas kernels by their ``name=``, what ran
    under no scope by opcode, and the device's idle seconds by the
    ``mx_*`` host span that covers most of each gap.  The trace is
    read once for each run.

    An operation's scope is read from the traced event's own text
    where that carries ``metadata={op_name=...}`` (a v5e's does not),
    else from the compiled text of the program it belongs to: that of
    every program that noted itself during the run
    (:func:`note_program`).  A fusion counts under the scope of its
    root instruction.
    """
    global _device_read
    if _device_logdir is None or _jax_trace_active:
        return None
    paths = [p for p in glob.glob(
        os.path.join(_device_logdir, "**", "*.xplane.pb"), recursive=True)
        if os.path.getmtime(p) >= _device_run_started - 1.0]
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    if _device_read is None or _device_read[0] != path:
        for key, text in list(_programs.items()):
            if callable(text):
                _programs[key] = text()
        report = _reduce_device(_load_xplane(path),
                                list(_programs.values()))
        if report is not None:
            report["trace"] = path
        _device_read = (path, report)
    return _device_read[1]


def _device_table(rep):
    per = 1e3 / rep["runs"] if rep["runs"] else None
    unit = "ms/run" if per else "ms"
    per = per or 1e3
    lines = [
        "",
        f"Device {rep['device']}: {rep['runs']} program runs, busy "
        f"{rep['busy_s'] * 1e3:.3f} ms of {rep['window_s'] * 1e3:.3f} ms",
        f"{'Phase':<40s}{unit:>14s}{'Share(%)':>12s}"]
    for p in _PHASES:
        v = rep["phases"][p]
        lines.append(f"{p:<40s}{v['seconds'] * per:>14.3f}"
                     f"{v['share'] * 100:>12.2f}")
    for title, rows, label in (
            ("Fusions of a phase that hold another's",
             rep["fused"], lambda r: f"{r['phase']} holds {r['holds']}"),
            ("Block", rep["blocks"],
             lambda r: f"{r['phase']}:{r['block']}"),
            ("Kernel", rep["kernels"], lambda r: r["name"]),
            ("Unscoped opcode", rep["unscoped"], lambda r: r["opcode"]),
            ("Idle under", rep["idle"], lambda r: r["span"])):
        if rows:
            lines.append(f"{title:<40s}{unit:>14s}")
            lines += [f"{label(r):<40.40s}{r['seconds'] * per:>14.4f}"
                      for r in rows]
    return "\n".join(lines)


# ------------------------------------------------------------ user scopes
class Domain:
    """Reference: profiler.py:225 — namespace for user scope objects."""

    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    _cat = "user"

    def __init__(self, domain, name):
        self.name = name
        self.domain = domain
        self._start_ts = None

    def start(self):
        self._start_ts = _now_us()

    def stop(self):
        if self._start_ts is None:
            return
        if is_running():  # user scopes respect the run/pause window too
            dur = _now_us() - self._start_ts
            cat = f"{self._cat}:{self.domain}" if self.domain \
                else self._cat
            _record(self.name, cat, "X", self._start_ts, dur)
        self._start_ts = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass

    def __str__(self):
        return self.name


class Task(_Span):
    """Reference: profiler.py:284."""

    _cat = "task"


class Frame(_Span):
    """Reference: profiler.py:326."""

    _cat = "frame"


class Event(_Span):
    """Reference: profiler.py:368 (domain-less event)."""

    _cat = "event"

    def __init__(self, name):
        super().__init__(None, name)


class Counter:
    """Reference: profiler.py:404 — emits Chrome 'C' counter samples."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        if is_running():
            _record(self.name, f"counter:{self.domain}", "C", _now_us(),
                    args={self.name: value})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self

    def __str__(self):
        return str(self._value)


class Marker:
    """Reference: profiler.py:474 — instant event."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if is_running():
            _record(self.name, f"marker:{self.domain}", "i", _now_us(),
                    args={"scope": scope})


@atexit.register
def _shutdown():
    global _jax_trace_active
    if _jax_trace_active:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            pass
        _jax_trace_active = False
