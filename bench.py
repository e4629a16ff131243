"""Headline benchmark: ResNet-50 training throughput (img/s) on one chip.

Reference baseline (BASELINE.md): 363.69 img/s — MXNet 1.2 ResNet-50
training, batch 128, single V100 (docs perf.md:243-254).  The driver runs
this on the real TPU chip and records the JSON line.

One fused XLA program per step (fwd+bwd+SGD momentum, bf16 activations/
weights, fp32 BatchNorm statistics with a custom-VJP fused backward —
the cuDNN BatchNormBackward analog).  The model is built with
``no_bias=True`` — the reference's own benchmark symbol
(example/image-classification/symbols/resnet.py) sets no_bias=True on
every conv; the gluon-zoo 1x1 biases it omits are mathematically inert
under the following BatchNorm (zero gradient).

MEASUREMENT NOTE: this harness times a ``lax.fori_loop`` of K REAL
train steps (params/opt-state threaded through the carry, so iterations
serialize by construction) as ONE device program with ONE final loss
readback; the marginal per-step cost comes from two K values, which
cancels the dispatch and readback constant exactly once.  What it
reports is therefore device time per step with no host in the loop —
not what a training loop that calls the step from the host sees.  On a
directly attached chip ``jax.block_until_ready`` does wait for the
device, so a host-clock loop around single steps is a valid second
reading (chip_smoke.py prints one); whether the two-K scheme stays is
ROADMAP S1's to judge.

HARNESS PROTOCOL (round 6 — r05's run died silent at rc=124 and cost
the round its headline artifact):

* every phase prints a heartbeat line ``[bench] phase=<name> t=+S.Ss``
  to STDERR (import / device_init / build / autotune / compile / K1 /
  K2 / trials / peak / feed / done), so a hung run shows WHERE it
  hung;
* stdout carries exactly ONE JSON line;
* an internal wall-clock deadline (``--deadline`` / BENCH_DEADLINE_S,
  default 1500 s) degrades instead of dying: the K schedule shrinks,
  partial trials are used, the peak probe is skipped — and the JSON
  gains ``"degraded": true`` plus a ``"reason"``.  Even an exception
  emits the JSON line (value null) before exiting;
* the persistent compilation cache (``JAX_COMPILATION_CACHE_DIR`` if
  set, else the fixed ``<checkout>/.cache/xla`` —
  config.setup_compilation_cache) keeps every compiled program, so a
  recapture of an already-seen program costs a disk read, not an XLA
  compile;
* ``--smoke`` runs the full control flow on CPU with a small net in
  seconds — tier-1 CI exercises every phase so a silent-hang
  regression turns the suite red instead of costing a round.  Without
  ``--smoke`` the run needs a TPU: any other platform, and any phase
  that raised, makes the exit code non-zero;
* ``--conv-ab`` measures the step-level MXNET_CONV_1X1_DOT A/B
  (channel-last 1x1 convs as dot_general) in NHWC, the untried lever
  from VERDICT r05 weak #7;
* the in-step variant autotuner (mxnet_tpu/autotune.py) races
  registered lowerings inside a chained run of the REAL step and
  persists winners in autotune.json; its report lands under
  ``"autotune"`` in the JSON (``--no-autotune`` skips);
* the async device feed A/B (``"device_feed"`` in the JSON) runs real
  steps fed blocking vs through io.DeviceFeedIter and reports the
  per-phase feed/compute overlap;
* the ``collectives`` phase compiles the dp step over a forced
  8-device CPU mesh in a subprocess, sharded
  (``optimizer_sharding="ps"``, the flat-bucketed reduce-scatter +
  shard-owned optimizer of parallel.zero) vs replicated, and reports
  each program's HLO collective counts/bytes under ``"collectives"``
  in the JSON — the launch-count win is measurable without TPUs;
* the ``telemetry`` phase arms a run log (telemetry.RunLog), reports
  real steps + program introspection into it, folds the profiler's op
  events into the aggregate opstats table (count/avg/p99/bytes per
  op), records numerics-monitor ``tensor_stats`` rows, then RE-READS
  its own JSONL — schema verdict, record counts and the step's
  memory/flop/collective report land under ``"telemetry"`` in the
  JSON (the observability layer validating itself every bench run);
* the ``serving`` INFERENCE phase (round 13) stands the continuous-
  batching model server (mxnet_tpu.serving) in front of the net's
  inference forward — microbatch winner-seeded buckets, deadline-
  aware admission — and drives bursty synthetic load: admitted
  p50/p99 latency, shed counts, batch structure and the warm-start
  budget land under ``"serving"`` in the JSON;
* the ``fleet`` INFERENCE phase (round 15) spawns 2 replica server
  PROCESSES behind the fault-tolerant FleetRouter (HTTP front,
  least-queue-depth routing, health probes) under bursty load, then
  rolls a zero-downtime ``.mxje`` model swap across the fleet:
  replicas/requests/shed/failovers/swap_ms/p50/p99/slo land under
  ``"fleet"`` in the JSON;
* the ``freshness`` phase (round 18) runs the supervised online
  learning loop (mxnet_tpu.online.OnlineLoop) — continuously-updating
  trainer, stamped ``.mxje`` exports, zero-downtime rolling swaps
  into a 2-replica fleet — and reports the sample-to-served
  freshness distribution vs ``MXNET_FRESHNESS_SLO_MS``:
  swaps/shed/rollbacks, the served-version monotonicity verdict and
  p50/p99 land under ``"freshness"`` in the JSON;
* the ``quantization`` INFERENCE phase (round 18; fp8 arm round 19)
  runs the quantized pipeline end to end — entropy calibration of a
  trained net, ``quantization.quantize_net`` rewrite, the
  quantized_conv/quantized_fc adoption race (three arms since round
  19; winners persisted in autotune.json), fp32 AND force-pinned int8
  AND force-pinned fp8 ``.mxje`` exports, all served AOT — reporting
  top-1 agreement per quantized arm (accuracy delta vs the fp32 arm),
  p50/p99/throughput per arm and the race verdicts under
  ``"quantization"`` in the JSON; the main step's dtype-ladder race
  carries the fp8 rung (roster ``fp32,bf16,fp8``) and its verdict is
  lifted into the ``"dtype_ladder"`` sub-report;

HARNESS PROTOCOL (round 11 — stall-proofing; r05's stall sat inside an
uninterruptible XLA call where none of the above could run):

* a hang WATCHDOG thread (telemetry.Watchdog; ``--watchdog`` /
  MXNET_WATCHDOG_SEC, bench defaults it ON) is armed BEFORE the first
  device_put/trace and beaten by every heartbeat: when the heartbeat
  goes quiet — even with the main thread blocked in C++ — it appends
  all-thread faulthandler stack dumps to ``<partial>.stacks.txt``,
  flushes the flight recorder with reason ``stall``, emits a
  ``watchdog`` run-log record, and stamps the stall into the partial
  JSON.  It observes; the external kill still executes;
* the PARTIAL headline JSON (``--partial-json`` / BENCH_PARTIAL_JSON,
  default ``BENCH_partial.json`` beside bench.py) is atomically
  rewritten after EVERY phase with ``degraded: true`` + the completed
  phases' results, and removed only after the final stdout emit — so
  an external ``timeout -k`` (or ``kill -9``) can never again leave
  zero artifact; the SIGTERM emitter prints it as the JSON line;
* every ``Deadline``-triggered degradation also logs a ``deadline``
  run-log event with the phase name and remaining budget, so the
  reasons survive in the run log even when the final JSON does not.
* ``--checkpoint PREFIX`` writes timed atomic checkpoints
  (resilience.checkpoint) after the measure and feed phases — write
  cost lands under ``"checkpoint": {"write_s": ...}`` in the JSON
  (smoke mode always exercises the writer); ``--resume-from PREFIX``
  restores params/opt state from a verified checkpoint before
  measuring and records ``"resumed": true``.

Also reported: achieved TFLOP/s from ``compiled.cost_analysis()`` and
MFU relative to the chip's bf16 matmul peak measured in-process by a
4096^3 chained probe (same methodology; measures 195 TF/s on v5e,
consistent with the 197 TF/s spec sheet).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_T0 = time.monotonic()
_EMITTED = False

#: hang watchdog (telemetry.Watchdog), armed in main() before the
#: first device_put/trace; every heartbeat beats it
_WD = [None]

#: partial headline JSON: atomically rewritten after every phase so an
#: external kill — SIGKILL included — always leaves a phase-level
#: artifact on disk.  "blob" holds the last main-thread serialization
#: of the results dict: the watchdog thread stamps stalls onto that
#: frozen snapshot, never onto the live (mutating) dict.
_PARTIAL = {"path": None, "phases": [], "blob": None,
            "lock": threading.Lock(), "extra": {}}


def _heartbeat(phase, **info):
    extra = "".join(f" {k}={v}" for k, v in info.items())
    print(f"[bench] phase={phase} t=+{time.monotonic() - _T0:.1f}s"
          f"{extra}", file=sys.stderr, flush=True)
    wd = _WD[0]
    if wd is not None:
        wd.beat(phase)


def _write_partial(out, phase=None, extra=None):
    """Atomically rewrite the partial headline JSON with everything
    measured so far (``degraded: true`` + completed-phase list).

    The main thread passes the live results dict (serialized HERE, on
    the owning thread, into ``_PARTIAL["blob"]``); the watchdog thread
    passes ``out=None`` and only merges its stall stamp onto that
    frozen snapshot — it must never iterate the live dict the main
    thread is mutating mid-phase."""
    path = _PARTIAL["path"]
    if not path:
        return
    with _PARTIAL["lock"]:
        if phase and phase not in _PARTIAL["phases"]:
            _PARTIAL["phases"].append(phase)
        if extra:
            _PARTIAL["extra"].update(extra)
        if out is not None:
            try:
                _PARTIAL["blob"] = json.dumps(out)
            except (TypeError, ValueError):
                pass  # keep the previous good snapshot
        payload = json.loads(_PARTIAL["blob"]) if _PARTIAL["blob"] \
            else {}
        payload.update(_PARTIAL["extra"])
        payload["degraded"] = True
        payload["partial"] = True
        payload["phases_completed"] = list(_PARTIAL["phases"])
        reason = payload.get("reason")
        kill_note = ("partial artifact: the run was still in flight "
                     "(or killed) before the final emit")
        payload["reason"] = f"{reason}; {kill_note}" if reason \
            else kill_note
        tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass


def _clear_partial():
    path = _PARTIAL["path"]
    if path:
        try:
            os.remove(path)
        except OSError:
            pass


def _emit(payload):
    global _EMITTED
    print(json.dumps(payload), flush=True)
    _EMITTED = True
    # the final JSON made it to stdout: the partial is now redundant
    _clear_partial()


class _Deadline:
    """Internal wall clock: the harness must beat any external kill."""

    def __init__(self, seconds):
        self.end = _T0 + float(seconds)

    def remaining(self):
        return self.end - time.monotonic()

    def exceeded(self, margin=0.0):
        return self.remaining() <= margin

    def note(self, phase):
        """A deadline check just triggered degradation: log a RunLog
        ``deadline`` event with the phase and remaining budget — the
        reasons list in the final JSON is exactly the artifact a hang
        loses, the run log survives."""
        if "mxnet_tpu" not in sys.modules:
            return  # degrading before import: nothing to log into
        try:
            from mxnet_tpu import telemetry as _tm

            _tm.event("deadline", phase=str(phase),
                      remaining_s=round(self.remaining(), 3))
        except Exception:
            pass  # telemetry must never break the degrade path


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _matmul_peak_tflops(m=4096):
    """Measured bf16 matmul roofline of this chip via the device-chained
    timer (benchmark/devtime.py)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmark"))
    import jax.numpy as jnp
    import numpy as onp
    from devtime import device_chain_time

    a = jnp.asarray(onp.random.rand(m, m), jnp.bfloat16)
    dt, _ = device_chain_time(lambda p, q: p @ q, [a, a],
                              target_spread=0.4)
    return 2 * m**3 / dt / 1e12


def _build_net(smoke, layout):
    """The benchmark model: ResNet-50 (reference benchmark symbol), or a
    small conv net in smoke mode that still exercises conv/BN/1x1/dense
    so every harness phase and the conv A/B are executed for real."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    # the accelerator; Context.jax_device resolves it to a CPU device
    # on a host without one, which only --smoke may rely on (main()
    # refuses any platform but tpu otherwise)
    ctx = mx.gpu(0)
    if smoke:
        with nn.default_layout(layout):
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Conv2D(8, 3, padding=1, use_bias=False),
                        nn.BatchNorm(),
                        nn.Activation("relu"),
                        nn.Conv2D(16, 1, use_bias=False),  # 1x1: A/B path
                        nn.BatchNorm(),
                        nn.Activation("relu"),
                        nn.GlobalAvgPool2D(),
                        nn.Dense(10))
        net.initialize(init=mx.init.Xavier(), ctx=ctx)
        shp = (1, 3, 16, 16) if layout == "NCHW" else (1, 16, 16, 3)
        classes = 10
    else:
        net = gluon.model_zoo.vision.resnet50_v1(
            classes=1000, layout=layout, no_bias=True)
        net.initialize(init=mx.init.Xavier(), ctx=ctx)
        shp = (1, 3, 224, 224) if layout == "NCHW" else (1, 224, 224, 3)
        classes = 1000
    net(mx.nd.zeros(shp, ctx=ctx))  # resolve deferred shapes
    return net, classes


def _make_step(net, classes, batch, smoke, layout, autotune=False):
    import numpy as onp

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_train_step

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    side = 16 if smoke else 224
    xshp = (batch, 3, side, side) if layout == "NCHW" \
        else (batch, side, side, 3)
    dt = jnp.float32 if smoke else jnp.bfloat16
    x = jnp.asarray(onp.random.rand(*xshp), dtype=dt)
    y = jnp.asarray(
        onp.random.randint(0, classes, size=(batch,)).astype("float32"))
    key = jax.random.key(0)
    # donate=True (the default): params/opt_state are dead after each
    # call by construction of the fori_loop carry; donation lets XLA
    # update them in place (static_alloc ≡ donate_argnums, SURVEY §7).
    # autotune=True additionally races the registered in-step variants
    # (conv 1x1 dot vs conv emitter, ...) inside a chained run of THIS
    # step on the sample batch; the winner persists in autotune.json
    # and the returned step traces under it (mxnet_tpu/autotune.py).
    step_fn, params, opt_state = make_train_step(
        net, loss_fn, optimizer="sgd", learning_rate=0.1, momentum=0.9,
        donate=True,
        compute_dtype=None if smoke else "bfloat16",
        sample_data=(x, y) if autotune else None,
        autotune=None if autotune else False)
    return step_fn, params, opt_state, x, y, key


def _measure(step_fn, params, opt_state, x, y, key, batch, deadline,
             plans):
    """Two-K-slope measurement with deadline-driven K degradation.

    plans: list of (K1, K2, n_trials), preferred first.  Returns a dict
    with ms_per_step/throughput (or value None if nothing could be
    measured) plus degradation bookkeeping.
    """
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(0,))
    def multi_step(k, p, o):
        def body(i, carry):
            p_, o_, _ = carry
            loss, p2, o2 = step_fn(p_, o_, x, y, key,
                                   (i + 1).astype(jnp.float32))
            return (p2, o2, loss)

        return jax.lax.fori_loop(
            0, k, body, (p, o, jnp.float32(0.0)))[2]

    def run(k):
        t0 = time.perf_counter()
        loss = multi_step(k, params, opt_state)
        _ = float(loss)  # materialize: drains the device pipeline
        return time.perf_counter() - t0

    degraded, reasons = False, []
    k1 = plans[0][0]
    t_first = run(k1)  # compiles the K1 loop program
    _heartbeat("K1", k1=k1, first_run_s=round(t_first, 2))
    t_k1 = run(k1)
    step_est = t_k1 / k1
    compile_est = max(t_first - t_k1, 0.0)
    if deadline.exceeded():
        # no budget left for even the K2 compile: a single-K rate is a
        # biased estimate (constant overhead uncancelled) but beats
        # silence
        deadline.note("measure:single-K")
        return {"ms_per_step": step_est * 1e3,
                "throughput": batch / step_est,
                "k1": k1, "k2": k1, "trials": 0, "degraded": True,
                "reasons": ["deadline: single-K rate, no slope"]}

    # pick the largest plan that fits the remaining budget (2x safety
    # on the estimate: compile of the K2 program + warmups + trials)
    chosen = None
    for (p1, p2, nt) in plans:
        cost = compile_est + step_est * (p2 + (p1 + p2) * nt)
        if deadline.remaining() > 2.0 * cost:
            chosen = (p1, p2, nt)
            break
    if chosen is None:
        chosen = plans[-1]
        degraded = True
        reasons.append("deadline: fell back to smallest K plan")
        deadline.note("measure:k-plan")
    elif chosen != plans[0]:
        degraded = True
        reasons.append(f"deadline: reduced K plan to {chosen}")
        deadline.note("measure:k-plan")
    if chosen[0] != k1:
        run(chosen[0])  # warm the downgraded K1 program too
        t_k1 = run(chosen[0])
    k1, k2, n_trials = chosen

    t_k2_warm = run(k2)  # compiles the K2 loop program
    _heartbeat("K2", k2=k2, first_run_s=round(t_k2_warm, 2))

    trials = []
    for i in range(n_trials):
        if trials and deadline.exceeded():
            degraded = True
            reasons.append(
                f"deadline: stopped after {len(trials)}/{n_trials} "
                "trials")
            deadline.note("measure:trials")
            break
        t1, t2 = run(k1), run(k2)
        trials.append((t2 - t1) / (k2 - k1))
        _heartbeat("trials", done=len(trials), total=n_trials,
                   ms_per_step=round(trials[-1] * 1e3, 2))
    if not trials:
        # nothing fit: one degenerate slope from the warmup runs
        trials = [max(t_k2_warm - t_k1, 1e-9) / (k2 - k1)]
        degraded = True
        reasons.append("deadline: single warmup-slope estimate")
        deadline.note("measure:warmup-slope")
    dt = _median(trials)
    return {"ms_per_step": dt * 1e3, "throughput": batch / dt,
            "k1": k1, "k2": k2, "trials": len(trials),
            "degraded": degraded, "reasons": reasons}


def _measure_feed(step_fn, params, opt_state, x, y, key, smoke,
                  deadline):
    """Feed/compute overlap A/B: N REAL train steps fed (a) blocking —
    per-step host batch assembly + device_put inline in the loop — vs
    (b) through ``DeviceFeedIter`` with assembly + H2D in its producer
    thread.  Returns (report, params, opt_state) — params/opt_state are
    threaded through because the step donates its inputs.

    Host-loop wall timing is acceptable HERE: both arms run the
    identical loop and only their ratio (the overlap) is the result;
    the headline ms/step stays on the chained-K methodology above."""
    import numpy as onp

    import jax
    from mxnet_tpu.config import get_env
    from mxnet_tpu.io.device_feed import DeviceFeedIter

    n = 6 if smoke else 16
    depth = get_env("MXNET_DEVICE_FEED_DEPTH")
    xf = onp.asarray(x).astype("float32")
    yh = onp.asarray(y)
    xdt = onp.asarray(x).dtype

    def assemble(i):
        # representative host tail work (normalize + cast), varied per
        # batch so nothing can be hoisted/cached across iterations
        a = (xf * (1.0 / 255.0) - 0.45 + 1e-6 * i) * (1.0 / 0.225)
        return a.astype(xdt), yh

    def run_blocking(p, o):
        t0 = time.perf_counter()
        loss = None
        for i in range(n):
            xb, yb = assemble(i)
            xb = jax.device_put(xb)
            yb = jax.device_put(yb)
            loss, p, o = step_fn(p, o, xb, yb, key, 1.0)
        _ = float(loss)  # drain
        return time.perf_counter() - t0, p, o

    def run_feed(p, o):
        it = DeviceFeedIter((assemble(i) for i in range(n)),
                            depth=depth)
        t0 = time.perf_counter()
        loss = None
        for xb, yb in it:
            loss, p, o = step_fn(p, o, xb._data, yb._data, key, 1.0)
        _ = float(loss)
        return time.perf_counter() - t0, it.stats(), p, o

    # warm the direct single-step program (the AOT compile above does
    # not populate the jit call cache) — outside both timed arms
    loss, params, opt_state = step_fn(params, opt_state, x, y, key, 1.0)
    _ = float(loss)
    t_block, params, opt_state = run_blocking(params, opt_state)
    t_feed, stats, params, opt_state = run_feed(params, opt_state)
    report = {
        "batches": n,
        "depth": depth,
        "blocking_ms_per_step": round(t_block / n * 1e3, 3),
        "feed_ms_per_step": round(t_feed / n * 1e3, 3),
        "feed_wait_ms_per_step": round(
            stats["consumer_wait_s"] / max(stats["batches"], 1) * 1e3,
            3),
        "producer_busy_ms_per_step": round(
            stats["producer_busy_s"] / max(stats["batches"], 1) * 1e3,
            3),
        "overlap_frac": round(max(0.0, 1.0 - t_feed / t_block), 3)
        if t_block > 0 else None,
    }
    return report, params, opt_state


def _measure_telemetry(step_fn, params, opt_state, x, y, key, smoke,
                       deadline):
    """Telemetry phase: arm a run log, run REAL steps reporting into
    it (program introspection + per-step records on the default
    sampling), fold the profiler's op events into the aggregate
    opstats table, record numerics-monitor tensor_stats rows, then
    RE-READ the JSONL — the dogfood check: the bench validates its own
    run log against the schema and folds the result into the headline
    JSON.  Returns (report, params, opt_state) — threaded because the
    step donates its inputs."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import profiler as prof
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.config import get_env
    from mxnet_tpu.telemetry import numerics as tm_num
    from mxnet_tpu.telemetry import opstats as tm_ops
    from mxnet_tpu.telemetry import schema as tm_schema

    n = 4 if smoke else 8
    batch = int(x.shape[0])
    tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_tm_")
    path = os.path.join(tmpdir, "run.jsonl")
    rl = tm.reset(path)
    p, o = params, opt_state
    opstats_report = None
    numerics_report = None
    started_prof = False
    try:
        try:
            # compile/memory introspection of the measured step
            # program (a persistent-cache disk hit: the program is
            # already built)
            tm.describe_program(step_fn, p, o, x, y, key, 1.0,
                                program="train_step")
            # profiler collection window: step spans mirror onto the
            # telemetry lane AND a few representative eager op
            # dispatches land in the operator lane, so the aggregate
            # opstats fold has both kinds of events to chew on.  An
            # externally armed profiler is left alone — this phase
            # only stops a collection it started itself.
            if not prof.is_running():
                prof.set_config(aggregate_stats=True,
                                profile_imperative=True)
                prof.set_state("run")
                started_prof = True
            for i in range(n):
                if deadline.exceeded(margin=0.0):
                    # the un-killable contract beats completeness:
                    # report however many steps landed before the
                    # budget ran out
                    deadline.note("telemetry:steps")
                    break
                t0 = time.perf_counter()
                loss, p, o = step_fn(p, o, x, y, key, 1.0)
                synced = rl.should_sync(i)
                # sampled sync only: the loss readback (one device
                # sync) happens on sampled steps, like the fit loop
                lv = float(loss) if synced else None
                rl.step(0, i, time.perf_counter() - t0, batch,
                        loss=lv, synced=synced)
            if deadline.exceeded(margin=0.0):
                # budget gone: no eager ops, no opstats fold, and
                # above all no first-time jit of the numerics
                # summarizer — every extra second here eats the
                # external timeout's grace window, the exact rc=124
                # window this phase exists to keep the bench out of
                deadline.note("telemetry:reports")
                opstats_report = "skipped (deadline)"
                numerics_report = "skipped (deadline)"
            else:
                arr = mx.nd.array(onp.ones((64, 64), "float32"))
                for _ in range(3):
                    ((arr * 2.0) + 1.0).asnumpy()
                if started_prof:
                    prof.set_state("stop")
                # the profiler.dumps() analog: per-op count/total/avg/
                # min/max/p99/bytes, as a RunLog record + text table
                rows = tm_ops.record(source="bench", top=32)
                table = tm_ops.dumps(sort_by="total")
                opstats_report = {
                    "ops": len(rows),
                    "table_lines": len(table.splitlines()),
                    "has_p99": all("p99_us" in r
                                   for r in rows.values()),
                    "has_bytes": any(r.get("bytes")
                                     for r in rows.values()),
                }
                # numerics monitor (Monitor 2.0) over the step's named
                # parameter tensors: one sampled tensor_stats record —
                # the in-graph gradient path is exercised by the unit
                # suite; here the bench proves the record pipeline
                named = dict(list(p.items())[:8])
                vecs = tm_num.summarize_named(named)
                nrows, bad = tm_num.emit(rl, 0, vecs, where="param")
                numerics_report = {"tensors": len(nrows),
                                   "nonfinite": bad}
        finally:
            if started_prof and prof.is_running():
                prof.set_state("stop")
            tm.close()  # next telemetry.current() re-resolves env
        with open(path) as f:
            recs, problems = tm_schema.validate_lines(f)
        by_type = {}
        for r in recs:
            by_type[r["type"]] = by_type.get(r["type"], 0) + 1
        prog = next((r for r in recs if r["type"] == "program_report"),
                    None)
        steps = [r for r in recs if r["type"] == "step"]
        return {
            "steps": len(steps),
            "records": by_type,
            "schema_valid": not problems,
            "schema_problems": problems[:5],
            "sample_period": int(get_env("MXNET_TELEMETRY_SAMPLE")),
            "synced_steps": sum(1 for r in steps if r["synced"]),
            "program_report": {k: prog.get(k) for k in
                               ("memory", "flops", "collectives")}
            if prog else None,
            "opstats": opstats_report,
            "tensor_stats": numerics_report,
        }, p, o
    finally:
        # a phase failure lands in main()'s degraded handler — the
        # temp run-log dir must not accumulate across CI runs
        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_data_plane(smoke, deadline):
    """The ``data_plane`` phase (round 17): the multi-worker record
    pipeline fed a shard with SEEDED corruption — one torn frame, one
    unpackable header, one undecodable payload.  Reported: feed
    throughput with ``MXNET_IO_WORKERS=4`` vs the single-producer
    baseline, per-batch p50/p99 latency, consumer feed-wait, and the
    quarantine evidence (skip count == seeded corruption, manifest
    entries) — the epoch must COMPLETE, structurally degraded, never
    dead."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.telemetry.opstats import percentile
    from mxnet_tpu.test_utils import corrupt_rec, write_rec_corpus

    tmpdir = tempfile.mkdtemp(prefix="bench_dataplane_")
    try:
        n = 64 if smoke else 256
        size = 24
        rec = os.path.join(tmpdir, "bench.rec")
        offsets = write_rec_corpus(rec, n=n, size=size, seed=7)
        # seeded corruption, 3 records via the shared recipe: torn
        # frame / unpackable header / undecodable payload
        corrupt_rec(rec, offsets, torn=[n // 4], unpack=[n // 2],
                    decode=[3 * n // 4])

        def run_epochs(workers, epochs=2):
            it = mx.io.ImageRecordIter(
                path_imgrec=rec, data_shape=(3, size, size),
                batch_size=16, std_r=255.0, std_g=255.0, std_b=255.0,
                io_workers=workers, device_feed=False,
                quarantine_manifest=os.path.join(
                    tmpdir, f"q{workers}.json"))
            lat_ms = []
            samples = 0
            t0 = time.perf_counter()
            try:
                for ep in range(epochs):
                    while True:
                        tb = time.perf_counter()
                        try:
                            batch = it.next()
                        except StopIteration:
                            break
                        lat_ms.append(
                            (time.perf_counter() - tb) * 1e3)
                        samples += batch.data[0].shape[0] \
                            - (batch.pad or 0)
                    _heartbeat("data_plane", workers=workers, epoch=ep)
                    if ep + 1 < epochs:
                        it.reset()
                wall = time.perf_counter() - t0
                return {"samples": samples, "wall_s": wall,
                        "lat_ms": lat_ms,
                        "stats": it.data_plane_stats()}
            finally:
                it.close()

        multi = run_epochs(4)
        if deadline.exceeded():
            single = None
            deadline.note("data_plane_single_arm")
        else:
            single = run_epochs(0)
        stats = multi["stats"]
        import json as _json

        with open(stats["manifest"]) as f:
            manifest = _json.load(f)
        report = {
            "records": n, "corrupt": 3, "workers": 4,
            "skipped": stats["skipped"],
            "respawns": stats["respawns"],
            "manifest_entries": len(manifest["entries"]),
            "throughput_img_s": round(
                multi["samples"] / max(multi["wall_s"], 1e-9), 2),
            "p50_batch_ms": round(
                percentile(sorted(multi["lat_ms"]), 0.5), 4),
            "p99_batch_ms": round(
                percentile(sorted(multi["lat_ms"]), 0.99), 4),
            "feed_wait_s": round(sum(multi["lat_ms"]) / 1e3, 4),
        }
        if single is not None:
            report["single_thread_img_s"] = round(
                single["samples"] / max(single["wall_s"], 1e-9), 2)
        else:  # skipped on deadline: say so, never a silent absence
            report["single_thread_img_s"] = None
            report["note"] = "single-thread arm skipped (deadline)"
        return report
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_healing(smoke, deadline):
    """The ``healing`` phase (round 16): the self-healing runtime's
    two headline numbers, measured for real.

    1. **async-checkpoint steal** — the same jitted train-step loop
       runs A/B: plain vs with ``CheckpointManager.save_async``
       snapshots every 4 steps (device→host capture at the step
       boundary, serialization + atomic write on the background
       writer).  Min-of-rounds per arm; the acceptance bar is <5%
       step-time overhead (``overhead_ok``) — what makes a
       batches-fresh recovery point affordable.
    2. **detect-to-resume latency** — a live heartbeat/failure-
       detector drill: a ghost peer's beat goes stale, the detector
       declares it dead (``detect_s``), and the recovery path (load
       the freshest snapshot + reshard verdict + cursor re-slice at
       the surviving world size) completes (``resume_s``).  The sum
       is the operator-facing "how stale is my job after a SIGKILL"
       number the 2-process drill bounds end-to-end.

    ``tools/ckpt_fsck.py`` then walks every version the phase wrote —
    zero torn artifacts is part of the report.
    """
    import shutil
    import tempfile

    import numpy as onp

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import ndarray as mxnd
    from mxnet_tpu.resilience import healing
    from mxnet_tpu.resilience.checkpoint import CheckpointManager
    from mxnet_tpu.resilience.elastic import (reshard_verdict,
                                              reslice_cursor,
                                              topology_block)
    from tools import ckpt_fsck

    tmpdir = tempfile.mkdtemp(prefix="bench_healing_")
    report = {}
    try:
        # ---- arm A/B: plain step loop vs + async snapshots ----
        # production-representative ratio: a full-model snapshot every
        # 16 steps of an ms-scale step (real cadences are seconds to
        # minutes); at toy ratios (256 KB snapshots every 3 ms) the
        # writer thread's CPU/IO visibly contends with the host-backed
        # "device" math and the A/B measures the box, not the design
        dim = 512 if smoke else 1024
        steps = 64
        snap_every = 16
        rounds = 4
        rng = onp.random.RandomState(0)
        w0 = jnp.asarray(rng.randn(dim, dim).astype("float32") * 0.05)
        x = jnp.asarray(rng.randn(dim, dim).astype("float32"))

        @jax.jit
        def step(w, t):
            # a matmul-bound mini-step with an SGD-ish update: enough
            # compute that the snapshot capture cost is measured
            # against real work, not against a no-op
            y = jnp.tanh(x @ w)
            g = x.T @ (y - x) / dim
            return w - 1e-3 * g

        step(w0, 0).block_until_ready()  # compile outside both arms

        snapshots_taken = [0]

        def run_arm(mgr):
            w = w0
            t0 = time.perf_counter()
            for i in range(steps):
                w = step(w, i)
                if mgr is not None and (i + 1) % snap_every == 0:
                    w.block_until_ready()  # a real step boundary
                    mgr.save_async(
                        arg_params={"w": mxnd.NDArray(w)},
                        batch_cursor=i + 1)
                    snapshots_taken[0] += 1
            w.block_until_ready()
            return time.perf_counter() - t0

        ck_prefix = os.path.join(tmpdir, "ab", "ck")
        mgr = CheckpointManager(ck_prefix, keep_n=3)
        # INTERLEAVED rounds (plain, async, plain, async, ...), and
        # the verdict is the best PER-ROUND ratio: each round's two
        # arms run back-to-back under the same box load, so a
        # contention burst cancels out of the ratio instead of
        # landing on whichever arm it happened to hit (min-of-each-
        # arm across rounds could pair a quiet plain round with a
        # loaded async one and report the box, not the design)
        pairs = []
        for _ in range(rounds):
            t_p = run_arm(None)
            t_a = run_arm(mgr)
            mgr.wait_async(timeout=60.0)  # drain BETWEEN rounds: disk
            #   time is the writer thread's, not the step loop's
            pairs.append((t_p, t_a))
        plain, t_best = min(pairs, key=lambda pa: pa[1] / pa[0])
        overhead_pct = (t_best - plain) / plain * 100.0
        mgr.close_async()
        report["overhead"] = {
            "steps": steps, "snapshot_every": snap_every,
            "dim": dim,
            "plain_ms_per_step": round(plain / steps * 1e3, 4),
            "async_ms_per_step": round(t_best / steps * 1e3, 4),
            "overhead_pct": round(overhead_pct, 2),
            "overhead_ok": bool(overhead_pct < 5.0),
            # snapshots the measured arms actually PAID for (versions
            # on disk understate this: keep_n retention prunes)
            "async_versions_written": snapshots_taken[0],
        }

        # ---- detect-to-resume: ghost peer goes stale mid-"run" ----
        hb_dir = os.path.join(tmpdir, "hb")
        # telemetry=False: this ghost is a synthetic measurement rig —
        # its "death" must not count peer_deaths in the headline
        # bench run log
        det = healing.FailureDetector(hb_dir, rank=0, num_ranks=2,
                                      timeout=0.25, telemetry=False)
        healing._write_beat(hb_dir, 0)
        ghost = healing._write_beat(hb_dir, 1)
        import json as _json

        with open(ghost) as f:
            payload = _json.load(f)
        payload["host"] = "bench-ghost"  # foreign host: staleness path
        with open(ghost, "w") as f:
            f.write(_json.dumps(payload))
        assert det.dead_peers() == []  # alive while fresh
        topo2 = topology_block(world_size=2, global_batch=8)
        topo1 = topology_block(world_size=1, global_batch=8)
        old = time.time() - 999.0
        os.utime(ghost, (old, old))
        t0 = time.perf_counter()
        while not det.dead_peers():
            if deadline.exceeded():
                raise RuntimeError("deadline inside detect drill")
            time.sleep(0.005)
        t_detect = time.perf_counter() - t0
        t0 = time.perf_counter()
        st = mgr.load()  # the freshest async snapshot
        verdict = reshard_verdict(topo2, topo1)
        cursor = reslice_cursor(st["batch_cursor"], topo2, topo1)
        onp.asarray(st["arg_params"]["w"].asnumpy())
        t_resume = time.perf_counter() - t0
        report["detect_s"] = round(t_detect, 4)
        report["resume_s"] = round(t_resume, 4)
        report["detect_to_resume_s"] = round(t_detect + t_resume, 4)
        report["reshard_verdict"] = {"reshard": verdict["reshard"],
                                     "old_world": 2, "new_world": 1}
        report["resumed_cursor"] = int(cursor)

        # ---- zero torn artifacts: fsck everything the phase wrote --
        fsck_report = ckpt_fsck.fsck(os.path.join(tmpdir, "ab"),
                                     check_all=True)
        report["fsck_clean"] = bool(fsck_report["clean"])
        report["fsck_versions"] = fsck_report["versions_checked"]
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return report


def _measure_quantization(smoke, deadline):
    """Quantized-inference phase (round 18): the full calibrate ->
    rewrite -> race -> export -> serve chain on a small TRAINED net.

    A prototype-class synthetic task trains a conv net until its logit
    margins dwarf the int8 grid, then: entropy calibration over a held
    corpus, ``quantization.quantize_net`` rewrite, the
    ``quantized_conv``/``quantized_fc`` adoption race (winners persist
    in autotune.json — the per-op, per-shape, per-platform verdict),
    both arms exported through ``deploy.export_model`` (the int8 arm
    force-pinned so the comparison is honest even where the race said
    fp32), and both ``.mxje`` artifacts served AOT through
    ``ModelServer.from_artifact``.  Reports top-1 agreement (the
    accuracy delta vs the fp32 arm) plus p50/p99/throughput per arm
    into the headline JSON.  Round 19 adds the fp8 arm alongside:
    force-pinned fp8 export, its own agreement_top1_fp8 (held to the
    same ≥0.99 benchdiff floor as int8) and served metrics."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autotune, deploy, gluon, nd
    from mxnet_tpu import quantization as quant
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelTrainer
    from mxnet_tpu.serving import ModelServer, ServeRejected
    from mxnet_tpu.telemetry.opstats import percentile

    # the WHOLE phase is seeded: net init (Xavier draws from the
    # global RNGs) plus the synthetic task — an unseeded init made
    # the trained margins, and therefore the int8 agreement, vary
    # run to run
    mx.random.seed(42)
    onp.random.seed(42)
    rng = onp.random.RandomState(42)
    nclass, item = 4, (3, 16, 16)
    protos = rng.rand(nclass, *item).astype("float32")
    train_steps = 60 if smoke else 150
    n_req = 48 if smoke else 192
    batch = 32

    def make_batch(n):
        # noise well inside the prototype separation: the logit
        # margins must dwarf the int8 grid so the agreement verdict
        # measures QUANTIZATION error, not boundary samples
        y = rng.randint(0, nclass, n)
        x = (protos[y]
             + 0.15 * rng.rand(n, *item)).astype("float32")
        return x, y.astype("float32")

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.Activation("relu"),
                nn.MaxPool2D(), nn.Flatten(), nn.Dense(nclass))
    net.initialize(init=mx.init.Xavier())
    net(nd.zeros((1,) + item))
    # the training step shares the main bench step's autotune key
    # (batch 32, fp32, cpu): pin the dtype ladder to fp32 so a cached
    # bf16 winner from the MAIN step's race cannot leak into this
    # phase's training numerics — the phase measures quantization,
    # not the ladder
    with autotune.force(dtype_ladder="fp32"):
        trainer = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer="sgd", learning_rate=0.2)
        for i in range(train_steps):
            xb, yb = make_batch(batch)
            trainer.fit_batch(xb, yb)
            if i % 20 == 0 and deadline.exceeded():
                deadline.note("quantization:train")
                break
        trainer.sync_to_block()
    _heartbeat("quantization", trained=True)

    corpus = [make_batch(batch)[0] for _ in range(4)]
    calib = quant.calibrate(net, corpus, mode="entropy",
                            num_batches=len(corpus))
    qnet = quant.quantize_net(net, calib)
    race = quant.tune_quantized(qnet, corpus[0], iters=4)
    _heartbeat("quantization", raced=sorted(race))

    tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_quant_")
    try:
        p_int8 = os.path.join(tmpdir, "int8.mxje")
        p_fp32 = os.path.join(tmpdir, "fp32.mxje")
        p_fp8 = os.path.join(tmpdir, "fp8.mxje")
        # honest arms: the int8 export force-pins every quantized
        # wrapper on, the fp8 export pins the fp8 program, the fp32
        # export force-pins them all off — the RACE report (above) is
        # where per-op adoption lives
        plats = ("cpu",) if smoke else ("cpu", "tpu")
        with autotune.force(quantized_conv=True, quantized_fc=True):
            deploy.export_model(qnet, corpus[0], p_int8,
                                platforms=plats)
        with autotune.force(quantized_conv=False, quantized_fc=False):
            deploy.export_model(qnet, corpus[0], p_fp32,
                                platforms=plats)
        with autotune.force(quantized_conv="fp8", quantized_fc="fp8"):
            deploy.export_model(qnet, corpus[0], p_fp8,
                                platforms=plats)
        info = deploy.artifact_info(p_int8)
        info_fp8 = deploy.artifact_info(p_fp8)

        # accuracy delta: top-1 agreement of the int8 and fp8 programs
        # vs the fp32 arm over the calibration corpus
        f_int8 = deploy.load_model(p_int8)
        f_fp32 = deploy.load_model(p_fp32)
        f_fp8 = deploy.load_model(p_fp8)
        agree = agree_fp8 = n_total = 0
        for xb in corpus:
            b = f_fp32(xb).asnumpy().argmax(1)
            agree += int((f_int8(xb).asnumpy().argmax(1) == b).sum())
            agree_fp8 += int((f_fp8(xb).asnumpy().argmax(1) == b).sum())
            n_total += len(b)
        agreement = agree / max(n_total, 1)
        agreement_fp8 = agree_fp8 / max(n_total, 1)

        def serve_arm(path):
            srv = ModelServer.from_artifact(
                path, slo_ms=8000.0 if smoke else 2000.0,
                coalesce_ms=1.0)
            srv.start(warm=True)
            lat, shed = [], 0
            t0 = time.perf_counter()
            try:
                sample = corpus[0][0]
                handles = []
                for _ in range(n_req):
                    try:
                        handles.append(srv.submit(sample))
                    except ServeRejected:
                        shed += 1
                for h in handles:
                    try:
                        h.result(timeout=60)
                        lat.append(h.latency_ms)
                    except ServeRejected:
                        shed += 1
            finally:
                wall = time.perf_counter() - t0
                srv.drain(timeout=10.0)
                srv.close()
            lat.sort()
            return {
                "p50_ms": round(percentile(lat, 0.50), 3),
                "p99_ms": round(percentile(lat, 0.99), 3),
                "throughput_req_s": round(len(lat) / wall, 2)
                if wall > 0 else None,
                "completed": len(lat), "shed": shed,
            }

        int8_arm = serve_arm(p_int8)
        if deadline.exceeded():
            deadline.note("quantization:fp8_arm")
            fp8_arm = None
        else:
            fp8_arm = serve_arm(p_fp8)
        if deadline.exceeded():
            deadline.note("quantization:fp32_arm")
            fp32_arm = None
        else:
            fp32_arm = serve_arm(p_fp32)
        speedup = speedup_fp8 = None
        if fp32_arm and int8_arm["p50_ms"] and fp32_arm["p50_ms"]:
            speedup = round(fp32_arm["p50_ms"] / int8_arm["p50_ms"], 3)
        if fp32_arm and fp8_arm and fp8_arm["p50_ms"] \
                and fp32_arm["p50_ms"]:
            speedup_fp8 = round(
                fp32_arm["p50_ms"] / fp8_arm["p50_ms"], 3)
        return {
            "calib_mode": calib.mode,
            "calib_batches": calib.num_batches,
            "layers_quantized": len(
                [w for w in quant.quantized_layers(qnet)
                 if w.variant_op is not None]),
            "train_steps": train_steps,
            "agreement_top1": round(agreement, 4),
            "accuracy_delta": round(1.0 - agreement, 4),
            "agreement_top1_fp8": round(agreement_fp8, 4),
            "accuracy_delta_fp8": round(1.0 - agreement_fp8, 4),
            "autotune": {op: {"winner": r["winner"],
                              "cached": bool(r.get("cached"))}
                         for op, r in race.items()},
            "artifact": {"quantized": info["quantized"],
                         "param_dtypes": info["param_dtypes"]},
            "artifact_fp8": {"quantized": info_fp8["quantized"],
                             "param_dtypes": info_fp8["param_dtypes"]},
            "int8": int8_arm,
            "fp8": fp8_arm,
            "fp32": fp32_arm,
            "speedup_p50": speedup,
            "speedup_p50_fp8": speedup_fp8,
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_serving(net, smoke, deadline):
    """INFERENCE serving phase (round 13): stand the continuous-
    batching model server (mxnet_tpu.serving) in front of the bench
    net's inference forward — seeded by the persisted tune_microbatch
    winners — and drive BURSTY (not steady) synthetic load: two bursts
    each submitting a queue's worth of requests at once, so admission
    control, bucketed coalescing and (under pressure) load shedding
    all execute for real.  Reports admitted-request p50/p99 latency,
    shed/rejection counts, batch/bucket structure and the warm-start
    budget into the headline JSON."""
    import numpy as onp

    from mxnet_tpu.parallel import functionalize
    from mxnet_tpu.serving import ModelServer, ServeRejected
    from mxnet_tpu.telemetry.opstats import percentile

    params, apply_fn = functionalize(net, train=False)
    side = 16 if smoke else 224
    item = (3, side, side)
    max_batch = 8
    n_req = 48 if smoke else 192
    # the SLO gates the REPORT (p99_within_slo), not the harness: a
    # loaded CI box must degrade the verdict, never hang the phase
    slo_ms = 5000.0 if smoke else 1000.0
    ex = onp.random.rand(max_batch, *item).astype("float32")
    srv = ModelServer.from_predictor(
        apply_fn, params, ex, candidates=(1, 2), tune_iters=4,
        slo_ms=slo_ms, coalesce_ms=1.0, name="bench")
    srv.start(warm=True)
    lat, shed, submitted = [], 0, 0
    try:
        sample = ex[0]
        for _burst in range(2):
            if deadline.exceeded():
                deadline.note("serving:burst")
                break
            handles = []
            for _ in range(n_req // 2):
                submitted += 1
                try:
                    handles.append(srv.submit(sample))
                except ServeRejected:
                    shed += 1
            for h in handles:
                try:
                    h.result(timeout=60)
                    lat.append(h.latency_ms)
                except ServeRejected:
                    shed += 1
        st = dict(srv.stats)
        health = srv.health()
        wr = srv.warm_report()
    finally:
        srv.drain(timeout=10.0)
        srv.close()
    lat.sort()
    p99 = percentile(lat, 0.99)
    return {
        # the ACTUAL offered load: a deadline break mid-phase must not
        # overstate it (completed + shed == requests, smoke-asserted)
        "requests": submitted, "admitted": st["admitted"],
        "completed": len(lat), "shed": shed,
        "rejected_by_reason": st["rejected"],
        "batches": st["batches"],
        "mean_batch": round(st["admitted"] / st["batches"], 2)
        if st["batches"] else None,
        "buckets": wr["buckets"],
        "microbatch": list(getattr(srv, "microbatch", (1, False))),
        "p50_ms": round(percentile(lat, 0.50), 3),
        "p99_ms": round(p99, 3),
        "slo_ms": slo_ms,
        "p99_within_slo": bool(lat) and p99 <= slo_ms,
        "warm_start_s": round(wr["warm_start_s"], 4),
        "steady_state_traces": wr["steady_state_traces"],
        "breaker": health["breaker"],
        "breaker_trips": st["breaker_trips"],
    }


def _measure_generate(smoke, deadline):
    """Generative decode INFERENCE phase (round 17): stand the paged-
    KV continuous-batching server (mxnet_tpu.serving.generate) on the
    toy decoder and drive BURSTY load — two bursts of ragged prompts
    submitted at once, so token-budget admission, slot eviction and
    the compile-once decode loop all execute for real.  Reports
    tokens/s, TTFT p50/p99, max sequences in flight, eviction/shed
    counts, the post-warm compile count (the zero-retrace proof) and
    the int8-vs-fp32 capacity ratio from page-pool accounting into
    the headline JSON."""
    import numpy as onp

    from mxnet_tpu.serving import (GenerativeServer, PagedKVPool,
                                   ServeRejected)

    rng = onp.random.default_rng(42)
    vocab, layers, heads, head_dim = 32, 2, 2, 8
    prompt_buckets = (4, 8) if smoke else (4, 8, 16)
    max_new = 6 if smoke else 12
    slots = 4 if smoke else 8
    page_tokens = 4
    pool_budget = 64 * 1024
    n_req = 16 if smoke else 64
    srv = GenerativeServer(
        seed=0, vocab=vocab, layers=layers, heads=heads,
        head_dim=head_dim, prompt_buckets=prompt_buckets,
        max_new=max_new, slots=slots, page_tokens=page_tokens,
        pool_budget=pool_budget, kv_dtype="int8",
        evict_after_ms=25.0, name="bench-generate")
    srv.start(warm=True)
    shed = submitted = 0
    try:
        for _burst in range(2):
            if deadline.exceeded():
                deadline.note("generate:burst")
                break
            handles = []
            for _ in range(n_req // 2):
                submitted += 1
                n = int(rng.integers(1, prompt_buckets[-1] + 1))
                prompt = [int(t) for t in rng.integers(0, vocab, n)]
                try:
                    handles.append(srv.submit(prompt))
                except ServeRejected:
                    shed += 1
            for h in handles:
                try:
                    h.result(timeout=60)
                except ServeRejected:
                    shed += 1
        rep = srv.report()
        st = dict(srv.stats)
        agreement = srv.kv_agreement
    finally:
        srv.drain(timeout=10.0)
        srv.close()
    # the capacity acceptance ratio comes from page-pool ACCOUNTING
    # alone (never wall clock): same byte budget, fp32 vs int8 pages,
    # concurrent sequences of the campaign's full token budget
    tokens_per_seq = prompt_buckets[-1] + max_new
    cap = {}
    for d in ("float32", "int8"):
        pool = PagedKVPool(layers, heads, head_dim,
                           page_tokens=page_tokens,
                           budget_bytes=pool_budget, dtype=d)
        cap[d] = pool.capacity_sequences(tokens_per_seq)
    return {
        # the ACTUAL offered load: a deadline break mid-phase must not
        # overstate it (completed + shed == requests, smoke-asserted)
        "requests": submitted,
        "admitted": st["admitted"],
        "completed": st["completed"],
        "shed": shed,
        "rejected_by_reason": st["rejected"],
        "tokens": rep["tokens"],
        "tokens_s": rep["tokens_s"],
        "ttft_p50_ms": rep["ttft_p50_ms"],
        "ttft_p99_ms": rep["ttft_p99_ms"],
        "max_in_flight": rep["max_in_flight"],
        "evictions": rep["evictions"],
        "pages_in_use": rep["pages_in_use"],
        # campaign stats were reset after warm start: any nonzero here
        # is a retrace of the decode/prefill programs under load
        "compiles_after_warm": st["compiles"],
        "warm_traces": st["warm_traces"],
        "kv_dtype": st["kv_dtype_effective"],
        "kv_agreement": agreement,
        "capacity_fp32_seqs": cap["float32"],
        "capacity_int8_seqs": cap["int8"],
        "capacity_ratio_int8": round(cap["int8"] /
                                     max(cap["float32"], 1), 2),
    }


def _measure_fleet(smoke, deadline):
    """Fleet INFERENCE phase (round 15): stand the replicated serving
    fleet (mxnet_tpu.serving.FleetRouter) — 2 replica server
    PROCESSES behind least-queue-depth routing with health probes —
    and drive bursty load through the HTTP front, then roll a
    zero-downtime ``.mxje`` model swap across the fleet.  Reports
    replicas/requests/shed/failovers/swap_ms/p50/p99/slo into the
    headline JSON.

    The replicas always run ``JAX_PLATFORMS=cpu`` on a compact
    artifact: the phase measures the FLEET machinery (routing,
    failover accounting, rolling-swap cost, drain exits) — the
    chip-level inference latency story belongs to the ``serving``
    phase — and two subprocesses must never contend for the benched
    TPU's exclusive lock."""
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.serving import FleetRouter, ServeRejected
    from mxnet_tpu.telemetry.opstats import percentile

    tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_fleet_")
    slo_ms = 8000.0 if smoke else 4000.0
    n_req = 48 if smoke else 96
    replicas = 2
    try:
        def export(name, seed):
            mx.random.seed(seed)
            net = gluon.nn.Dense(16, in_units=8)
            net.initialize(init=mx.init.Xavier())
            path = os.path.join(tmpdir, name)
            mx.deploy.export_model(net, nd.zeros((4, 8)), path,
                                   platforms=("cpu",))
            return path

        p1 = export("v1.mxje", 11)
        p2 = export("v2.mxje", 12)
        router = FleetRouter.spawn(
            p1, replicas=replicas, slo_ms=slo_ms,
            env={"JAX_PLATFORMS": "cpu"}, coalesce_ms=1.0,
            ready_timeout=min(120.0, max(20.0, deadline.remaining())))
        lat, shed, errors = [], 0, []
        lock = threading.Lock()
        swap = None
        try:
            x = onp.random.rand(8).astype("float32")

            def worker(k):
                nonlocal shed
                for _ in range(k):
                    t0 = time.perf_counter()
                    try:
                        router.submit(x, deadline_ms=slo_ms)
                        with lock:
                            lat.append(
                                (time.perf_counter() - t0) * 1e3)
                    except ServeRejected:
                        with lock:
                            shed += 1
                    except Exception as exc:  # noqa: BLE001
                        # an unexpected failure must stay in the
                        # ledger — a dead worker thread would break
                        # completed + shed + errors == requests and
                        # hide the real error from the report
                        with lock:
                            errors.append(repr(exc))

            for _burst in range(2):
                if deadline.exceeded():
                    deadline.note("fleet:burst")
                    break
                ts = [threading.Thread(target=worker,
                                       args=(n_req // 8,))
                      for _ in range(4)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=120)
                _heartbeat("fleet", completed=len(lat), shed=shed)
            if not deadline.exceeded():
                swap = router.rolling_swap(p2)
            else:
                deadline.note("fleet:swap")
            st = dict(router.stats)
            health = router.health()
        finally:
            rcs = router.close()
        lat.sort()
        p99 = percentile(lat, 0.99) if lat else None
        return {
            "replicas": replicas,
            "replicas_final": health["replicas"],
            "requests": st["requests"], "completed": len(lat),
            "shed": shed, "errors": len(errors),
            "error_sample": errors[:3],
            "failovers": st["failovers"],
            "resizes": st["resizes"],
            "swap_ms": swap["swap_ms"] if swap else None,
            "swap_errors": len(swap["errors"]) if swap else None,
            "p50_ms": round(percentile(lat, 0.50), 3) if lat
            else None,
            "p99_ms": round(p99, 3) if p99 is not None else None,
            "slo_ms": slo_ms,
            "p99_within_slo": bool(lat) and p99 <= slo_ms,
            "drain_rcs": {str(k): v for k, v in rcs.items()},
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_freshness(smoke, deadline):
    """Online-learning freshness phase (round 18): run the supervised
    trainer→export→rolling-swap loop (mxnet_tpu.online.OnlineLoop)
    against a 2-replica CPU fleet and report the sample-to-served
    freshness distribution — how stale the fleet's newest committed
    model is relative to the live stream — against
    ``MXNET_FRESHNESS_SLO_MS``.  swaps/shed/rollbacks/relaunches and
    the served-version monotonicity verdict land in the headline JSON
    next to the p50/p99; the SLO gate judges the fault-free p99 (the
    tainted post-heal samples stay visible, excluded not hidden).

    Like the fleet phase this measures the MACHINERY — export cost,
    swap commit latency, supervisor scheduling — on compact CPU
    artifacts; chip-level inference latency belongs to ``serving``."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.online import OnlineLoop
    from mxnet_tpu.serving import FleetRouter

    tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_fresh_")
    steps = 12 if smoke else 30
    export_every = 4 if smoke else 5
    try:
        mx.random.seed(11)
        net = gluon.nn.Dense(1, in_units=4)
        net.initialize(init=mx.init.Xavier())
        base = os.path.join(tmpdir, "base.mxje")
        mx.deploy.export_model(net, nd.zeros((8, 4)), base,
                               platforms=("cpu",))
        router = FleetRouter.spawn(
            base, replicas=2, env={"JAX_PLATFORMS": "cpu"},
            coalesce_ms=1.0,
            ready_timeout=min(120.0, max(20.0, deadline.remaining())))
        try:
            loop = OnlineLoop(os.path.join(tmpdir, "loop"), router,
                              steps=steps, export_every=export_every,
                              seed=11, pace_s=0.02)
            rep = loop.run(timeout=min(
                300.0, max(60.0, deadline.remaining())))
        finally:
            router.close()
        fr = rep["freshness"]
        _heartbeat("freshness", swaps=rep["swaps"],
                   shed=rep["swaps_shed"])
        return {
            "steps": rep["steps"],
            "exports": rep["exports_seen"],
            "swaps": rep["swaps"],
            "swaps_shed": rep["swaps_shed"],
            "swap_rollbacks": rep["swap_rollbacks"],
            "relaunches": rep["relaunches"],
            "versions_served": rep["served_versions"],
            "monotonic": rep["monotonic"],
            "slo_ms": fr["slo_ms"],
            "violations": fr["violations"],
            "p50_ms": fr["all"]["p50_ms"],
            "p99_ms": fr["all"]["p99_ms"],
            "fault_free_p99_ms": fr["fault_free"]["p99_ms"],
            "p99_within_slo": fr["fault_free"]["within_slo"],
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_trace(smoke, deadline):
    """Distributed-tracing phase (round 20): drive bursty load through
    a 2-replica CPU fleet with ``serve.model:delay`` armed on replica 1,
    runlogs armed per process (router + replicas), then merge the logs
    with ``tools/tracemerge.py`` IN-PROCESS and report the causal
    timeline's vitals into the headline JSON: span count, process
    count, the estimated per-process clock skew, the doctor verdict
    (dominant component + named bottleneck replica) and the
    queue/coalesce/compute attribution of the request p99.

    The phase also measures the tracing overhead ratio — armed-vs-
    unarmed p50 of an in-process ModelServer submit (the PR-5 hot-path
    bound, A/B on the same server config) — which benchdiff gates
    absolutely."""
    import importlib.util
    import shutil
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, telemetry
    from mxnet_tpu.serving import FleetRouter, ModelServer, \
        ServeRejected
    from mxnet_tpu.telemetry.opstats import percentile

    tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_trace_")
    slo_ms = 8000.0
    n_req = 24 if smoke else 96
    delay_s = 0.05
    try:
        # ---- A/B overhead: same in-process server, unarmed vs armed
        def submit_p50(armed):
            if armed:
                telemetry.reset(os.path.join(tmpdir, "ab.jsonl"))
            else:
                telemetry.reset(None)
            srv = ModelServer(lambda xs: xs * 2.0, (8,), max_batch=8,
                              slo_ms=slo_ms, coalesce_ms=0.5,
                              name="ab")
            srv.start()
            try:
                xs = onp.zeros(8, dtype="float32")
                lats = []
                for _ in range(16 if smoke else 64):
                    t0 = time.perf_counter()
                    srv.submit(xs, deadline_ms=slo_ms)
                    lats.append((time.perf_counter() - t0) * 1e3)
            finally:
                srv.close()
                telemetry.reset(None)
            return percentile(sorted(lats), 0.50)

        p50_unarmed = submit_p50(False)
        p50_armed = submit_p50(True)
        overhead = (p50_armed / p50_unarmed) if p50_unarmed else None
        _heartbeat("trace", overhead=round(overhead, 3)
                   if overhead else None)

        # ---- the 2-replica drill: one replica delay-injected
        mx.random.seed(11)
        net = gluon.nn.Dense(16, in_units=8)
        net.initialize(init=mx.init.Xavier())
        artifact = os.path.join(tmpdir, "v1.mxje")
        mx.deploy.export_model(net, nd.zeros((4, 8)), artifact,
                               platforms=("cpu",))
        logdir = os.path.join(tmpdir, "logs")
        os.makedirs(logdir)
        telemetry.reset(os.path.join(logdir, "router.jsonl"))
        completed, shed, errors = [], 0, []
        lock = threading.Lock()
        try:
            router = FleetRouter.spawn(
                artifact, replicas=2, slo_ms=slo_ms,
                env={"JAX_PLATFORMS": "cpu"}, coalesce_ms=1.0,
                runlog_dir=logdir,
                replica_env={1: {"MXNET_FAULT_SPEC":
                                 f"serve.model:delay={delay_s}@1+"}},
                ready_timeout=min(120.0, max(20.0,
                                             deadline.remaining())))
            try:
                x = onp.random.rand(8).astype("float32")

                def worker(k):
                    nonlocal shed
                    for _ in range(k):
                        t0 = time.perf_counter()
                        try:
                            router.submit(x, deadline_ms=slo_ms)
                            with lock:
                                completed.append(
                                    (time.perf_counter() - t0) * 1e3)
                        except ServeRejected:
                            with lock:
                                shed += 1
                        except Exception as exc:  # noqa: BLE001
                            with lock:
                                errors.append(repr(exc))

                for _burst in range(2):
                    if deadline.exceeded():
                        deadline.note("trace:burst")
                        break
                    ts = [threading.Thread(target=worker,
                                           args=(n_req // 8,))
                          for _ in range(4)]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join(timeout=120)
                    _heartbeat("trace", completed=len(completed),
                               shed=shed)
            finally:
                router.close()
        finally:
            telemetry.reset(None)

        # ---- merge + doctor, in-process (the tool is stdlib-only)
        spec = importlib.util.spec_from_file_location(
            "tracemerge", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "tracemerge.py"))
        tm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tm)
        procs = tm.load_runlogs([logdir])
        rep = tm.doctor(procs)
        merged = tm.merge_trace(procs)
        spans = sum(len(p["spans"]) for p in procs)
        p99 = percentile(sorted(completed), 0.99) if completed \
            else None
        return {
            "requests": len(completed) + shed + len(errors),
            "completed": len(completed), "shed": shed,
            "errors": len(errors), "error_sample": errors[:3],
            "p50_ms": round(percentile(sorted(completed), 0.50), 3)
            if completed else None,
            "p99_ms": round(p99, 3) if p99 is not None else None,
            "spans": spans,
            "processes": rep["processes"],
            "traced_requests": rep["requests"],
            "skew_s": rep["skew_s"],
            "components_pct": rep["components_pct"],
            "dominant": rep["dominant"],
            "bottleneck_process": rep["bottleneck_process"],
            "swap_in_progress_requests":
                rep["swap_in_progress_requests"],
            "flow_links": sum(1 for e in merged["traceEvents"]
                              if e.get("ph") == "s"),
            "overhead_ratio": round(overhead, 4)
            if overhead is not None else None,
            "p50_unarmed_ms": round(p50_unarmed, 4),
            "p50_armed_ms": round(p50_armed, 4),
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _ckpt_save(prefix, epoch, params, opt_state):
    """Atomic checkpoint of the trained params/opt state
    (resilience.checkpoint); returns the timed write duration so the
    JSON records checkpoint cost per phase."""
    import pickle

    import numpy as onp

    import jax
    from mxnet_tpu.resilience.checkpoint import CheckpointManager

    arg = {k: onp.asarray(v) for k, v in params.items()}
    states = pickle.dumps(jax.tree_util.tree_map(
        lambda a: onp.asarray(a), opt_state))
    t0 = time.perf_counter()
    CheckpointManager(prefix, keep_n=2).save(
        epoch, arg_params=arg, optimizer_states=states, step=epoch)
    return time.perf_counter() - t0


def _ckpt_resume(prefix, params, opt_state):
    """Restore params/opt state from a checkpoint prefix (the newest
    version that verifies); dtypes follow the live params so a bf16
    run resumes a bf16 run."""
    import pickle

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.resilience.checkpoint import CheckpointManager

    st = CheckpointManager(prefix).load()
    loaded = st["arg_params"]
    params = {k: (jnp.asarray(loaded[k].asnumpy(),
                              getattr(params[k], "dtype", None))
                  if k in loaded else params[k]) for k in params}
    if st["optimizer_states"]:
        opt_state = jax.tree_util.tree_map(
            jnp.asarray, pickle.loads(st["optimizer_states"]))
    # jnp.asarray may alias the host numpy buffers (zero-copy on CPU);
    # the donating step would then free memory it does not own — a
    # jitted identity materializes fresh XLA-owned buffers, same as
    # make_train_step's own donate path
    params = jax.jit(lambda p: p)(params)
    opt_state = jax.jit(lambda s: s)(opt_state)
    return params, opt_state, st["epoch"]


def _collectives_probe(n_devices):
    """Child mode (``--collectives-probe N``): compile the smoke-net dp
    train step over an N-device CPU mesh twice — replicated vs
    ``optimizer_sharding="ps"`` — and print ONE JSON line with each
    program's HLO collective counts/bytes.  Runs in a subprocess
    because the device count must be forced before JAX initializes."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu  # noqa: F401
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import get_mesh, make_train_step
    from mxnet_tpu.parallel.zero import collective_bytes

    net, classes = _build_net(True, "NCHW")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = get_mesh((n_devices,), ("data",),
                    devices=jax.devices()[:n_devices])
    batch = n_devices * 2
    x = jnp.asarray(onp.random.rand(batch, 3, 16, 16).astype("float32"))
    y = jnp.asarray(
        onp.random.randint(0, classes, (batch,)).astype("float32"))
    key = jax.random.key(0)
    out = {"n": n_devices,
           "net": "smoke-conv (structural metric; counts do not depend "
                  "on the net's scale, only its tensor list)"}
    for label, kw in (("replicated", {}),
                      ("sharded", {"optimizer_sharding": "ps"})):
        step, p, s = make_train_step(
            net, loss_fn, optimizer="sgd", learning_rate=0.1,
            momentum=0.9, mesh=mesh, donate=False, autotune=False, **kw)
        acc = collective_bytes(
            step.lower(p, s, x, y, key, 1.0).compile().as_text())
        out[label] = acc
    rep = out["replicated"]["counts"]
    shd = out["sharded"]["counts"]
    out["launches_replicated"] = sum(rep.values())
    out["launches_sharded"] = sum(shd.values())
    # ZeRO-stage block (round 16): stage-1 (state-only sharding, the
    # replicated-param baseline) vs stage-3 (params live as flat bucket
    # shards, forward all-gather prefetch) on the SAME net/mesh under
    # adam — the optimizer whose 2x state makes the per-chip ratio
    # meaningful (analytic floor 3/(N+2) of stage 1's param+state
    # bytes).  Gates ride on three ratios benchdiff trends:
    #   rs_ag_ratio  — measured RS+AG bytes / analytic_exchange_bytes
    #                  minimum for the plan (<= 1.05: no hidden
    #                  gathers, no double exchange)
    #   mem_ratio    — stage-3 per-chip param+opt-state bytes / stage 1
    #                  (<= analytic expectation * 1.15)
    #   step_ratio   — stage-3 timed step / stage 1 (<= 1.10: the
    #                  prefetch overlap pays for resharding)
    from mxnet_tpu.parallel.zero import analytic_exchange_bytes
    zero = {"optimizer": "adam"}
    for zlabel, stg in (("stage1", 1), ("stage3", 3)):
        step, p, s = make_train_step(
            net, loss_fn, optimizer="adam", learning_rate=1e-3,
            mesh=mesh, donate=False, autotune=False,
            optimizer_sharding="ps", zero_stage=stg)
        hlo = step.lower(p, s, x, y, key, 1.0).compile().as_text()
        acc = collective_bytes(hlo)
        per_chip = 0
        for leaf in jax.tree_util.tree_leaves((p, s)):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                per_chip += shards[0].data.nbytes
        jax.block_until_ready(step(p, s, x, y, key, 1.0))  # warm
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            jax.block_until_ready(step(p, s, x, y, key, 1.0))
        ms = (time.perf_counter() - t0) * 1e3 / iters
        arm = {"counts": acc["counts"], "bytes": acc["bytes"],
               "per_chip_param_state_bytes": int(per_chip),
               "step_ms": round(ms, 4)}
        if stg == 3:
            floor = analytic_exchange_bytes(step.zero_plan,
                                            n_devices, 3)
            measured = (acc["bytes"].get("reduce-scatter", 0)
                        + acc["bytes"].get("all-gather", 0))
            analytic = (floor["reduce-scatter"] + floor["all-gather"])
            arm["analytic_rs_ag_bytes"] = int(analytic)
            arm["rs_ag_ratio"] = round(measured / analytic, 4)
        zero[zlabel] = arm
    zero["mem_ratio"] = round(
        zero["stage3"]["per_chip_param_state_bytes"]
        / zero["stage1"]["per_chip_param_state_bytes"], 4)
    # analytic floor for adam on an N-way mesh: stage 1 keeps params
    # replicated (P bytes/chip) + m,v sharded (2P/N); stage 3 shards
    # all three (3P/N) -> ratio 3/(N+2)
    zero["mem_ratio_expected"] = round(
        3.0 / (n_devices + 2.0), 4)
    zero["step_ratio"] = round(
        zero["stage3"]["step_ms"] / zero["stage1"]["step_ms"], 4)
    out["zero"] = zero
    print(json.dumps(out), flush=True)


def _measure_collectives(deadline):
    """The ``collectives`` phase: per-step collective launch counts and
    bytes of the compiled dp step, sharded vs replicated, measured
    WITHOUT TPUs on a forced 8-device CPU mesh (the
    ``_collective_bytes`` methodology the multichip dryrun anchors
    on).  Subprocess because the device count is a pre-init flag."""
    import subprocess
    import sys as _sys

    n = 8
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    # the budget must NEVER exceed the remaining internal deadline —
    # granting a slow box a fixed minimum here would let the subprocess
    # push the run past the external watchdog the deadline pre-empts
    budget = min(600.0, deadline.remaining())
    if budget < 10.0:
        raise RuntimeError(
            "deadline: insufficient budget left for the collectives "
            "probe subprocess")
    proc = subprocess.run(
        [_sys.executable, os.path.abspath(__file__),
         "--collectives-probe", str(n)],
        env=env, capture_output=True, text=True, timeout=budget)
    if proc.returncode != 0:
        raise RuntimeError(
            f"collectives probe rc={proc.returncode}: "
            f"{proc.stderr[-500:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1])


def _fk_chain_time(fn, init, deadline, iters=6):
    """The shared in-step slope timer (autotune.chain_time) with the
    bench's deadline degrade: a bitten budget shortens the slope to 2
    iterations — a degraded slope beats no slope."""
    from mxnet_tpu.autotune import chain_time

    if deadline.exceeded():
        iters = 2
    return chain_time(fn, init, iters=iters)


def _measure_fused_kernels(smoke, deadline):
    """The ``fused_kernels`` phase (round 14): race every new Pallas
    kernel variant in-step through the autotune registry on a
    representative mini-program — the fused-bucket optimizer update
    (``fused_bucket_opt``), flash attention with its block-size and
    padding sub-variants (``flash_attention``), and the three-way
    BN+ReLU+conv1x1 backward (``pallas_bnreluconv``: stock vs fused-
    jnp vs fused-pallas).  Winners persist in autotune.json exactly
    like the main step's conv race; on CPU the kernel arms run in
    interpret mode (they lose, correctly — the phase proves the race
    and the registry, the TPU run proves the speedup)."""
    import numpy as onp

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import autotune as at
    from mxnet_tpu.ops.flash_attention import flash_attention
    from mxnet_tpu.ops.pallas_conv import fused_bn_relu_conv1x1
    from mxnet_tpu.optimizer.optimizer import Adam
    from mxnet_tpu.parallel import zero

    report = {}
    rng = onp.random.RandomState(0)

    # -- fused_bucket_opt: the ZeRO-1 inner update over one flat bucket
    L = 64 * 1024 if smoke else 4 * 1024 * 1024
    w0 = jnp.asarray(rng.randn(L).astype("float32"))
    g0 = jnp.asarray(rng.randn(L).astype("float32") * 1e-3)
    opt = Adam(learning_rate=1e-3, wd=1e-4)
    plan = zero.plan_buckets({"w": w0}, 1, capacity=L + 1)
    bucket = plan[0]

    def bucket_measure(_value):
        m0 = jnp.zeros((L,), jnp.float32)
        v0 = jnp.zeros((L,), jnp.float32)

        def fn(c, i):
            w, m, v = c
            _, uw, (um, uv) = zero.bucket_shard_update(
                bucket, opt, {"w": w}, g0, (m, v),
                (i + 1).astype(jnp.float32), n_shards=1, idx=0,
                axis=None)
            return (uw, um, uv)

        return _fk_chain_time(fn, (w0, m0, v0), deadline)

    winner, info = at.tune("fused_bucket_opt", (L,), "float32",
                           at.VARIANT_OPS["fused_bucket_opt"],
                           bucket_measure)
    report["fused_bucket_opt"] = {"winner": winner, **info}
    if deadline.exceeded():
        deadline.note("fused_kernels:bucket")

    # -- flash_attention: fwd+bwd through the custom vjp; the smoke
    # seq (96) is deliberately NOT tile-aligned so the pallas arm falls
    # back (emitting the attributed event) while pallas_pad races the
    # kernel through the padding shim
    b, h, s, d = (1, 1, 96, 8) if smoke else (2, 8, 512, 64)
    q0 = jnp.asarray(rng.randn(b, h, s, d).astype("float32") * 0.1)
    kk = jnp.asarray(rng.randn(b, h, s, d).astype("float32") * 0.1)
    vv = jnp.asarray(rng.randn(b, h, s, d).astype("float32") * 0.1)

    def attn_measure(_value):
        def loss(q):
            return (flash_attention(q, kk, vv, causal=True)
                    .astype(jnp.float32) ** 2).mean()

        def fn(c, i):
            return c - 0.01 * jax.grad(loss)(c)

        return _fk_chain_time(fn, q0, deadline)

    winner, info = at.tune("flash_attention", q0.shape, "float32",
                           at.VARIANT_OPS["flash_attention"],
                           attn_measure)
    report["flash_attention"] = {"winner": winner, **info}

    # -- pallas_bnreluconv: stock (unfused) vs fused-jnp vs
    # fused-pallas backward over the bottleneck-tail shape
    M, Ci, Co = (512, 8, 16) if smoke else (16384, 256, 64)
    u0 = jnp.asarray(rng.randn(M, 1, 1, Ci).astype("float32"))
    gamma = jnp.asarray(rng.rand(Ci).astype("float32") + 0.5)
    beta = jnp.asarray(rng.randn(Ci).astype("float32") * 0.1)
    wt = jnp.asarray(rng.randn(Co, 1, 1, Ci).astype("float32") * 0.1)

    def brc_measure(value):
        if value == "stock":
            w2 = wt.reshape(Co, Ci).T

            def loss(u):
                # the unfused layer-path math XLA fuses on its own
                u32 = u.astype(jnp.float32).reshape(-1, Ci)
                mean = u32.mean(0)
                var = ((u32 - mean) ** 2).mean(0)
                bnout = ((u32 - mean) * jax.lax.rsqrt(var + 1e-5)
                         * gamma + beta).astype(u.dtype)
                act = jnp.maximum(bnout, 0)
                y = act @ w2
                return (y.astype(jnp.float32) ** 2).mean()
        else:
            def loss(u):
                # fused op; jnp-vs-pallas backward follows the forced
                # variant via _use_pallas at trace time
                y, _, _ = fused_bn_relu_conv1x1(u, gamma, beta, wt)
                return (y.astype(jnp.float32) ** 2).mean()

        def fn(c, i):
            return c - 0.01 * jax.grad(loss)(c)

        return _fk_chain_time(fn, u0, deadline)

    winner, info = at.tune("pallas_bnreluconv", u0.shape, "float32",
                           at.VARIANT_OPS["pallas_bnreluconv"],
                           brc_measure)
    report["pallas_bnreluconv"] = {"winner": winner, **info}
    report["raced"] = sorted(k for k in report if k != "raced")
    return report


def _conv_ab(batch, smoke, deadline):
    """Step-level MXNET_CONV_1X1_DOT A/B in NHWC (the flag only lowers
    CHANNEL-LAST 1x1 convs to dot_general — ops/conv.py:60-83).
    Returns (results, degraded, reasons): a deadline-bitten arm must
    surface as degraded, not as a clean-looking speedup."""
    results, degraded, reasons = {}, False, []
    plans = [(1, 2, 1)] if smoke else [(2, 8, 1)]
    for flag in ("0", "1"):
        arm = "dot" if flag == "1" else "conv"
        if flag == "1" and deadline.exceeded():
            degraded = True
            reasons.append("deadline: conv A/B dot arm skipped")
            deadline.note("conv_ab:dot-arm")
            break
        os.environ["MXNET_CONV_1X1_DOT"] = flag
        try:
            net, classes = _build_net(smoke, "NHWC")
            step = _make_step(net, classes, batch, smoke, "NHWC")
            m = _measure(*step, batch, deadline, plans)
            results[arm] = round(m["throughput"], 2)
            if m["degraded"]:
                degraded = True
                reasons.extend(f"conv A/B {arm}: {r}"
                               for r in m["reasons"])
        finally:
            os.environ.pop("MXNET_CONV_1X1_DOT", None)
    if results.get("conv") and results.get("dot"):
        results["dot_speedup"] = round(
            results["dot"] / results["conv"], 3)
    return results, degraded, reasons


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU smoke: full control flow, tiny net, "
                         "seconds not minutes")
    ap.add_argument("--conv-ab", action="store_true",
                    help="also measure the MXNET_CONV_1X1_DOT step A/B "
                         "(NHWC)")
    ap.add_argument("--no-autotune", action="store_true",
                    help="skip the in-step variant autotuner (winners "
                         "otherwise persist in autotune.json and apply "
                         "to the measured step)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="internal wall-clock budget in seconds "
                         "(BENCH_DEADLINE_S; default 1500, smoke 240)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--checkpoint", default=None,
                    help="atomic-checkpoint the trained params/opt "
                         "state to this prefix after the measure and "
                         "feed phases (write times land under "
                         "'checkpoint' in the JSON); smoke mode "
                         "defaults to a temp prefix so CI exercises "
                         "the writer")
    ap.add_argument("--resume-from", dest="resume_from", default=None,
                    help="restore params/opt state from a checkpoint "
                         "prefix before measuring; the JSON records "
                         "resumed: true")
    ap.add_argument("--watchdog", type=float, default=None,
                    help="hang-watchdog quiet timeout in seconds "
                         "(MXNET_WATCHDOG_SEC; bench defaults it ON: "
                         "60 smoke / 300 full; 0 disables).  On a "
                         "stall it dumps all-thread stacks and stamps "
                         "the partial JSON — it never kills")
    ap.add_argument("--partial-json", dest="partial_json", default=None,
                    help="path of the partial headline JSON, "
                         "atomically rewritten after every phase "
                         "(BENCH_PARTIAL_JSON; default "
                         "BENCH_partial.json beside bench.py; 'none' "
                         "disables).  Removed after the final stdout "
                         "emit")
    ap.add_argument("--collectives-probe", dest="collectives_probe",
                    type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.collectives_probe:
        # child mode for the collectives phase: the parent forced the
        # CPU platform + device count in our env before exec
        return _collectives_probe(args.collectives_probe)

    default_deadline = 240.0 if args.smoke else 1500.0
    deadline_s = args.deadline if args.deadline is not None else float(
        os.environ.get("BENCH_DEADLINE_S", default_deadline))
    deadline = _Deadline(deadline_s)
    batch = args.batch if args.batch is not None else int(
        os.environ.get("BENCH_BATCH", "8" if args.smoke else "128"))
    layout = "NCHW"  # NHWC supported too; identical on this chip (XLA
    #                  assigns physical layouts itself — measured r03/r04)
    baseline = 363.69  # V100 bs128 (BASELINE.md row 1)

    out = {
        "metric": "resnet50_train_throughput",
        "value": None,
        "unit": "img/s/chip",
        "degraded": False,
        "smoke": bool(args.smoke),
        "deadline_s": deadline_s,
    }
    reasons = []
    raised = []

    def phase_raised(what, exc):
        """A phase raised: the run goes on and says so under
        ``reason``; in full mode the exit code says so too."""
        raised.append(what)
        reasons.append(f"{what} failed: {exc!r}")

    # partial headline JSON: armed BEFORE any phase so even an import
    # hang + SIGKILL leaves an artifact saying how far the run got
    partial = args.partial_json or os.environ.get("BENCH_PARTIAL_JSON")
    if partial is None:
        partial = os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "BENCH_partial.json")
    if str(partial).lower() in ("none", "off", ""):
        partial = None
    _PARTIAL["path"] = partial
    _write_partial(out, "start")

    def bail(reason, phase="bail"):
        deadline.note(phase)
        out["degraded"] = True
        out["reason"] = reason
        _emit(out)

    if deadline.exceeded():
        return bail("deadline exceeded before import", "pre-import")

    _heartbeat("import")
    if args.smoke:
        # the smoke drill is a CPU run by definition: force the
        # platform before jax initializes
        os.environ["JAX_PLATFORMS"] = "cpu"
    import mxnet_tpu  # noqa: F401  (registers ops; timed by heartbeat)
    from mxnet_tpu.config import setup_compilation_cache

    if partial is not None:
        # a faultsim `crash` action os._exit()s between its flight dump
        # and any pending partial rewrite — register the partial
        # flusher on the crash path so a faultsim-killed run (the
        # multiprocess resize-drill children included) still leaves a
        # parseable phase-level artifact
        from mxnet_tpu.resilience import faultsim as _fsim

        _fsim.on_crash(lambda: _write_partial(
            None, extra={"fault_crash": True}))

    import jax

    # hang watchdog: armed BEFORE the first device_put/trace — the
    # r05 stall predated phase 1's measurement loop entirely, sitting
    # in device/platform init where no cooperative check runs.  On a
    # stall it stamps the partial JSON (from its own thread) so even
    # a SIGKILL'd run says WHERE it wedged.
    wd_timeout = args.watchdog
    if wd_timeout is None:
        env_wd = os.environ.get("MXNET_WATCHDOG_SEC")
        wd_timeout = float(env_wd) if env_wd else \
            (60.0 if args.smoke else 300.0)
    if wd_timeout > 0:
        from mxnet_tpu.telemetry.watchdog import Watchdog

        stack_path = (f"{partial}.stacks.txt" if partial else None)

        def _on_stall(phase, quiet_s, stacks):
            # out=None: stamp onto the last frozen snapshot — this
            # runs on the watchdog thread while main mutates `out`
            _write_partial(None, extra={
                "stalled": {"phase": phase,
                            "quiet_s": round(quiet_s, 1),
                            "stacks": stacks}})

        _WD[0] = Watchdog(timeout=wd_timeout, stack_path=stack_path,
                          on_stall=_on_stall).arm("import")
        out["watchdog_sec"] = wd_timeout

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = setup_compilation_cache()
    out["compilation_cache"] = cache_dir
    if deadline.exceeded():
        return bail("deadline exceeded during import", "import")
    _write_partial(out, "import")

    _heartbeat("device_init")
    devs = jax.devices()
    _heartbeat("device_init", platform=devs[0].platform, n=len(devs))
    if not args.smoke and devs[0].platform != "tpu":
        # a benchmark number from anything but the chip is not one
        bail(f"no TPU: jax.devices()[0].platform is "
             f"{devs[0].platform!r} (use --smoke for the CPU drill)",
             "device_init")
        sys.exit(2)
    if deadline.exceeded():
        return bail("deadline exceeded during device init",
                    "device_init")
    _write_partial(out, "device_init")

    # the dtype-ladder arms (bf16 round 14, fp8 round 19) race in the
    # main step's autotune when no explicit compute_dtype pins the
    # answer (smoke runs fp32 nets; full mode pins bfloat16, so the
    # ladder race is a smoke/registry proof there).  Opt-in by knob;
    # the bench names the full three-rung roster — fp8 never joins a
    # roster implicitly — but respects a caller's explicit setting.
    os.environ.setdefault("MXNET_DTYPE_LADDER", "fp32,bf16,fp8")

    _heartbeat("build")
    t_build0 = time.monotonic()
    net, classes = _build_net(args.smoke, layout)
    # in-step autotune rides inside make_train_step (skipped when the
    # remaining budget could not absorb the extra variant compiles;
    # a warm autotune.json costs lookups only)
    do_tune = not args.no_autotune and (
        args.smoke or not deadline.exceeded(margin=300.0))
    if do_tune:
        _heartbeat("autotune")
    step_fn, params, opt_state, x, y, key = _make_step(
        net, classes, batch, args.smoke, layout, autotune=do_tune)
    from mxnet_tpu import autotune as _at

    out["autotune"] = _at.last_report() if do_tune else {
        "skipped": "disabled" if args.no_autotune else "deadline"}
    # dtype-ladder sub-report (round 19): which rungs raced and which
    # won, lifted out of the autotune report so benchdiff can gate the
    # fp8 arm's presence without digging through per-op entries
    _lad = out["autotune"].get("dtype_ladder") \
        if isinstance(out["autotune"], dict) else None
    out["dtype_ladder"] = {
        "rungs": list(_at.ladder_rungs()),
        "winner": _lad.get("winner") if _lad else None,
        "cached": bool(_lad.get("cached")) if _lad else None,
    }
    if deadline.exceeded():
        return bail("deadline exceeded during model build", "build")
    _write_partial(out, "build")

    out["resumed"] = False
    if args.resume_from:
        _heartbeat("resume", prefix=args.resume_from)
        params, opt_state, from_epoch = _ckpt_resume(
            args.resume_from, params, opt_state)
        out["resumed"] = True
        out["resumed_from_epoch"] = from_epoch

    _heartbeat("compile")
    # static program cost (flops/bytes) for the MFU report; also
    # populates the persistent cache with the single-step program
    compiled = step_fn.lower(params, opt_state, x, y, key, 1.0).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    step_flops = float(ca.get("flops", 0.0))
    step_bytes = float(ca.get("bytes accessed", 0.0))
    _heartbeat("compile", gflops=round(step_flops / 1e9, 1))
    if deadline.exceeded():
        return bail("deadline exceeded during compile", "compile")
    _write_partial(out, "compile")

    plans = [(1, 3, 2), (1, 2, 1)] if args.smoke else \
        [(3, 33, 3), (2, 13, 2), (1, 4, 1)]
    m = _measure(step_fn, params, opt_state, x, y, key, batch, deadline,
                 plans)
    t_main = time.monotonic() - t_build0  # build+compile+measure cost
    out["degraded"] = m["degraded"]
    reasons.extend(m["reasons"])
    dt = m["ms_per_step"] / 1e3

    # per-phase atomic checkpoint writes (--checkpoint; smoke always):
    # write-time is a first-class cost for elastic training, so it
    # lands in the JSON next to the throughput it taxes
    ckpt_prefix = args.checkpoint
    ckpt_tmpdir = None
    if args.smoke and ckpt_prefix is None:
        import tempfile

        ckpt_tmpdir = tempfile.mkdtemp(prefix="mxnet_tpu_bench_ckpt_")
        ckpt_prefix = os.path.join(ckpt_tmpdir, "bench")
    ckpt_times = {}
    if ckpt_prefix:
        _heartbeat("checkpoint", after="measure")
        try:
            ckpt_times["measure"] = round(
                _ckpt_save(ckpt_prefix, 1, params, opt_state), 4)
        except Exception as exc:  # auxiliary: never kill the run
            ckpt_times["measure"] = None
            out["degraded"] = True
            phase_raised("checkpoint (measure)", exc)

    peak = None  # smoke: no matmul-peak probe on CPU (mfu is null)
    if args.smoke:
        pass
    elif deadline.exceeded(margin=60.0):
        out["degraded"] = True
        reasons.append("deadline: skipped matmul-peak probe")
        deadline.note("peak")
    else:
        _heartbeat("peak")
        peak = _matmul_peak_tflops()

    achieved = step_flops / dt / 1e12
    out.update({
        "value": round(m["throughput"], 2),
        "vs_baseline": round(m["throughput"] / baseline, 3),
        "ms_per_step": round(m["ms_per_step"], 2),
        "achieved_tflops": round(achieved, 1),
        "matmul_peak_tflops": round(peak, 1) if peak else None,
        "mfu": round(achieved / peak, 3) if peak else None,
        "step_gflops": round(step_flops / 1e9, 1),
        "step_gbytes": round(step_bytes / 1e9, 1),
        "k1": m["k1"], "k2": m["k2"], "trials": m["trials"],
        "methodology": "fori_loop-chained K-step programs, two-K slope, "
                       "single loss readback (device time per step, no "
                       "host in the loop); donated params/opt_state, "
                       "persistent compilation cache",
    })
    # the headline number is now measured: the partial artifact carries
    # it from here on, whatever kills the remaining phases
    _write_partial(out, "measure")
    from mxnet_tpu.resilience import faultsim as _fs

    _fs.inject("bench.stall")  # test harness stall point (delay spec
    #                            wedges here with NO heartbeats, so the
    #                            watchdog path is provable end-to-end)

    # per-phase feed/compute overlap (async device feed vs blocking
    # per-step H2D) — the DeviceFeedIter A/B runs REAL steps
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["device_feed"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped device-feed phase")
        deadline.note("feed")
    else:
        _heartbeat("feed")
        try:
            feed_report, params, opt_state = _measure_feed(
                step_fn, params, opt_state, x, y, key, args.smoke,
                deadline)
            out["device_feed"] = feed_report
        except Exception as exc:  # auxiliary metric: never kill the run
            out["device_feed"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("device-feed phase", exc)
    _write_partial(out, "feed")

    if ckpt_prefix:
        _heartbeat("checkpoint", after="feed")
        try:
            ckpt_times["feed"] = round(
                _ckpt_save(ckpt_prefix, 2, params, opt_state), 4)
            from mxnet_tpu.resilience.checkpoint import CheckpointManager

            verified = CheckpointManager(ckpt_prefix).latest_epoch()
            out["checkpoint"] = {"prefix": ckpt_prefix,
                                 "write_s": ckpt_times,
                                 "verified": verified is not None}
        except Exception as exc:
            out["checkpoint"] = {"prefix": ckpt_prefix,
                                 "write_s": ckpt_times,
                                 "error": repr(exc)}
            out["degraded"] = True
            phase_raised("checkpoint (feed)", exc)
        if ckpt_tmpdir:
            # the smoke default wrote to a private tempdir — repeated
            # CI runs must not accumulate checkpoint garbage
            import shutil

            shutil.rmtree(ckpt_tmpdir, ignore_errors=True)

    # collective launch accounting (sharded-server vs replicated dp
    # step on the virtual CPU mesh) — the round-9 structural metric:
    # counts/bytes land in the JSON so a per-tensor-collective
    # regression is visible in the headline artifact, not just in CI
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["collectives"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped collectives phase")
        deadline.note("collectives")
    else:
        _heartbeat("collectives")
        try:
            out["collectives"] = _measure_collectives(deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["collectives"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("collectives phase", exc)
    _write_partial(out, "collectives")

    # fused-kernels phase (round 14): race every new Pallas kernel
    # variant in-step through the autotune registry — the fused-bucket
    # optimizer update, flash attention (block-size + padding-shim
    # sub-variants) and the three-way BN+ReLU+conv backward — winners
    # persisted in autotune.json beside the main step's
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["fused_kernels"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped fused-kernels phase")
        deadline.note("fused_kernels")
    else:
        _heartbeat("fused_kernels")
        try:
            out["fused_kernels"] = _measure_fused_kernels(args.smoke,
                                                          deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["fused_kernels"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("fused-kernels phase", exc)
    _write_partial(out, "fused_kernels")

    # healing phase (round 16): async-checkpoint steal A/B (<5% is
    # the acceptance bar) + the detect-to-resume latency of the peer
    # failure detector — the numbers that price the self-healing loop
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["healing"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped healing phase")
        deadline.note("healing")
    else:
        _heartbeat("healing")
        try:
            out["healing"] = _measure_healing(args.smoke, deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["healing"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("healing phase", exc)
    _write_partial(out, "healing")

    # data-plane phase (round 17): the multi-worker record pipeline
    # under seeded corruption — throughput, skip counts, feed-wait and
    # p99 batch latency land in the headline JSON; the epoch must
    # complete with the corruption QUARANTINED, never dead
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["data_plane"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped data-plane phase")
        deadline.note("data_plane")
    else:
        _heartbeat("data_plane")
        try:
            out["data_plane"] = _measure_data_plane(args.smoke,
                                                    deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["data_plane"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("data-plane phase", exc)
    _write_partial(out, "data_plane")

    # INFERENCE serving phase (round 13): the continuous-batching
    # model server under bursty synthetic load — admitted p50/p99,
    # shed counts and the warm-start budget land in the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["serving"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped serving phase")
        deadline.note("serving")
    else:
        _heartbeat("serving")
        try:
            out["serving"] = _measure_serving(net, args.smoke,
                                              deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["serving"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("serving phase", exc)
    _write_partial(out, "serving")

    # quantization INFERENCE phase (round 18): the calibrate ->
    # rewrite -> race -> export -> AOT-serve chain on a trained net —
    # top-1 agreement (accuracy delta vs the fp32 arm), p50/p99 and
    # throughput per arm, and the persisted adoption winners land in
    # the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["quantization"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped quantization phase")
        deadline.note("quantization")
    else:
        _heartbeat("quantization")
        try:
            out["quantization"] = _measure_quantization(args.smoke,
                                                        deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["quantization"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("quantization phase", exc)
    _write_partial(out, "quantization")

    # generative decode INFERENCE phase (round 17): paged-KV-resident
    # continuous batching under bursty ragged-prompt load — tokens/s,
    # TTFT p50/p99, eviction/shed counts, the zero-retrace proof and
    # the int8 capacity ratio land in the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["generate"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped generate phase")
        deadline.note("generate")
    else:
        _heartbeat("generate")
        try:
            out["generate"] = _measure_generate(args.smoke, deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["generate"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("generate phase", exc)
    _write_partial(out, "generate")

    # fleet INFERENCE phase (round 15): 2 replica serving processes
    # behind the fault-tolerant router — bursty load over HTTP, a
    # rolling model swap, clean drain exits — fleet robustness
    # metrics (p99/shed/failovers/swap_ms) land in the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["fleet"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped fleet phase")
        deadline.note("fleet")
    else:
        _heartbeat("fleet")
        try:
            out["fleet"] = _measure_fleet(args.smoke, deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["fleet"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("fleet phase", exc)
    _write_partial(out, "fleet")

    # online-learning freshness phase (round 18): the supervised
    # trainer→export→rolling-swap loop against a 2-replica fleet —
    # sample-to-served freshness p50/p99 vs MXNET_FRESHNESS_SLO_MS,
    # swap/shed/rollback counts and the served-version monotonicity
    # verdict land in the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["freshness"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped freshness phase")
        deadline.note("freshness")
    else:
        _heartbeat("freshness")
        try:
            out["freshness"] = _measure_freshness(args.smoke, deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["freshness"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("freshness phase", exc)
    _write_partial(out, "freshness")

    # distributed-tracing phase (round 20): per-process runlogs from a
    # 2-replica fleet (one replica delay-injected) merged by
    # tools/tracemerge.py into one causal timeline — span/process
    # counts, clock-skew estimates, the doctor bottleneck verdict and
    # the armed-vs-unarmed overhead ratio land in the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 60.0):
        out["trace"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped trace phase")
        deadline.note("trace")
    else:
        _heartbeat("trace")
        try:
            out["trace"] = _measure_trace(args.smoke, deadline)
        except Exception as exc:  # auxiliary metric: never kill the run
            out["trace"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("trace phase", exc)
    _write_partial(out, "trace")

    # run-telemetry dogfood (round 10): the bench arms a run log,
    # reports its own steps into it, re-reads the JSONL and folds the
    # schema verdict + program introspection into the headline JSON
    if deadline.exceeded(margin=0.0 if args.smoke else 30.0):
        out["telemetry"] = "skipped (deadline)"
        out["degraded"] = True
        reasons.append("deadline: skipped telemetry phase")
        deadline.note("telemetry")
    else:
        _heartbeat("telemetry")
        try:
            tm_report, params, opt_state = _measure_telemetry(
                step_fn, params, opt_state, x, y, key, args.smoke,
                deadline)
            out["telemetry"] = tm_report
        except Exception as exc:  # auxiliary metric: never kill the run
            out["telemetry"] = {"error": repr(exc)}
            out["degraded"] = True
            phase_raised("telemetry phase", exc)
    _write_partial(out, "telemetry")

    if args.conv_ab or args.smoke:
        # the A/B costs roughly two more build+compile+measure passes
        # (NHWC arms, smaller K) — project from the measured main-pass
        # cost with 2.5x headroom so a cold-cache compile can't push
        # the JSON emission past an external kill
        ab_margin = 0.0 if args.smoke else 2.5 * t_main
        if deadline.exceeded(margin=ab_margin):
            out["conv_1x1_ab"] = "skipped (deadline)"
            out["degraded"] = True
            reasons.append("deadline: skipped conv 1x1 A/B")
            deadline.note("conv_ab")
        else:
            _heartbeat("conv_ab")
            ab, ab_deg, ab_reasons = _conv_ab(batch, args.smoke,
                                              deadline)
            out["conv_1x1_ab"] = ab
            if ab_deg:
                out["degraded"] = True
                reasons.extend(ab_reasons)
        _write_partial(out, "conv_ab")

    if reasons:
        out["reason"] = "; ".join(reasons)
    if _WD[0] is not None:
        out["watchdog_stalls"] = _WD[0].stalls
        _WD[0].close()
    _heartbeat("done", img_s=out["value"])
    _emit(out)
    if raised and not args.smoke:
        # on the chip a phase that raised is a failed run: the JSON
        # above says which, the exit code says so.  (--smoke, the CPU
        # drill, keeps "degraded" and exit 0.)
        sys.exit(1)


def _install_sigterm_emitter():
    """Last-resort: `timeout` sends SIGTERM before SIGKILL — emit the
    degraded JSON line on the way down instead of dying silent.  (Only
    fires when the interpreter regains control, so a SIGTERM landing
    inside a native XLA compile still depends on the -k grace period —
    the deadline margins above exist to keep us out of that window;
    the partial JSON on disk survives even the SIGKILL case.)"""
    import signal

    def _on_term(signum, frame):
        if not _EMITTED:
            payload = {"metric": "resnet50_train_throughput",
                       "value": None, "unit": "img/s/chip",
                       "degraded": True,
                       "reason": "terminated externally (SIGTERM)"}
            # everything the completed phases measured rides along:
            # the partial artifact IS the headline now
            try:
                path = _PARTIAL["path"]
                if path and os.path.exists(path):
                    with open(path) as f:
                        partial = json.load(f)
                    partial["reason"] = (
                        "terminated externally (SIGTERM); "
                        + str(partial.get("reason", "")))
                    payload = partial
            except Exception:
                pass
            _emit(payload)
        sys.exit(124)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):
        pass  # non-main thread / unsupported platform


if __name__ == "__main__":
    _install_sigterm_emitter()
    try:
        main()
    except SystemExit:
        raise
    except BaseException as exc:  # noqa: BLE001 — the contract is ONE
        # JSON line on stdout no matter what; a silent rc=124 cost
        # round 5 its headline artifact
        import traceback

        traceback.print_exc()
        if not _EMITTED:
            _emit({"metric": "resnet50_train_throughput", "value": None,
                   "unit": "img/s/chip", "degraded": True,
                   "reason": f"exception: {exc!r}"})
        sys.exit(1)
