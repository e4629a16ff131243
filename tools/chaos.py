#!/usr/bin/env python
"""Seeded chaos campaign: reproducible fault schedules over short
training runs, with the self-healing invariants asserted after every
one.

The reference framework has no fault-injection harness at all; this
repo's ``MXNET_FAULT_SPEC`` registry (PR 8) made single faults
deterministic program points.  The campaign composes them into a
SCHEDULE: ``--seed`` fixes every parameter (which scenario, which hit
count, when the external kill lands), ``--runs`` sets the volume, and
after each run three invariants must hold:

1. **no hangs** — the supervised run exits inside its deadline (a
   wedged survivor or a leaked non-daemon thread is a failure);
2. **no torn artifacts** — ``tools/ckpt_fsck.py --all`` walks every
   checkpoint version written during the run and every one must
   verify (stray ``.tmp.*`` files are allowed: they are the proof a
   mid-write death never reached the real artifact);
3. **healed == uninterrupted** — the run's final parameters (after
   any supervisor relaunch + resume) match the fault-free reference
   run ``allclose(1e-5)``.

Scenarios (round-robin over the schedule):

================  ====================================================
``sigkill``       the campaign SIGKILLs the victim process (pidfile)
                  at a seeded delay — uncooperative death anywhere,
                  mid-step and mid-checkpoint-write included; the
                  healing supervisor relaunches and the resume
                  continues from the newest good version
``sigterm_drain`` a seeded-delay SIGTERM: cooperative drain
                  checkpoint, rc -15, supervisor relaunch, resume
``peer_death``    a ghost peer's heartbeat goes stale mid-run: the
                  failure detector declares it dead, the emergency
                  checkpoint flushes from the freshest snapshot, the
                  survivor heal-exits (rc 83) and the relaunch
                  resumes
``heartbeat_delay``  ``peer.heartbeat:delay=...`` faults stall this
                  rank's own beats — absorbed, the run completes
``ckpt_async_crash``  ``ckpt.async:crash@K``: the process dies
                  mid-payload inside the ASYNC snapshot writer;
                  latest must stay previous-good, fsck clean
``ckpt_write_crash``  same for the synchronous writer (``ckpt.write``)
``collective_delay``  ``dist.collective:delay`` inside the dp(2)
                  sharded exchange — absorbed, the run completes
``record_corrupt``  the training shard is a .rec with 3 seeded-
                  corrupt records (torn frame / unpackable header /
                  undecodable payload) fed through the
                  MXNET_IO_WORKERS=4 pool: every corruption is
                  QUARANTINED (run-log counter evidence), the run
                  completes, and the final params match a
                  single-producer reference over the same corpus —
                  worker count and corruption perturb nothing
``io_worker_kill``  ``io.worker:crash@K`` kills a decode worker
                  thread mid-epoch (the pool's SIGKILL analog): the
                  batch it held is re-dispatched, the pool respawns
                  (run-log counter evidence), params still match the
                  reference
``zero3_peer_death``  the ghost-peer death lands mid-run in a ZeRO
                  STAGE-3 step (params live as flat bucket shards;
                  Module.fit cannot drive it, so the worker runs
                  make_train_step(zero_stage=3) directly on the dp(2)
                  mesh): the survivor flushes an emergency PARAMETER-
                  SHARD checkpoint — host-gathered through
                  stage3_save_params into the legacy named layout,
                  stamped sharding="zero3" + plan fingerprint — heal-
                  exits rc 83, and the relaunch verifies the
                  fingerprint, re-shards via stage3_load_params and
                  finishes shard-exact vs the reference
``decode_fault``  ``serve.decode:raise@K`` kills generative decode
                  steps mid-campaign (round 17): the breaker trips at
                  the consecutive-failure limit, every in-flight
                  sequence is shed ``ServeRejected(model_error)``,
                  EVERY page returns to the pool (the no-leak
                  invariant), and after the fault window drains the
                  SAME server recovers — the final fault-free
                  generation must match the fault-free reference
                  token-for-token
``trainer_death_midstream``  ``online.step:crash@K`` kills the online
                  trainer (round 18) between export boundaries, after
                  at least one stamped artifact was published: the
                  healing supervisor relaunches, the cursor-bearing
                  checkpoint resumes SAMPLE-EXACT (final params match
                  the fault-free reference), every published manifest
                  still points at a live stamp-matching artifact, and
                  the published version sequence stays strictly
                  increasing across the death
``swap_rollback``  a seeded ``serve.model:raise`` window is armed in
                  ONE fleet replica so its post-swap warm probe fails
                  mid-rollout: the swap must abort, roll every
                  already-cut-over replica back, leave the fleet on
                  ONE artifact identity (run-log counter evidence),
                  and the retried swap after the window drains must
                  commit everywhere with the reference's prediction
================  ====================================================

Usage::

    python tools/chaos.py --seed 1234 --runs 20 --out /tmp/chaos
    python tools/chaos.py --seed 7 --runs 7 --epochs 2   # quick

Prints one JSON summary line last; exit 0 iff every invariant held.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

SCENARIOS = ("sigkill", "sigterm_drain", "peer_death",
             "heartbeat_delay", "ckpt_async_crash", "ckpt_write_crash",
             "collective_delay", "record_corrupt", "io_worker_kill",
             "zero3_peer_death", "decode_fault",
             "trainer_death_midstream", "swap_rollback")

#: scenarios that intentionally kill the victim (a relaunch+resume is
#: expected); the others must complete on attempt 0
_LETHAL = {"sigkill", "sigterm_drain", "peer_death",
           "ckpt_async_crash", "ckpt_write_crash", "zero3_peer_death",
           "trainer_death_midstream"}


# ======================================================= worker half
def _build_rec_corpus(path, n=32):
    """A deterministic .rec shard with 3 seeded-bad records (torn
    frame / unpackable header / undecodable payload) via the SHARED
    recipe in ``mxnet_tpu.test_utils``.  Every attempt AND the
    reference build byte-identical corpora, so the surviving stream —
    and therefore the final params — must match regardless of worker
    count or worker faults."""
    from mxnet_tpu.test_utils import corrupt_rec, write_rec_corpus

    offsets = write_rec_corpus(path, n=n, labels=lambda i: i % 4)
    corrupt_rec(path, offsets, torn=[6], unpack=[13], decode=[22])
    return path


def _worker_zero3(args, attempt):
    """The ZeRO stage-3 arm: the live params are flat bucket shards
    (``make_train_step(zero_stage=3)``), which ``Module.fit`` cannot
    drive, so the training loop is explicit.  Attempt 0 arms healing
    against a fake 2-rank world, plants a live ghost beat, backdates
    it at the scheduled step, and the PeerDeadError at the next
    step-boundary poll flushes an emergency PARAMETER-SHARD
    checkpoint (host-gathered via ``stage3_save_params``, stamped
    ``sharding="zero3"``) before heal-exiting rc 83.  The relaunch
    refuses a fingerprint mismatch (``reshard_verdict``), re-shards
    via ``stage3_load_params`` and must finish shard-exact."""
    import pickle

    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import get_mesh, make_train_step
    from mxnet_tpu.resilience import healing
    from mxnet_tpu.resilience.checkpoint import (
        CheckpointManager, stage3_load_params, stage3_save_params)
    from mxnet_tpu.resilience.elastic import (
        host_gather, reshard_verdict, topology_block)

    mx.random.seed(11)
    onp.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    net(mx.nd.zeros((1, 10)))

    mesh = get_mesh((2,), ("data",))
    step, params, opt_state = make_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="adam",
        learning_rate=0.05, mesh=mesh, donate=False, autotune=False,
        optimizer_sharding="ps", zero_stage=3, bucket_bound=200)
    plan = step.zero_plan
    topo = topology_block(mesh=mesh, sharding="zero3", plan=plan,
                          zero_stage=3)

    rng = onp.random.RandomState(7)
    X = rng.randn(64, 10).astype("float32")
    y = (X @ rng.randn(10, 4)).argmax(axis=1).astype("float32")
    batches = [(jnp.asarray(X[o:o + 8]), jnp.asarray(y[o:o + 8]))
               for o in range(0, 64, 8)]
    total = int(args.epochs) * len(batches)
    key = jax.random.key(3)

    def _save(mgr, done):
        # a fresh version id per save (the mid-epoch-drain rule: never
        # rewrite an existing version in place); `step` carries the
        # resume cursor
        ver = (mgr.latest_epoch() or 0) + 1
        mgr.save(ver, arg_params=stage3_save_params(plan, params),
                 optimizer_states=pickle.dumps(jax.tree_util.tree_map(
                     host_gather, opt_state)),
                 step=done, epoch=done, topology=topo)

    start = 0
    mgr = CheckpointManager(args.prefix) if args.prefix else None
    if attempt > 0 and mgr is not None \
            and mgr.latest_epoch() is not None:
        st = mgr.load()
        verdict = reshard_verdict(st["topology"], topo)
        if (st["topology"] or {}).get("sharding") != "zero3" \
                or verdict["reshard"]:
            raise RuntimeError(
                "zero3 resume refused: checkpoint topology "
                f"{st['topology']} does not match the live plan: "
                f"{verdict}")
        params = stage3_load_params(plan, st["arg_params"], mesh=mesh)
        opt_state = jax.tree_util.tree_map(
            jnp.asarray, pickle.loads(st["optimizer_states"]))
        start = int(st["step"])
        telemetry.heal("healed_resume", detail=f"step={start}",
                       attempt=attempt)

    ghost_at = int(os.environ.get("CHAOS_GHOST_AT_BATCH", "0"))
    hb_dir = f"{args.prefix}.hb" if args.prefix else None
    ghost = {"armed": False, "stale": False}

    def _ghost_tick(t):
        # same choreography as the fit-level peer_death scenario: arm
        # + plant a live foreign-host ghost at the first boundary,
        # keep it beating, backdate it past the timeout at the
        # scheduled step
        if not ghost["armed"]:
            ghost["armed"] = True
            healing.arm(hb_dir, rank=0, num_ranks=2, timeout=0.5)
            healing._write_beat(hb_dir, 1)
            _unhost(hb_dir)
        elif not ghost["stale"] and t + 1 >= ghost_at:
            ghost["stale"] = True
            path = healing._hb_path(hb_dir, 1)
            old = time.time() - 999.0
            os.utime(path, (old, old))
        elif not ghost["stale"]:
            healing._write_beat(hb_dir, 1)
            _unhost(hb_dir)

    def _unhost(hb_dir):
        path = healing._hb_path(hb_dir, 1)
        with open(path) as f:
            payload = json.load(f)
        payload["host"] = "chaos-ghost"
        with open(path, "w") as f:
            f.write(json.dumps(payload))

    done = start
    try:
        for t in range(start, total):
            if attempt == 0 and ghost_at > 0 and hb_dir:
                _ghost_tick(t)
            healing.poll(step=t)
            xb, yb = batches[t % len(batches)]
            _, params, opt_state = step(params, opt_state, xb, yb,
                                        key, float(t + 1))
            done = t + 1
            if mgr is not None and done % 5 == 0:
                _save(mgr, done)
    except healing.PeerDeadError as e:
        print(f"chaos-worker: peer death detected ({e}); flushing "
              "parameter shards and healing out", flush=True)
        telemetry.heal("peer_death", detail=str(e))
        if mgr is not None:
            _save(mgr, done)
        healing.heal_exit("peer_death")
    finally:
        healing.disarm()

    import threading

    telemetry.close()
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and not t.daemon
             and t is not threading.main_thread()]
    final = stage3_save_params(plan, params)
    print(json.dumps({
        "final": {k: onp.asarray(v).ravel().tolist()
                  for k, v in sorted(final.items())},
        "threads_ok": not stray, "stray_threads": stray,
        "attempt": attempt}), flush=True)
    return 0


def _worker_generate(args, attempt):
    """The generative-serving arm (round 17, ``decode_fault``): a
    warm-started GenerativeServer takes a burst of prompts while the
    seeded ``serve.decode:raise`` spec kills decode steps — the
    breaker must trip, in-flight sequences must shed
    ``ServeRejected(reason="model_error")`` and EVERY page must return
    to the pool (the no-leak invariant).  Then the faults are
    disarmed and the SAME server must recover: the final fault-free
    generation is the run's ``final`` payload, compared
    token-for-token against the fault-free reference."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.resilience import faultsim
    from mxnet_tpu.serving import GenerativeServer, ServeRejected

    spec = os.environ.get("MXNET_FAULT_SPEC", "")
    # warm start fault-free: the warm-up probe steps the decode
    # program too, and a spec hit-window indexed from process start
    # would land there instead of mid-campaign
    faultsim.reset("")
    srv = GenerativeServer(
        seed=0, vocab=32, prompt_buckets=(4, 8), max_new=6, slots=4,
        page_tokens=4, pool_budget=64 * 1024, kv_dtype="float32",
        breaker_limit=2, name="chaos-generate")
    srv.start(warm=True)
    if attempt == 0 and spec:
        faultsim.reset(spec)  # hit 1 = the campaign's first decode
    prompts = [[(7 * i + j) % srv.vocab for j in range(2 + i % 6)]
               for i in range(6)]
    problems = []
    final = {}
    try:
        # storm phase: the armed fault lands on the decode loop
        reasons = {}
        handles = []
        for p in prompts:
            try:
                handles.append(srv.submit(p))
            except ServeRejected as e:
                reasons[e.reason] = reasons.get(e.reason, 0) + 1
        for h in handles:
            try:
                h.result(timeout=60)
            except ServeRejected as e:
                reasons[e.reason] = reasons.get(e.reason, 0) + 1
        if attempt == 0 and spec:
            if srv.stats["breaker_trips"] < 1:
                problems.append(
                    "breaker never tripped under the armed decode "
                    "fault")
            if reasons.get("model_error", 0) < 1:
                problems.append(
                    "no in-flight sequence was shed ServeRejected"
                    f"(model_error); shed reasons: {reasons}")
        if srv.pool.pages_in_use != 0:
            problems.append(
                f"page leak: {srv.pool.pages_in_use} page(s) still "
                "held after the storm")
        # recovery phase: disarm, the SAME server must serve again
        faultsim.reset("")
        give_up = time.monotonic() + 30.0
        for i, p in enumerate(prompts):
            toks = None
            while toks is None and time.monotonic() < give_up:
                try:
                    toks = srv.submit(p).result(timeout=30)
                except ServeRejected:
                    time.sleep(0.02)  # breaker still re-warming
            if toks is None:
                problems.append(
                    f"no recovery: prompt {i} never served after the "
                    "faults were disarmed")
                break
            final[f"prompt{i}"] = [int(t) for t in toks]
    finally:
        srv.drain(timeout=10.0)
        srv.close()

    import threading

    telemetry.close()
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and not t.daemon
             and t is not threading.main_thread()]
    if problems:
        print("chaos-worker(generate): " + "; ".join(problems),
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"final": final, "threads_ok": not stray,
                      "stray_threads": stray, "attempt": attempt}),
          flush=True)
    return 0


def _worker_online(args, attempt):
    """The online-learning arm (round 18, ``trainer_death_midstream``):
    the :class:`OnlineTrainer` consumes its deterministic replay
    stream, exporting a stamped ``.mxje`` every few steps, while the
    seeded ``online.step:crash`` spec kills the process mid-stream —
    after the first export, before the last.  The healing supervisor
    relaunches; the resume must be SAMPLE-EXACT (final params match
    the fault-free reference bit-for-bit), every published manifest
    must point at a live artifact whose stamp agrees (no torn
    publishes), and the version sequence must stay strictly
    increasing across the death."""
    from mxnet_tpu import deploy, telemetry
    from mxnet_tpu.online import OnlineTrainer
    from mxnet_tpu.resilience import faultsim

    if attempt > 0:
        faultsim.reset("")
    workdir = (f"{args.prefix}.online" if args.prefix
               else tempfile.mkdtemp(prefix="chaos_online_"))
    tr = OnlineTrainer(workdir, steps=12, export_every=4, seed=5)
    if args.pidfile and attempt == 0:
        with open(args.pidfile, "w") as f:
            f.write(str(os.getpid()))
    final = tr.run()  # attempt 0 may os._exit(87) mid-stream here

    problems = []
    versions = []
    for name in sorted(os.listdir(tr.publish_dir)):
        if not (name.startswith("v") and name.endswith(".json")):
            continue
        with open(os.path.join(tr.publish_dir, name)) as f:
            man = json.load(f)
        versions.append(int(man["model_version"]))
        try:
            meta = deploy.read_artifact_meta(man["path"])
        except Exception as e:
            problems.append(f"manifest {name} points at an unreadable "
                            f"artifact: {e}")
            continue
        if int(meta.get("model_version", -1)) != versions[-1]:
            problems.append(
                f"manifest {name} stamp mismatch: artifact says "
                f"{meta.get('model_version')}")
    if not versions:
        problems.append("no artifact was ever published")
    elif versions != sorted(set(versions)):
        problems.append(
            f"published versions not strictly increasing: {versions}")

    import threading

    telemetry.close()
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and not t.daemon
             and t is not threading.main_thread()]
    if problems:
        print("chaos-worker(online): " + "; ".join(problems),
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"final": final["params"],
                      "threads_ok": not stray, "stray_threads": stray,
                      "attempt": attempt}), flush=True)
    return 0


def _worker_swap(args, attempt):
    """The rolling-swap arm (round 18, ``swap_rollback``): a 2-replica
    fleet serves v1 and the seeded ``serve.model:raise`` window is
    armed in ONE replica's env, so its post-swap warm probe fails
    after its sibling already cut over — the rollout must abort, roll
    the cut-over replica back and leave the fleet on ONE artifact
    identity.  Once the window is consumed the retried swap must
    commit v2 everywhere, and the final routed prediction is the
    run's ``final`` payload, compared against the fault-free
    reference (which swaps cleanly first try)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import deploy, gluon, nd, telemetry
    from mxnet_tpu.resilience import faultsim
    from mxnet_tpu.serving import FleetRouter

    spec = os.environ.get("MXNET_FAULT_SPEC", "")
    # the spec targets a REPLICA's probe path, not this client process
    faultsim.reset("")
    workdir = (f"{args.prefix}.swap" if args.prefix
               else tempfile.mkdtemp(prefix="chaos_swap_"))
    os.makedirs(workdir, exist_ok=True)

    def _artifact(version, seed):
        net = gluon.nn.Dense(1, in_units=4,
                             prefix=f"chaos_swap{version}_")
        net.initialize(init=mx.init.Xavier())
        net(nd.zeros((1, 4)))
        rng = onp.random.RandomState(seed)
        net.weight.set_data(nd.array(rng.uniform(
            -0.5, 0.5, size=(1, 4)).astype("float32")))
        net.bias.set_data(nd.zeros((1,)))
        path = os.path.join(workdir, f"model-v{version}.mxje")
        deploy.export_model(net, nd.zeros((8, 4)), path,
                            platforms=("cpu",),
                            extra_meta={"model_version": version})
        return path

    v1, v2 = _artifact(1, 31), _artifact(2, 32)
    replica_env = ({1: {"MXNET_FAULT_SPEC": spec}}
                   if attempt == 0 and spec else None)
    problems = []
    final = {}
    router = FleetRouter.spawn(v1, replicas=2,
                               env={"JAX_PLATFORMS": "cpu"},
                               coalesce_ms=1.0,
                               replica_env=replica_env or {})
    try:
        first = router.rolling_swap(v2, probe_timeout=60.0)
        if replica_env:
            if first["committed"]:
                problems.append(
                    "armed probe fault but the rollout committed")
            elif not first["consistent"]:
                problems.append(
                    "fleet straddles two identities after rollback: "
                    f"{first['identities']}")
            elif set(first["identities"].values()) != {v1}:
                problems.append(
                    "rollback left the fleet off the previous "
                    f"artifact: {first['identities']}")
        res = first
        give_up = time.monotonic() + 30.0
        while not res["committed"] and time.monotonic() < give_up:
            time.sleep(0.1)
            res = router.rolling_swap(v2, probe_timeout=60.0)
        if not res["committed"]:
            problems.append(
                f"retried swap never committed: {res['errors']}")
        elif not res["consistent"] \
                or set(res["identities"].values()) != {v2}:
            problems.append(
                f"post-retry identities inconsistent: "
                f"{res['identities']}")
        out = router.submit(onp.ones((4,), dtype="float32"),
                            deadline_ms=10000)
        final = {"probe": onp.asarray(out, dtype="float64")
                 .ravel().tolist()}
    finally:
        router.close()

    import threading

    telemetry.close()
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and not t.daemon
             and t is not threading.main_thread()]
    if problems:
        print("chaos-worker(swap): " + "; ".join(problems),
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"final": final, "threads_ok": not stray,
                      "stray_threads": stray, "attempt": attempt}),
          flush=True)
    return 0


def _worker(args):
    """One training run (the supervised command): attempt 0 arms the
    scenario's faults and may die; relaunch attempts scrub the faults
    and resume from the newest good checkpoint.  Deterministic model,
    data and seeds — every attempt and the reference consume the same
    stream."""
    attempt = int(os.environ.get("MXNET_HEAL_ATTEMPT", "0"))
    if args.prefix:
        os.environ["MXNET_RUNLOG"] = \
            f"{args.prefix}.runlog.a{attempt}.jsonl"
    if attempt > 0:
        os.environ.pop("MXNET_FAULT_SPEC", None)
        os.environ.pop("CHAOS_GHOST_AT_BATCH", None)
    if args.ctx == "zero3":
        return _worker_zero3(args, attempt)
    if args.ctx == "generate":
        return _worker_generate(args, attempt)
    if args.ctx == "online":
        return _worker_online(args, attempt)
    if args.ctx == "online_swap":
        return _worker_swap(args, attempt)

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.resilience import faultsim, healing
    from mxnet_tpu.resilience.checkpoint import CheckpointManager

    if attempt > 0:
        faultsim.reset("")

    mx.random.seed(11)
    onp.random.seed(11)
    if args.ctx == "rec":
        # the data-plane scenarios: train straight from a .rec shard
        # with seeded-corrupt records through the record pipeline
        rec_dir = tempfile.mkdtemp(prefix="chaos_rec_")
        rec_path = _build_rec_corpus(os.path.join(rec_dir, "train.rec"))
        it = mx.io.ImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 16, 16),
            batch_size=8, std_r=255.0, std_g=255.0, std_b=255.0)
        top = sym.Flatten(sym.Variable("data"))
    else:
        rng = onp.random.RandomState(7)
        X = rng.randn(64, 10).astype("float32")
        y = (X @ rng.randn(10, 4)).argmax(axis=1).astype("float32")
        it = mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False)
        top = sym.Variable("data")

    fc1 = sym.FullyConnected(top, num_hidden=16, name="fc1")
    act = sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = sym.FullyConnected(act, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(fc2, sym.Variable("softmax_label"),
                            name="softmax")

    if args.ctx == "dp2":
        context = [mx.gpu(i) for i in range(2)]
        kvstore = "dist_sync"
    else:
        context = mx.cpu()
        kvstore = "local"
    mod = mx.mod.Module(net, context=context)

    resume_from = None
    if attempt > 0 and args.prefix \
            and CheckpointManager(args.prefix).latest_epoch() \
            is not None:
        resume_from = args.prefix

    # ghost-peer injection (the peer_death scenario): at batch 1 arm
    # healing against a fake 2-rank world and plant a LIVE ghost beat;
    # at the scheduled batch, backdate it past MXNET_PEER_TIMEOUT_SEC
    # — the next step-boundary poll must declare the peer dead
    ghost_at = int(os.environ.get("CHAOS_GHOST_AT_BATCH", "0"))
    callbacks = []
    if attempt == 0 and os.environ.get("CHAOS_SELF_HEAL") \
            and args.prefix:
        # a 1-rank healing world: no peers to lose, but the heartbeat
        # thread runs for real — the peer.heartbeat delay faults land
        # on live beats and must be absorbed, not fatal
        healing.arm(f"{args.prefix}.hb", rank=0, num_ranks=1)

    # the external-kill scenarios need the kill to land MID-fit, not
    # mid-import: the pidfile (the campaign's kill trigger) is written
    # at the FIRST batch boundary, and CHAOS_PACE_S stretches the fit
    # so the seeded delay window stays inside it
    pace = float(os.environ.get("CHAOS_PACE_S", "0") or 0)
    pid_done = [attempt != 0 or not args.pidfile]

    def _pace(param):
        if not pid_done[0]:
            pid_done[0] = True
            with open(args.pidfile, "w") as f:
                f.write(str(os.getpid()))
        if pace:
            time.sleep(pace)

    callbacks.append(_pace)
    if attempt == 0 and ghost_at > 0 and args.prefix:
        hb_dir = f"{args.prefix}.hb"
        state = {"armed": False, "stale": False}

        def _ghost(param):
            if not state["armed"]:
                state["armed"] = True
                healing.arm(hb_dir, rank=0, num_ranks=2, timeout=0.5)
                healing._write_beat(hb_dir, 1)
                _unhost_ghost(hb_dir)
            elif not state["stale"] and param.nbatch + 1 >= ghost_at:
                state["stale"] = True
                path = healing._hb_path(hb_dir, 1)
                old = time.time() - 999.0
                os.utime(path, (old, old))
            elif not state["stale"]:
                healing._write_beat(hb_dir, 1)
                _unhost_ghost(hb_dir)

        def _unhost_ghost(hb_dir):
            # a foreign-host ghost: the detector must use staleness,
            # not the same-host pid probe (the recorded pid is ours)
            path = healing._hb_path(hb_dir, 1)
            with open(path) as f:
                payload = json.load(f)
            payload["host"] = "chaos-ghost"
            with open(path, "w") as f:
                f.write(json.dumps(payload))

        callbacks.append(_ghost)

    try:
        mod.fit(it, num_epoch=args.epochs,
                kvstore=kvstore, optimizer="adam",
                optimizer_params=(("learning_rate", 0.05),),
                initializer=mx.init.Xavier(),
                checkpoint=args.prefix or None,
                resume_from=resume_from,
                batch_end_callback=callbacks or None)
    except healing.PeerDeadError as e:
        print(f"chaos-worker: peer death detected ({e}); healing out",
              flush=True)
        healing.heal_exit("peer_death")
    finally:
        healing.disarm()
        if args.ctx == "rec":
            import shutil

            it.close()
            shutil.rmtree(rec_dir, ignore_errors=True)

    import threading

    from mxnet_tpu import telemetry

    telemetry.close()  # flush run_end + final counters
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and not t.daemon
             and t is not threading.main_thread()]
    arg_p, _ = mod.get_params()
    print(json.dumps({
        "final": {k: v.asnumpy().ravel().tolist()
                  for k, v in sorted(arg_p.items())},
        "threads_ok": not stray, "stray_threads": stray,
        "attempt": attempt}), flush=True)
    return 0


# ===================================================== campaign half
def _schedule(seed, runs, scenarios):
    """The seeded, reproducible fault schedule: same seed = same
    scenario order, hit counts and kill delays, run for run."""
    rng = random.Random(int(seed))
    plan = []
    for i in range(int(runs)):
        scen = scenarios[i % len(scenarios)]
        entry = {"run": i, "scenario": scen}
        if scen == "sigkill":
            entry["kill_delay_s"] = round(rng.uniform(0.2, 2.0), 3)
            entry["signal"] = int(signal.SIGKILL)
        elif scen == "sigterm_drain":
            entry["kill_delay_s"] = round(rng.uniform(0.2, 2.0), 3)
            entry["signal"] = int(signal.SIGTERM)
        elif scen == "peer_death":
            entry["ghost_at_batch"] = rng.randint(2, 6)
        elif scen == "zero3_peer_death":
            entry["ghost_at_batch"] = rng.randint(2, 6)
        elif scen == "heartbeat_delay":
            entry["self_heal"] = 1
            # window pinned to start at hit 1: inline beats are
            # rate-limited, so a short run may only beat a few times
            entry["fault_spec"] = (
                f"peer.heartbeat:delay="
                f"{round(rng.uniform(0.1, 0.4), 2)}"
                f"@1-{rng.randint(4, 8)}")
        elif scen == "ckpt_async_crash":
            entry["fault_spec"] = \
                f"ckpt.async:crash@{rng.randint(2, 8)}"
        elif scen == "ckpt_write_crash":
            entry["fault_spec"] = \
                f"ckpt.write:crash@{rng.randint(2, 6)}"
        elif scen == "collective_delay":
            entry["fault_spec"] = (
                f"dist.collective:delay="
                f"{round(rng.uniform(0.05, 0.3), 2)}"
                f"@{rng.randint(1, 6)}")
        elif scen == "record_corrupt":
            entry["io_workers"] = 4  # corruption IS the fault
        elif scen == "io_worker_kill":
            entry["io_workers"] = 4
            entry["fault_spec"] = \
                f"io.worker:crash@{rng.randint(2, 6)}"
        elif scen == "decode_fault":
            # the worker re-arms AFTER its warm start, so hit 1 is
            # the campaign's first decode step; breaker_limit is 2
            start = rng.randint(1, 3)
            entry["fault_spec"] = \
                f"serve.decode:raise@{start}-{start + 1}"
        elif scen == "trainer_death_midstream":
            # the online worker exports every 4 of 12 steps: a crash
            # in hits 5..11 always lands AFTER the first publish and
            # BEFORE the final export
            entry["fault_spec"] = \
                f"online.step:crash@{rng.randint(5, 11)}"
        elif scen == "swap_rollback":
            # armed in ONE replica's env; hit 1 is its post-swap warm
            # probe and the server retries FaultInjected 3x per
            # batch, so the window must span all 3 attempts — hits
            # past it stay clean for the retried swap
            entry["fault_spec"] = \
                f"serve.model:raise@1-{rng.randint(3, 4)}"
        plan.append(entry)
    return plan


def _worker_env(base, entry, prefix):
    env = dict(base)
    env.pop("MXNET_FAULT_SPEC", None)
    env.pop("CHAOS_GHOST_AT_BATCH", None)
    if entry.get("fault_spec"):
        env["MXNET_FAULT_SPEC"] = entry["fault_spec"]
    env.pop("CHAOS_SELF_HEAL", None)
    if entry.get("ghost_at_batch"):
        env["CHAOS_GHOST_AT_BATCH"] = str(entry["ghost_at_batch"])
        env["MXNET_PEER_TIMEOUT_SEC"] = "0.5"
    if entry.get("self_heal"):
        env["CHAOS_SELF_HEAL"] = "1"
    if entry.get("io_workers"):
        env["MXNET_IO_WORKERS"] = str(entry["io_workers"])
    if "kill_delay_s" in entry:
        # stretch the fit past the kill window so the seeded delay
        # lands mid-run (mid-step, mid-epoch-boundary, mid-ckpt-write)
        env["CHAOS_PACE_S"] = "0.15"
    env["MXNET_SNAPSHOT_EVERY"] = "3"
    return env


def _kill_when_ready(pidfile, delay, sig, result, deadline=60.0):
    """The external executioner: wait for the victim's pidfile, sleep
    the SEEDED delay, deliver the signal.  A victim that already
    finished is left in peace.  ``result['delivered']`` records
    whether the signal actually landed — the campaign's fault count
    must not claim kills that out-raced the run."""
    t0 = time.monotonic()
    while not os.path.exists(pidfile):
        if time.monotonic() - t0 > deadline:
            return
        time.sleep(0.05)
    try:
        with open(pidfile) as f:
            pid = int(f.read().strip())
    except (OSError, ValueError):
        return
    time.sleep(delay)
    try:
        os.kill(pid, sig)
        result["delivered"] = True
    except (ProcessLookupError, PermissionError):
        pass  # already gone: the schedule out-raced the run


def _ctx_for(entry):
    if entry["scenario"] == "collective_delay":
        return "dp2"
    if entry["scenario"] in ("record_corrupt", "io_worker_kill"):
        return "rec"  # reference: same corrupt corpus, 0 workers
    if entry["scenario"] == "zero3_peer_death":
        return "zero3"  # reference: same loop, no ghost, no faults
    if entry["scenario"] == "decode_fault":
        return "generate"  # reference: same campaign, no faults
    if entry["scenario"] == "trainer_death_midstream":
        return "online"  # reference: same stream, no crash
    if entry["scenario"] == "swap_rollback":
        return "online_swap"  # reference: clean first-try swap
    return "cpu"


def _run_reference(ctx, outdir, env):
    ref_prefix = os.path.join(outdir, f"reference-{ctx}")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--ctx", ctx, "--epochs", str(env["_CHAOS_EPOCHS"])],
        env={k: v for k, v in env.items() if not k.startswith("_")},
        capture_output=True, text=True, timeout=240)
    if r.returncode != 0:
        raise RuntimeError(
            f"reference run ({ctx}) failed rc={r.returncode}:\n"
            + r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    with open(ref_prefix + ".json", "w") as f:
        f.write(json.dumps(out["final"]))
    return out["final"]


def campaign(args):
    import threading

    import numpy as onp

    outdir = args.out or tempfile.mkdtemp(prefix="mxnet_tpu_chaos_")
    os.makedirs(outdir, exist_ok=True)
    scenarios = tuple(args.scenarios.split(",")) if args.scenarios \
        else SCENARIOS
    unknown = set(scenarios) - set(SCENARIOS)
    if unknown:
        raise SystemExit(f"unknown scenario(s) {sorted(unknown)}; "
                         f"known: {list(SCENARIOS)}")
    plan = _schedule(args.seed, args.runs, scenarios)

    env = dict(os.environ)
    # scrub operator-level state that would poison the campaign: an
    # armed fault spec must not fire in the fault-free REFERENCE arm
    # (workers re-arm per scenario), a parent run log must not absorb
    # every child's telemetry (workers set their own per attempt),
    # and ambient healing must not arm where a scenario did not ask
    for k in ("MXNET_FAULT_SPEC", "MXNET_RUNLOG",
              "MXNET_METRICS_TEXTFILE", "MXNET_HEARTBEAT_DIR",
              "MXNET_SNAPSHOT_EVERY", "CHAOS_GHOST_AT_BATCH",
              "CHAOS_SELF_HEAL", "CHAOS_PACE_S", "MXNET_HEAL_ATTEMPT",
              "MXNET_IO_WORKERS"):
        env.pop(k, None)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = \
            flags + " --xla_force_host_platform_device_count=2"
    # the compile cache is placed from outside or by the one rule in
    # config.setup_compilation_cache — never under a per-run directory
    # (the path is part of the cache key: a cache that moves never hits)
    # the in-step autotuner races numerically-inequivalent variants
    # (jnp vs pallas adam differ by ulps): pin it off so every arm of
    # every run compiles the identical program
    env["MXNET_AUTOTUNE"] = "0"
    env["_CHAOS_EPOCHS"] = str(args.epochs)

    print(f"chaos: seed={args.seed} runs={len(plan)} "
          f"scenarios={list(scenarios)} out={outdir}", flush=True)
    references = {}
    failures = []
    results = []
    faults_injected = 0
    from tools import ckpt_fsck

    for entry in plan:
        i = entry["run"]
        scen = entry["scenario"]
        ctx = _ctx_for(entry)
        if ctx not in references:
            references[ctx] = _run_reference(ctx, outdir, env)
        rundir = os.path.join(outdir, f"run{i:02d}")
        os.makedirs(rundir, exist_ok=True)
        prefix = os.path.join(rundir, "ck")
        pidfile = os.path.join(rundir, "victim.pid")
        run_env = _worker_env(env, entry, prefix)
        run_env = {k: v for k, v in run_env.items()
                   if not k.startswith("_")}
        cmd = [sys.executable, "-m", "mxnet_tpu.resilience.healing",
               "--relaunch", "--max-relaunch", "2", "--",
               sys.executable, os.path.abspath(__file__), "--worker",
               "--prefix", prefix, "--ctx", ctx,
               "--epochs", str(args.epochs), "--pidfile", pidfile]
        killer = None
        kill_result = {"delivered": False}
        if "kill_delay_s" in entry:
            killer = threading.Thread(
                target=_kill_when_ready,
                args=(pidfile, entry["kill_delay_s"],
                      entry["signal"], kill_result),
                daemon=True)
            killer.start()
        t0 = time.monotonic()
        problems = []
        try:
            r = subprocess.run(cmd, env=run_env, capture_output=True,
                               text=True, timeout=args.run_timeout)
        except subprocess.TimeoutExpired:
            problems.append(
                f"HANG: run exceeded {args.run_timeout}s")
            r = None
        wall = round(time.monotonic() - t0, 2)
        if killer is not None:
            killer.join(timeout=10)
        final = None
        if r is not None:
            if r.returncode != 0:
                problems.append(
                    f"supervised run exited rc={r.returncode}: "
                    + (r.stdout + r.stderr)[-800:])
            else:
                try:
                    last = [ln for ln in r.stdout.splitlines()
                            if ln.strip().startswith("{")][-1]
                    out = json.loads(last)
                    final = out["final"]
                    if not out.get("threads_ok", False):
                        problems.append(
                            "hung threads after fit: "
                            f"{out.get('stray_threads')}")
                except (IndexError, ValueError, KeyError) as e:
                    problems.append(
                        f"no final-params JSON from worker ({e}); "
                        f"tail: {r.stdout[-500:]}")
        # invariant 2: every artifact the run left behind verifies
        fsck_report = ckpt_fsck.fsck(rundir, check_all=True)
        if not fsck_report["clean"]:
            problems.append("torn artifacts: "
                            + "; ".join(fsck_report["problems"]))
        # deterministic-death scenarios MUST have died and relaunched
        # (a per-attempt run log proves the supervisor respawned);
        # peer_death additionally must show the heal chain in the
        # victim's log: a declared death and an emergency/fallback
        # checkpoint before the heal_exit
        relaunched = os.path.exists(f"{prefix}.runlog.a1.jsonl")
        if scen in ("peer_death", "zero3_peer_death",
                    "ckpt_async_crash", "ckpt_write_crash",
                    "trainer_death_midstream") and not relaunched:
            problems.append(
                "scenario guarantees a death but no relaunch run log "
                "exists — the fault never fired")
        if scen in ("peer_death", "zero3_peer_death") and relaunched:
            heals = []
            try:
                with open(f"{prefix}.runlog.a0.jsonl") as f:
                    heals = [json.loads(ln) for ln in f
                             if '"type": "heal"' in ln
                             or '"type":"heal"' in ln]
            except OSError:
                pass
            actions = {h.get("action") for h in heals}
            if "peer_death" not in actions:
                problems.append(
                    "victim run log carries no heal/peer_death "
                    f"record (heal actions: {sorted(actions)})")
        # invariant 3: healed == uninterrupted
        if final is not None:
            ref = references[ctx]
            for k in ref:
                if not onp.allclose(onp.asarray(final[k]),
                                    onp.asarray(ref[k]),
                                    rtol=1e-5, atol=1e-7):
                    problems.append(
                        f"final params diverge from reference at {k}")
                    break
        # HONEST fault accounting: count a fault only when it provably
        # landed — a delivered external signal, a relaunch forced by a
        # deterministic crash, or fault-counter evidence in the
        # victim's run log (the delay scenarios complete cleanly, so
        # their run_end counters survive).  A scheduled-but-undelivered
        # fault is a PROBLEM for the deterministic scenarios and a
        # benign miss for the timing-raced kills.
        fault_landed = False
        if "kill_delay_s" in entry:
            fault_landed = kill_result["delivered"] or relaunched
        elif scen in ("peer_death", "zero3_peer_death",
                      "ckpt_async_crash", "ckpt_write_crash",
                      "trainer_death_midstream"):
            fault_landed = relaunched
        elif scen in ("record_corrupt", "io_worker_kill"):
            # data-plane evidence: the victim's run_end counters must
            # show the quarantine (record_corrupt) or the worker
            # respawn (io_worker_kill) actually happened
            key = ("data_records_skipped" if scen == "record_corrupt"
                   else "io_worker_respawns")
            counters = {}
            try:
                with open(f"{prefix}.runlog.a0.jsonl") as f:
                    ends = [json.loads(ln) for ln in f
                            if '"type": "run_end"' in ln
                            or '"type":"run_end"' in ln]
                if ends:
                    counters = ends[-1].get("counters", {})
            except OSError:
                pass
            fault_landed = counters.get(key, 0) >= 1
            if not fault_landed:
                problems.append(
                    f"{scen}: run_end counter {key} shows zero — the "
                    "data-plane fault never landed")
            elif scen == "record_corrupt" \
                    and counters.get("data_records_skipped", 0) != 3:
                problems.append(
                    "record_corrupt: expected exactly 3 quarantined "
                    f"records, counters say "
                    f"{counters.get('data_records_skipped')}")
        elif scen == "swap_rollback":
            # the rollout runs IN the victim process: its run_end
            # counters must show the aborted+rolled-back swap
            counters = {}
            try:
                with open(f"{prefix}.runlog.a0.jsonl") as f:
                    ends = [json.loads(ln) for ln in f
                            if '"type": "run_end"' in ln
                            or '"type":"run_end"' in ln]
                if ends:
                    counters = ends[-1].get("counters", {})
            except OSError:
                pass
            fault_landed = \
                counters.get("fleet_swap_rollbacks", 0) >= 1
            if not fault_landed:
                problems.append(
                    "swap_rollback: run_end counter "
                    "fleet_swap_rollbacks shows zero — the probe "
                    "fault never forced a rollback")
        else:  # delay scenarios: the armed spec's hits are in the log
            try:
                with open(f"{prefix}.runlog.a0.jsonl") as f:
                    ends = [json.loads(ln) for ln in f
                            if '"type": "run_end"' in ln
                            or '"type":"run_end"' in ln]
                fault_landed = bool(ends) and \
                    ends[-1]["counters"].get("faults", 0) >= 1
            except OSError:
                fault_landed = False
            if not fault_landed:
                problems.append(
                    "delay fault spec armed but the victim run log "
                    "shows zero injected faults")
        if fault_landed:
            faults_injected += 1
        row = {"run": i, "scenario": scen, "wall_s": wall,
               "ok": not problems, "problems": problems,
               "relaunched": relaunched,
               "fault_landed": fault_landed,
               "schedule": {k: v for k, v in entry.items()
                            if k not in ("run", "scenario")}}
        results.append(row)
        status = "ok" if not problems else "FAIL"
        print(f"chaos run {i:02d} [{scen}] {status} ({wall}s)"
              + ("" if not problems else f" — {problems[0][:160]}"),
              flush=True)
        if problems:
            failures.append(row)
        elif not args.keep:
            import shutil

            shutil.rmtree(rundir, ignore_errors=True)

    fault_shortfall = faults_injected < int(args.min_faults)
    summary = {
        "seed": int(args.seed), "runs": len(plan),
        "scenarios": sorted(set(e["scenario"] for e in plan)),
        "faults_injected": faults_injected,
        "min_faults": int(args.min_faults),
        "failures": len(failures),
        "ok": not failures and not fault_shortfall,
        "out": outdir,
        "failed_runs": [f["run"] for f in failures],
    }
    if fault_shortfall:
        summary["fault_shortfall"] = (
            f"only {faults_injected} faults provably landed, "
            f"--min-faults wanted {args.min_faults}")
    with open(os.path.join(outdir, "chaos_summary.json"), "w") as f:
        f.write(json.dumps({"summary": summary, "results": results},
                           indent=1))
    print(json.dumps(summary), flush=True)
    return 0 if summary["ok"] else 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="chaos", description="seeded chaos campaign over the "
        "self-healing training runtime")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--scenarios", default=None,
                    help="comma list (default: all "
                    f"{len(SCENARIOS)})")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="campaign directory (default: a tempdir)")
    ap.add_argument("--run-timeout", type=float, default=180.0)
    ap.add_argument("--min-faults", type=int, default=0,
                    help="fail the campaign (exit 1) unless at least "
                    "this many faults PROVABLY landed — the CI gate's "
                    "enforcement of its >=N-faults claim")
    ap.add_argument("--keep", action="store_true",
                    help="keep per-run artifacts of passing runs")
    # worker half (the supervised command)
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--prefix", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--ctx", default="cpu", help=argparse.SUPPRESS)
    ap.add_argument("--pidfile", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.worker:
        return _worker(args)
    return campaign(args)


if __name__ == "__main__":
    sys.exit(main())
