"""Elastic serving fleet tests (round 15).

The contract under test, end to end:

* **HBM-budgeted multi-model residency** — a ``.mxje`` artifact is
  admitted only when its ``describe_program()`` reserved bytes fit the
  per-host budget next to the residents; refusal is a structured
  ``ServeRejected(reason='hbm_budget')``, never an OOM mid-batch.
* **Zero-downtime model swap** — the next artifact loads beside the
  live one, warm-probes, cuts over between batches; a failed probe
  rolls back with the old model still serving.
* **The HTTP front** maps the submit/deadline/breaker core onto the
  wire: every response is the model output or the same structured
  rejection reason the in-process API raises.
* **The router**: least-queue-depth across replicas, per-replica
  health probes, structured failover inside the original deadline,
  queue-depth-EWMA autoscaling riding the round-12
  reshard-not-restart resize.
* **THE fleet drill** (tier-1, subprocess like test_elastic.py):
  bursty load across 2 replica processes stays p99-within-SLO through
  (a) one replica hard-killed mid-burst (``fleet.replica`` crash
  fault) with in-flight work retried on its sibling inside the
  deadline, (b) a queue-depth-driven scale-up resize, and (c) a
  rolling ``.mxje`` swap — zero requests silently hung, retrace
  counter 0 on the new artifact.
* (slow) scale-down drains without shedding; a mid-swap replica crash
  (``fleet.swap`` crash fault) leaves the rest of the fleet upgraded
  and serving.
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, nd  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.resilience import faultsim  # noqa: E402
from mxnet_tpu.serving import (  # noqa: E402
    FleetRouter,
    ModelHost,
    ModelServer,
    ServeFrontend,
    ServeRejected,
    artifact_reserved_bytes,
)
from mxnet_tpu.serving.frontend import http_call  # noqa: E402
from mxnet_tpu.telemetry.opstats import percentile  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    faultsim.reset("")
    yield
    faultsim.reset("")


def _export(tmp_path, name, batch=4, nan=False, seed=None):
    """One Dense(5, in=3) inference artifact; ``nan=True`` bakes
    non-finite weights in (the swap warm probe must catch it)."""
    if seed is not None:
        mx.random.seed(seed)
    net = gluon.nn.Dense(5, in_units=3)
    net.initialize(init=mx.init.Xavier())
    net(nd.zeros((1, 3)))  # resolve shapes so set_data sees them
    if nan:
        w = net.weight.data()
        net.weight.set_data(nd.full(w.shape, float("nan")))
    path = os.path.join(str(tmp_path), f"{name}.mxje")
    mx.deploy.export_model(net, nd.zeros((batch, 3)), path,
                           platforms=("cpu",))
    return path, net


def _np_model(delay=0.0):
    def model(xb):
        if delay:
            time.sleep(delay)
        return xb * 2.0 + 1.0

    return model


# ------------------------------------------------------- fault registry
def test_fleet_fault_points_registered():
    pts = faultsim.points()
    assert {"fleet.route", "fleet.replica", "fleet.swap"} <= set(pts)
    # a spec arming them parses (the registry contract: a typo'd
    # drill fails loudly, a registered point arms cleanly)
    faultsim.reset("fleet.route:raise@999;fleet.swap:delay=0.1@999")
    faultsim.reset("")


# ----------------------------------------------------------- HBM budget
def test_hbm_budget_admits_within_and_rejects_past(tmp_path):
    p1, _ = _export(tmp_path, "m1")
    p2, _ = _export(tmp_path, "m2")
    reserved, _exp = artifact_reserved_bytes(p1)
    assert reserved > 0
    # budget fits ONE model (1.5x its reservation), not two
    budget_mb = (reserved * 1.5) / (1 << 20)
    host = ModelHost(hbm_budget_mb=budget_mb,
                     server_kw={"slo_ms": 30000})
    try:
        host.load("m1", p1)
        res = host.residency()
        assert res["models"]["m1"]["reserved_bytes"] == reserved
        assert res["used_bytes"] == reserved
        with pytest.raises(ServeRejected) as ei:
            host.load("m2", p2)
        assert ei.value.reason == "hbm_budget"
        assert "budget" in str(ei.value)
        assert host.stats["hbm_rejected"] == 1
        # freeing the resident admits the second model
        host.unload("m1")
        host.load("m2", p2)
        assert sorted(host.residency()["models"]) == ["m2"]
        # duplicate residency is loud, not a silent replace
        with pytest.raises(MXNetError, match="already resident"):
            host.load("m2", p2)
    finally:
        host.close_all()


def test_multi_model_residency_routes_by_name(tmp_path):
    p1, net1 = _export(tmp_path, "a", seed=1)
    p2, net2 = _export(tmp_path, "b", seed=2)
    host = ModelHost(server_kw={"slo_ms": 30000, "coalesce_ms": 0.5})
    try:
        host.load("a", p1)
        host.load("b", p2)
        x = onp.random.rand(3).astype("float32")
        out_a = host.submit(x, model="a").result(timeout=30)
        out_b = host.submit(x, model="b").result(timeout=30)
        onp.testing.assert_allclose(
            out_a, net1(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        onp.testing.assert_allclose(
            out_b, net2(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        # ambiguous default on a 2-model host is loud
        with pytest.raises(MXNetError, match="explicit model"):
            host.submit(x)
    finally:
        host.close_all()


# ------------------------------------------------------------- the swap
def test_swap_cuts_over_and_rolls_back_on_bad_probe(tmp_path):
    p1, net1 = _export(tmp_path, "v1", seed=3)
    p2, net2 = _export(tmp_path, "v2", seed=4)
    p_bad, _ = _export(tmp_path, "vbad", nan=True)
    host = ModelHost(server_kw={"slo_ms": 30000, "coalesce_ms": 0.5})
    try:
        host.load("model", p1)
        x = onp.random.rand(3).astype("float32")
        onp.testing.assert_allclose(
            host.submit(x).result(30),
            net1(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        # zero-downtime swap: new artifact beside the live one, warm
        # probe, cut over between batches
        swap_ms = host.swap("model", p2)
        assert swap_ms > 0
        assert host.stats["swaps"] == 1
        onp.testing.assert_allclose(
            host.submit(x).result(30),
            net2(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        # a poisoned artifact fails its warm probe: ROLLBACK — the
        # previous (v2) model keeps serving, loudly reported
        with pytest.raises(MXNetError, match="rolled back"):
            host.swap("model", p_bad)
        assert host.stats["rollbacks"] == 1
        onp.testing.assert_allclose(
            host.submit(x).result(30),
            net2(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        assert host.residency()["models"]["model"]["path"] == p2
    finally:
        host.close_all()


def test_swap_keeps_per_model_overrides_and_guards_unload(tmp_path):
    """A swap changes the ARTIFACT, not the model's admission
    contract: per-model load() overrides survive the upgrade.  And a
    model with a swap in flight refuses unload/load/swap until it
    resolves — the hole where an unload landing mid-probe was
    resurrected by the cutover."""
    p1, _ = _export(tmp_path, "v1", seed=5)
    p2, _ = _export(tmp_path, "v2", seed=6)
    host = ModelHost(server_kw={"slo_ms": 30000, "coalesce_ms": 0.5})
    try:
        host.load("model", p1, slo_ms=1234.0, queue_depth=7)
        host.swap("model", p2)
        srv = host.get("model")
        assert srv.slo_ms == 1234.0
        assert srv.queue_depth == 7
        # a name claimed by an in-flight load/swap is busy everywhere
        host._pending["model"] = 0
        with pytest.raises(MXNetError, match="in flight"):
            host.unload("model")
        with pytest.raises(MXNetError, match="in flight"):
            host.swap("model", p1)
        host._pending.clear()
    finally:
        host.close_all()


def test_swap_probe_carries_the_swaps_budget_not_the_slo(tmp_path):
    """A model whose SLO sits under its own warm latency (the override
    survives a swap by design) still swaps: the warm probe is the
    swap's request, admitted against ``probe_timeout``, not shed as
    "estimated completion exceeds deadline" against the model's SLO."""
    p1, _ = _export(tmp_path, "v1", seed=7)
    p2, net2 = _export(tmp_path, "v2", seed=8)
    host = ModelHost(server_kw={"slo_ms": 30000, "coalesce_ms": 0.5})
    try:
        host.load("model", p1, slo_ms=1e-3)  # 1 us: under any batch
        with pytest.raises(ServeRejected) as shed:
            host.submit(onp.zeros(3, "float32"))
        assert shed.value.reason == "deadline"
        host.swap("model", p2)
        assert host.stats["swaps"] == 1 and host.stats["rollbacks"] == 0
        assert host.get("model").slo_ms == 1e-3
        x = onp.random.rand(3).astype("float32")
        onp.testing.assert_allclose(
            host.submit(x, deadline_ms=30000).result(30),
            net2(nd.array(x[None])).asnumpy()[0], rtol=1e-5, atol=1e-5)
    finally:
        host.close_all()


# -------------------------------------------------------- HTTP frontend
def test_frontend_predict_health_metrics_and_rejections():
    srv = ModelServer(_np_model(delay=0.002), (3,), max_batch=4,
                      slo_ms=30000, coalesce_ms=0.5)
    srv.start(warm=True)
    fe = ServeFrontend(srv, port=0).start()
    try:
        x = onp.random.rand(2, 3).astype("float32")
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/v1/predict", {"inputs": x.tolist()})
        assert st == 200
        onp.testing.assert_allclose(onp.asarray(body["outputs"]),
                                    x * 2.0 + 1.0, rtol=1e-6)
        assert body["latency_ms"] > 0
        st, h = http_call("127.0.0.1", fe.port, "GET", "/healthz")
        assert st == 200 and h["ready"] and h["live"]
        st, text = http_call("127.0.0.1", fe.port, "GET", "/metrics")
        assert st == 200
        assert "mxnet_tpu_serve_ready 1" in text
        assert "mxnet_tpu_serve_live 1" in text
        assert "mxnet_tpu_serve_requests" in text
        # an impossible deadline is the SAME structured shed the
        # in-process API raises, carried as HTTP 429
        st, body = http_call(
            "127.0.0.1", fe.port, "POST", "/v1/predict",
            {"inputs": x.tolist(), "deadline_ms": 0.001})
        assert st == 429
        assert body["error"] == "deadline"
        # draining maps to 503 — the router's route-to-a-sibling code
        srv.drain(timeout=10)
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/v1/predict", {"inputs": x.tolist()})
        assert (st, body["error"]) == (503, "draining")
        st, h = http_call("127.0.0.1", fe.port, "GET", "/healthz")
        assert st == 503 and h["ready"] is False
        # malformed bodies are 400s, not handler deaths
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/v1/predict", {"nope": 1})
        assert st == 400
        # a bare ModelServer has no admin surface: explicit 501
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/swap", {"path": "x.mxje"})
        assert (st, body["error"]) == (501, "not_implemented")
    finally:
        fe.close()
        srv.close()


def test_frontend_admin_load_budget_is_507(tmp_path):
    p1, _ = _export(tmp_path, "m1")
    p2, _ = _export(tmp_path, "m2")
    reserved, _ = artifact_reserved_bytes(p1)
    host = ModelHost(hbm_budget_mb=(reserved * 1.5) / (1 << 20),
                     server_kw={"slo_ms": 30000})
    fe = ServeFrontend(host, port=0).start()
    try:
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/load", {"model": "m1",
                                             "path": p1})
        assert st == 200 and "m1" in body["models"]
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/load", {"model": "m2",
                                             "path": p2})
        assert st == 507, body
        assert body["error"] == "hbm_budget"
        st, res = http_call("127.0.0.1", fe.port, "GET", "/v1/models")
        assert st == 200 and sorted(res["models"]) == ["m1"]
        # a missing required field is the client's 400, not a 500
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/load", {"path": p2})
        assert st == 400, body
        assert body["error"] == "bad_request"
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/swap", {"model": "m1"})
        assert st == 400, body
        # a refusal that never started a swap (unknown model) is a
        # 400, NOT the 409 reserved for real rollbacks
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/swap", {"model": "ghost",
                                             "path": p2})
        assert (st, body["error"]) == (400, "bad_request"), body
        # an ATTEMPTED swap whose warm probe fails is the 409
        # rollback — the old artifact keeps serving
        p_bad, _ = _export(tmp_path, "mbad", nan=True)
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/admin/swap", {"model": "m1",
                                             "path": p_bad},
                             timeout=60.0)
        assert (st, body["error"]) == (409, "swap_rolled_back"), body
        x = onp.random.rand(3).astype("float32")
        st, body = http_call("127.0.0.1", fe.port, "POST",
                             "/v1/predict", {"inputs": [x.tolist()],
                                             "model": "m1"})
        assert st == 200, body  # still serving the previous artifact
    finally:
        fe.close()
        host.close_all()


# ------------------------------------------------------------ the router
def _attached_pair(delay_a=0.0, delay_b=0.0, slo_ms=10000):
    """Two in-process replicas (ModelServer + frontend) and a router
    attached to them — the full HTTP routing path without process
    spawn cost."""
    reps = []
    for d in (delay_a, delay_b):
        srv = ModelServer(_np_model(delay=d), (3,), max_batch=4,
                          slo_ms=slo_ms, coalesce_ms=0.2)
        srv.start(warm=True)
        fe = ServeFrontend(srv, port=0).start()
        reps.append((srv, fe))
    router = FleetRouter(
        endpoints=[("127.0.0.1", fe.port) for _, fe in reps],
        slo_ms=slo_ms, probe_interval=0.05)
    router.start_probes()
    deadline = time.monotonic() + 10
    while router.health()["ready"] < 2 \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert router.health()["ready"] == 2
    return router, reps


def test_router_routes_and_fails_over_to_sibling():
    router, reps = _attached_pair()
    try:
        x = onp.random.rand(3).astype("float32")
        for _ in range(6):
            onp.testing.assert_allclose(router.submit(x),
                                        x * 2.0 + 1.0, rtol=1e-6)
        assert router.stats["completed"] == 6
        # kill replica B (frontend down = connection refused): the
        # in-flight retry lands on the sibling INSIDE the deadline,
        # the probe loop ejects the dead endpoint
        reps[1][1].close()
        reps[1][0].close()
        for _ in range(6):
            onp.testing.assert_allclose(router.submit(x),
                                        x * 2.0 + 1.0, rtol=1e-6)
        deadline = time.monotonic() + 10
        while router.health()["replicas"] > 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        h = router.health()
        assert h["replicas"] == 1 and h["ready"] == 1
        assert router.stats["ejected"] == 1
        # failovers were counted iff a request was in flight when the
        # endpoint died; the routing kept succeeding either way
        assert router.stats["completed"] == 12
        assert router.stats["shed"] == 0
    finally:
        router.close()
        for srv, fe in reps:
            fe.close()
            srv.close()


def test_router_all_replicas_down_sheds_structured():
    router, reps = _attached_pair(slo_ms=2000)
    try:
        for srv, fe in reps:
            fe.close()
            srv.close()
        x = onp.zeros((3,), "float32")
        t0 = time.perf_counter()
        with pytest.raises(ServeRejected) as ei:
            router.submit(x)
        dt = time.perf_counter() - t0
        assert ei.value.reason in ("no_replica", "model_error")
        assert dt < 5.0  # bounded by the deadline, not a hang
        assert router.stats["shed"] == 1
    finally:
        router.close()


def test_router_prefers_least_loaded_replica():
    """Least-queue-depth: with replica A slow (its probed queue depth
    and outstanding count grow), new requests drift to B."""
    router, reps = _attached_pair(delay_a=0.2, delay_b=0.0)
    try:
        x = onp.zeros((3,), "float32")
        outs = []
        threads = [threading.Thread(
            target=lambda: outs.append(router.submit(x)))
            for _ in range(10)]
        for t in threads:
            t.start()
            time.sleep(0.01)
        for t in threads:
            t.join(timeout=30)
        assert len(outs) == 10
        h = router.health()["per_replica"]
        # the fast replica took the bulk of the traffic
        assert h[1]["routed"] > h[0]["routed"], h
    finally:
        router.close()
        for srv, fe in reps:
            fe.close()
            srv.close()


def test_autoscaler_ewma_scales_up_and_down(monkeypatch):
    """The autoscale decision path in isolation: a high queue EWMA
    spawns (after the cooldown), a low one drains, both bounded and
    both counted as resizes."""
    router = FleetRouter(scale_up_depth=2.0, scale_down_depth=0.2,
                         min_replicas=1, max_replicas=3,
                         scale_cooldown_s=0.0)
    router._spawn_spec = {"stub": True}  # enable the scaler
    spawned, drained = [], []
    monkeypatch.setattr(router, "_spawn_replica",
                        lambda: spawned.append(1))

    def fake_drain():
        drained.append(1)
        return object()  # a drain that actually started

    monkeypatch.setattr(router, "_drain_one", fake_drain)
    from mxnet_tpu.serving.fleet import _Replica

    router._replicas = [_Replica(0, port=1), _Replica(1, port=2)]
    for r in router._replicas:
        r.state = "ready"

    router.queue_ewma = 5.0      # way past scale_up_depth
    router._maybe_scale()
    assert spawned == [1]
    assert router.stats["resizes"] == 1
    router.queue_ewma = 0.05     # below scale_down_depth
    router._maybe_scale()
    assert drained == [1]
    assert router.stats["resizes"] == 2
    # bounds: at max_replicas no further spawn, at min no further drain
    router._replicas.append(_Replica(2, port=3))
    for r in router._replicas:
        r.state = "ready"
    router.queue_ewma = 5.0
    router._maybe_scale()
    assert spawned == [1]  # capped by max_replicas=3
    router._replicas = [_Replica(0, port=1)]
    router._replicas[0].state = "ready"
    router.queue_ewma = 0.0
    router._maybe_scale()
    assert drained == [1]  # floored by min_replicas=1
    # cooldown: a fresh scale within the window is suppressed
    router.scale_cooldown_s = 60.0
    router._last_scale = time.monotonic()
    router._replicas = [_Replica(0, port=1), _Replica(1, port=2)]
    for r in router._replicas:
        r.state = "ready"
    router.queue_ewma = 5.0
    router._maybe_scale()
    assert spawned == [1]
    # a still-converging (starting) replica pauses every decision
    router.scale_cooldown_s = 0.0
    router._replicas[1].state = "starting"
    router._maybe_scale()
    assert spawned == [1] and drained == [1]
    # the scale-down floor counts ROUTABLE replicas: with the sibling
    # benched (open breaker / missed probes), draining would take the
    # only ready replica — so nothing drains
    router._replicas[1].state = "unready"
    router.queue_ewma = 0.0
    n_drained = len(drained)
    router._maybe_scale()
    assert len(drained) == n_drained
    # a drain that could not start (momentarily no ready replica)
    # records NO resize — the event only reports what happened
    router._replicas[1].state = "ready"
    monkeypatch.setattr(router, "_drain_one", lambda: None)
    router.queue_ewma = 0.0
    before = router.stats["resizes"]
    router._maybe_scale()
    assert router.stats["resizes"] == before


def test_router_telemetry_fleet_records_and_counters(tmp_path):
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.telemetry import schema as tm_schema

    path = str(tmp_path / "run.jsonl")
    tm.reset(path)
    router, reps = _attached_pair()
    try:
        x = onp.zeros((3,), "float32")
        for _ in range(3):
            router.submit(x)
        reps[1][1].close()
        reps[1][0].close()
        deadline = time.monotonic() + 10
        while router.health()["replicas"] > 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        router.submit(x)
    finally:
        router.close()
        for srv, fe in reps:
            fe.close()
            srv.close()
        tm.close()
    with open(path) as f:
        recs, problems = tm_schema.validate_lines(f)
    assert not problems, problems[:5]
    fleet = [r for r in recs if r["type"] == "fleet"]
    assert fleet, "fleet records must land in the run log"
    assert {"eject", "close"} <= {r["action"] for r in fleet}
    for r in fleet:
        assert r["replicas"] >= r["ready"] >= 0
        assert r["requests"] >= 0
    end = next(r for r in recs if r["type"] == "run_end")
    c = end["counters"]
    assert c["fleet_requests"] == 4
    assert c["fleet_shed"] == 0
    ejects = [r for r in recs if r["type"] == "event"
              and r["kind"] == "fleet_eject"]
    assert len(ejects) == 1


# ------------------------------------------------------- THE fleet drill
def _burst(router, x, n, deadline_ms, outcomes, threads=6):
    """Bursty load from a small thread pool; every submit outcome is
    recorded — the zero-silent-hangs ledger."""
    def worker(k):
        for _ in range(k):
            t0 = time.perf_counter()
            try:
                out = router.submit(x, deadline_ms=deadline_ms)
                outcomes.append(("ok",
                                 (time.perf_counter() - t0) * 1e3,
                                 out))
            except ServeRejected as e:
                outcomes.append((e.reason, None, None))

    ts = [threading.Thread(target=worker, args=(n // threads,))
          for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in ts), \
        "burst workers hung — a request never reached terminal state"


@pytest.mark.unit
def test_fleet_drill_failover_resize_and_rolling_swap(tmp_path):
    """THE round-15 acceptance drill (subprocess, tier-1): bursty load
    across 2 replica server processes stays p99-within-SLO through

    (a) one replica hard-killed mid-burst (``fleet.replica:crash`` —
        the deterministic SIGKILL) with its in-flight work retried on
        the sibling inside the original deadline,
    (b) a queue-depth-EWMA-driven scale-up resize (the round-12
        reshard-not-restart event, counted + logged), and
    (c) a rolling ``.mxje`` model swap that leaves the run-log
        retrace counter 0 on the new artifact —

    with every submitted request reaching a terminal state."""
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.telemetry import schema as tm_schema

    p1, _net1 = _export(tmp_path, "v1", seed=11)
    p2, net2 = _export(tmp_path, "v2", seed=12)
    logdir = tmp_path / "replica-logs"
    logdir.mkdir()
    parent_log = str(tmp_path / "router.jsonl")
    tm.reset(parent_log)
    slo_ms = 8000.0
    router = FleetRouter.spawn(
        p1, replicas=2, slo_ms=slo_ms,
        env={"JAX_PLATFORMS": "cpu"},
        runlog_dir=str(logdir),
        # replica 0 dies HARD on its 15th predict request: mid-burst,
        # no cleanup — the deterministic kill -9
        replica_env={0: {"MXNET_FAULT_SPEC":
                         "fleet.replica:crash@15"}},
        probe_interval=0.05, scale_up_depth=0.5,
        scale_down_depth=None, max_replicas=3, scale_cooldown_s=1.0)
    outcomes = []
    try:
        x = onp.random.rand(3).astype("float32")
        # ---- (a) the burst that kills replica 0 + (b) builds queue
        _burst(router, x, 120, slo_ms, outcomes)
        # the crash fired: replica 0 is ejected (rc = faultsim's 87)
        deadline = time.monotonic() + 20
        while router.stats["ejected"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.stats["ejected"] == 1, router.health()
        assert router.stats["failovers"] >= 1, \
            "the killed replica's in-flight work must have retried"
        # ---- (b) the queue-depth EWMA demanded a third replica
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            h = router.health()
            if h["ready"] >= 2 and router.stats["resizes"] >= 1:
                break
            _burst(router, x, 24, slo_ms, outcomes, threads=4)
        assert router.stats["resizes"] >= 1, router.health()
        assert router.health()["ready"] >= 2
        # let the fleet converge (a replica spawned mid-burst must
        # finish starting — rolling_swap would otherwise flag it as
        # possibly coming up on the previous artifact)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            per = router.health()["per_replica"]
            if all(st["state"] != "starting" for st in per.values()):
                break
            time.sleep(0.1)
        # ---- (c) rolling swap under the surviving fleet
        swap = router.rolling_swap(p2)
        assert swap["errors"] == {}, swap
        assert swap["per_replica"], swap
        assert swap["swap_ms"] > 0
        out = router.submit(x, deadline_ms=slo_ms)
        onp.testing.assert_allclose(
            out, net2(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
        # ---- the SLO verdict over every admitted+completed request
        lat = sorted(l for kind, l, _ in outcomes if kind == "ok")
        assert lat, "no request completed"
        p99 = percentile(lat, 0.99)
        assert p99 <= slo_ms, \
            f"admitted p99 {p99:.1f} ms blew the {slo_ms} ms SLO"
        # zero silent hangs: every outcome is terminal + structured
        bad = [k for k, _, _ in outcomes
               if k not in ("ok", "queue_full", "deadline", "expired",
                            "model_error", "breaker_open", "draining",
                            "no_replica")]
        assert not bad, bad
    finally:
        rcs = router.close()
        tm.close()
    # the crashed replica died with the faultsim exit code; every
    # drained survivor exited rc -15 (clean SIGTERM drain)
    assert rcs[0] == faultsim.CRASH_EXIT_CODE, rcs
    survivors = {i: rc for i, rc in rcs.items() if i != 0}
    assert survivors and all(rc == -15 for rc in survivors.values()), \
        rcs
    # ---- load-not-retrace on the NEW artifact: each survivor's run
    # log closed with compile counter 0 (AOT swap = deserialize, not
    # trace)
    checked = 0
    for idx in survivors:
        rl = logdir / f"replica-{idx}.jsonl"
        if idx != 1 and not rl.exists():
            # a scale-up replica SIGTERM'd while still starting never
            # armed its run log; the original survivor (1) must have
            continue
        assert rl.exists(), sorted(os.listdir(logdir))
        recs = [json.loads(ln) for ln in open(rl)]
        end = next((r for r in recs if r["type"] == "run_end"), None)
        if end is None and idx != 1:
            continue  # killed before its drain closed the log
        assert end is not None, (idx, recs[-3:])
        assert end["counters"]["compiles"] == 0, (idx, end)
        if idx == 1:
            assert end["counters"]["serve_requests"] > 0
        checked += 1
    assert checked >= 1
    # ---- the parent run log carries the round-12 resize contract +
    # schema-valid fleet records
    with open(parent_log) as f:
        recs, problems = tm_schema.validate_lines(f)
    assert not problems, problems[:5]
    resizes = [r for r in recs if r["type"] == "event"
               and r["kind"] == "resize"]
    assert resizes, "the scale-up must emit the resize event"
    assert resizes[0]["scope"] == "serving_fleet"
    assert resizes[0]["new_world"] == resizes[0]["old_world"] + 1
    end = next(r for r in recs if r["type"] == "run_end")
    assert end["counters"]["reshards"] >= 1
    assert end["counters"]["fleet_resizes"] >= 1
    assert end["counters"]["fleet_swaps"] >= 1
    assert end["counters"]["fleet_failovers"] >= 1
    fleet_recs = [r for r in recs if r["type"] == "fleet"]
    assert {"eject", "resize", "swap", "close"} <= \
        {r["action"] for r in fleet_recs}


# --------------------------------------------------------- slow drills
@pytest.mark.slow
def test_scale_down_drains_without_shedding(tmp_path):
    """Scale-down under load: the SIGTERM'd replica leaves the routing
    pool FIRST and drains through PreemptionDrain — the fleet sheds
    NOTHING while going 3 -> 2."""
    p1, _net = _export(tmp_path, "v1", seed=21)
    router = FleetRouter.spawn(p1, replicas=3, slo_ms=10000,
                               env={"JAX_PLATFORMS": "cpu"},
                               probe_interval=0.05)
    outcomes = []
    stop = threading.Event()
    try:
        x = onp.random.rand(3).astype("float32")

        def steady():
            while not stop.is_set():
                try:
                    router.submit(x, deadline_ms=10000)
                    outcomes.append("ok")
                except ServeRejected as e:
                    outcomes.append(e.reason)
                time.sleep(0.01)

        ts = [threading.Thread(target=steady) for _ in range(2)]
        for t in ts:
            t.start()
        time.sleep(0.5)
        router.resize(2)
        # the drained replica exits -15; traffic never shed
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            h = router.health()
            if h["replicas"] == 2 and h["ready"] == 2:
                break
            time.sleep(0.1)
        time.sleep(0.5)
        stop.set()
        for t in ts:
            t.join(timeout=30)
        assert outcomes and all(o == "ok" for o in outcomes), \
            [o for o in outcomes if o != "ok"][:5]
        assert router.stats["resizes"] == 1
        h = router.health()
        assert h["replicas"] == 2
    finally:
        stop.set()
        rcs = router.close()
    assert sorted(rcs.values()) == [-15, -15, -15]


@pytest.mark.slow
def test_mid_swap_crash_leaves_fleet_serving_new_artifact(tmp_path):
    """fleet.swap:crash@1 on ONE replica: it dies mid-swap (hard, no
    cleanup); the rolling swap reports it in errors, the probe loop
    ejects it, and the REST of the fleet serves the new artifact."""
    p1, _net1 = _export(tmp_path, "v1", seed=31)
    p2, net2 = _export(tmp_path, "v2", seed=32)
    router = FleetRouter.spawn(
        p1, replicas=2, slo_ms=10000, env={"JAX_PLATFORMS": "cpu"},
        replica_env={1: {"MXNET_FAULT_SPEC": "fleet.swap:crash@1"}},
        probe_interval=0.05)
    try:
        x = onp.random.rand(3).astype("float32")
        router.submit(x)
        swap = router.rolling_swap(p2)
        assert list(swap["errors"]) == [1], swap
        assert list(swap["per_replica"]) == [0], swap
        # the dead replica is ejected; the survivor serves v2
        deadline = time.monotonic() + 20
        while router.health()["replicas"] > 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.health()["replicas"] == 1
        out = router.submit(x, deadline_ms=10000)
        onp.testing.assert_allclose(
            out, net2(nd.array(x[None])).asnumpy()[0],
            rtol=1e-5, atol=1e-5)
    finally:
        rcs = router.close()
    assert rcs[1] == faultsim.CRASH_EXIT_CODE
    assert rcs[0] == -15
