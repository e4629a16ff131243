"""Round 20 — distributed tracing: one causal timeline across router,
replicas, trainer, and data workers.

Tier-1 coverage for the trace-context plumbing
(``mxnet_tpu/telemetry/tracing.py``), the span schema, the pid-suffixed
crash artifacts, the clock-skew alignment in ``tools/tracemerge.py``
(synthetic 3-process logs with ±200 ms injected skew must merge into a
monotone-causal timeline, plus the zero-pair fallback), and THE
acceptance drill: a request submitted through a 2-replica FleetRouter
yields, after tracemerge, one trace whose spans cross >= 2 processes
with valid parent links and a queue/coalesce/compute decomposition that
sums to ~the end-to-end latency — with ``doctor`` naming the
delay-injected replica as the bottleneck.  The unarmed A/B guarantee
(no runlog => no minting, no trace fields, header ignored-but-harmless)
is asserted alongside.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as onp
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, nd  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.telemetry import schema, tracing  # noqa: E402

_TOOL = os.path.join(_REPO, "tools", "tracemerge.py")


def _load_tool():
    spec = importlib.util.spec_from_file_location("tracemerge", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("MXNET_RUNLOG", raising=False)
    monkeypatch.delenv(tracing.TRACE_ENV, raising=False)
    monkeypatch.delenv(tracing.ROLE_ENV, raising=False)
    monkeypatch.delenv(tracing.RANK_ENV, raising=False)
    tracing._reset_process_context()
    telemetry.reset(None)
    yield
    tracing._reset_process_context()
    telemetry.reset(None)


# ------------------------------------------------------------ context unit
@pytest.mark.unit
def test_traceparent_roundtrip_and_malformed():
    ctx = tracing.mint()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = tracing.from_header(ctx.to_header())
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    # 3-part form (missing flags) tolerated
    assert tracing.from_header(
        f"00-{ctx.trace_id}-{ctx.span_id}") is not None
    for bad in (None, "", "zz", "00-short-short-01",
                "00-" + "g" * 32 + "-" + "1" * 16 + "-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01"):
        assert tracing.from_header(bad) is None, bad
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_span_id == ctx.span_id
    assert child.span_id != ctx.span_id


@pytest.mark.unit
def test_thread_stack_and_process_stamp(monkeypatch):
    assert tracing.current_context() is None
    ctx = tracing.mint()
    with tracing.use(ctx):
        assert tracing.current_context() is ctx
        inner = ctx.child()
        with tracing.use(inner):
            assert tracing.current_context() is inner
        assert tracing.current_context() is ctx
    assert tracing.current_context() is None
    # the env stamp is the process-level root
    monkeypatch.setenv(tracing.TRACE_ENV, ctx.to_header())
    tracing._reset_process_context()
    got = tracing.current_context()
    assert got is not None and got.trace_id == ctx.trace_id


@pytest.mark.unit
def test_unarmed_zero_cost_ab(tmp_path, monkeypatch):
    """A/B: unarmed (no runlog) => no minting, no spans, stamp_env
    scrubs; armed => same call sites produce the records."""
    # ---- A: unarmed
    assert not tracing.enabled()
    with tracing.span("nothing") as ctx:
        assert ctx is None
    # the train path's host span is then the profiler's annotation and
    # no more: no id minted, no wrapper built
    minted = []
    monkeypatch.setattr(tracing, "_gen_span_id",
                        lambda: minted.append(1) or "0" * 16)
    monkeypatch.setattr(tracing, "_gen_trace_id",
                        lambda: minted.append(1) or "0" * 32)
    for r in (tracing.region("mx_nothing", depth=2),
              tracing.region("mx_nothing", step_num=3)):
        assert isinstance(r, jax.profiler.TraceAnnotation)
        with r as inside:
            inside.set_metadata(bytes=1)
    assert type(tracing.region("mx_nothing", step_num=3)) \
        is jax.profiler.StepTraceAnnotation
    assert not minted
    monkeypatch.undo()
    env = {tracing.TRACE_ENV: "stale"}
    assert tracing.stamp_env(env, "replica", rank=0) is None
    assert tracing.TRACE_ENV not in env  # scrubbed, never inherited
    assert env[tracing.ROLE_ENV] == "replica"
    # ---- B: armed — the same sites emit
    path = str(tmp_path / "r.jsonl")
    telemetry.reset(path)
    with tracing.span("something", kind="server", k=1) as ctx:
        assert ctx is not None
        with tracing.region("mx_something", depth=2) as r:
            r.set_metadata(bytes=7)
        # queued behind the next flushing record: no syscall of its own
        with open(path) as f:
            assert "mx_something" not in f.read()
        # the RunLog's sampled ones alone, where the caller counts them
        rl = telemetry.current()
        kept = [n for n in range(2 * rl.sample + 1)
                if type(tracing.region("mx_counted", nth=n))
                is not jax.profiler.TraceAnnotation]
        assert kept == [0, rl.sample, 2 * rl.sample]
    env2 = {}
    child = tracing.stamp_env(env2, "replica", rank=1)
    assert child is not None
    assert tracing.from_header(
        env2[tracing.TRACE_ENV]).trace_id == child.trace_id
    telemetry.close()
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:5]
    spans = [r for r in recs if r["type"] == "span"]
    assert [s["name"] for s in spans] == ["mx_something", "something"]
    assert spans[1]["kind"] == "server"
    assert spans[1]["attrs"]["k"] == 1
    assert spans[0]["attrs"] == {"depth": 2, "bytes": 7}
    assert spans[0]["parent_span_id"] == spans[1]["span_id"]


@pytest.mark.unit
def test_every_record_type_gains_trace_fields(tmp_path):
    """The auto-stamp: any record written under a bound context picks
    up trace ids; records outside stay untraced; both validate."""
    path = str(tmp_path / "r.jsonl")
    rl = telemetry.reset(path)
    rl.event("before")  # untraced
    ctx = tracing.mint()
    with tracing.use(ctx):
        rl.event("inside")
        rl.heal("relaunch", attempt=1)
    telemetry.close()
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:5]
    by = {}
    for r in recs:
        if r["type"] == "event":
            by[r["kind"]] = r
    assert "trace_id" not in by["before"]
    assert by["inside"]["trace_id"] == ctx.trace_id
    assert by["inside"]["span_id"] == ctx.span_id
    heal = [r for r in recs if r["type"] == "heal"][0]
    assert heal["trace_id"] == ctx.trace_id


@pytest.mark.unit
def test_run_start_process_identity(tmp_path, monkeypatch):
    monkeypatch.setenv(tracing.ROLE_ENV, "replica")
    monkeypatch.setenv(tracing.RANK_ENV, "3")
    path = str(tmp_path / "r.jsonl")
    telemetry.reset(path)
    telemetry.close()
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:5]
    start = recs[0]
    assert start["type"] == "run_start"
    assert start["role"] == "replica"
    assert start["rank"] == 3
    assert start["parent_pid"] == os.getppid()


@pytest.mark.unit
def test_pid_suffixed_dump_artifacts(tmp_path):
    """Satellite: flight/stack dumps are pid-suffixed (no clobber when
    N processes share a prefix) and the glob loaders find both new and
    legacy names, newest first."""
    base = str(tmp_path / "r.jsonl")
    assert telemetry.flight_path_for(base).endswith(
        f".flight.{os.getpid()}.json")
    from mxnet_tpu.telemetry.watchdog import stack_path_for
    assert stack_path_for(base).endswith(f".stacks.{os.getpid()}.txt")
    # two "processes" + one legacy artifact all found
    for name in (f"{base}.flight.111.json", f"{base}.flight.222.json",
                 f"{base}.flight.json"):
        with open(name, "w") as f:
            f.write("{}")
    found = telemetry.find_flight_dumps(base)
    assert len(found) == 3
    assert f"{base}.flight.json" in found
    for name in (f"{base}.stacks.111.txt", f"{base}.stacks.txt"):
        with open(name, "w") as f:
            f.write("x")
    from mxnet_tpu.telemetry.watchdog import find_stack_dumps
    assert len(find_stack_dumps(base)) == 2


# -------------------------------------------------------- skew alignment
def _write_synth_log(path, role, pid, rank, start_wall, spans):
    """One synthetic runlog.  ``spans`` rows: (name, kind, wall_start,
    wall_end, trace_id, span_id, parent) in the PROCESS's (possibly
    skewed) wall clock."""
    with open(path, "w") as f:
        f.write(json.dumps(
            {"type": "run_start", "time": start_wall, "pid": pid,
             "parent_pid": 1, "env": {}, "jax": {},
             "config": {"sample": 50, "flight_depth": 0,
                        "textfile": None},
             "role": role, "rank": rank}) + "\n")
        for name, kind, w0, w1, tr, sid, par in spans:
            f.write(json.dumps(
                {"type": "span", "t": round(w1 - start_wall, 6),
                 "name": name, "kind": kind,
                 "dur_ms": round((w1 - w0) * 1e3, 4),
                 "trace_id": tr, "span_id": sid,
                 "parent_span_id": par}) + "\n")


def _synth_fleet(tmp_path, skew0=0.2, skew1=-0.2, n_req=8):
    """3 processes (router + 2 replicas), replicas' clocks skewed by
    ``skew0``/``skew1`` seconds.  TRUE wall times are causally ordered;
    each process records times in its own skewed clock."""
    base = 1_700_000_000.0
    tr = lambda i: f"{i:032x}"
    sid = lambda i, j: f"{i * 100 + j:016x}"
    router, rep0, rep1 = [], [], []
    for i in range(1, n_req + 1):
        t0 = base + 0.1 * i
        root = (f"fleet_request", "server", t0, t0 + 0.05,
                tr(i), sid(i, 1), None)
        hop = ("route_attempt", "client", t0 + 0.002, t0 + 0.045,
               tr(i), sid(i, 2), sid(i, 1))
        router += [root, hop]
        dst, skew = (rep0, skew0) if i % 2 else (rep1, skew1)
        # the replica-side server span nests INSIDE the hop (true
        # causality); its recorded clock is skewed
        dst.append(("replica_request", "server",
                    t0 + 0.005 + skew, t0 + 0.040 + skew,
                    tr(i), sid(i, 3), sid(i, 2)))
        dst.append(("serve_model", "internal",
                    t0 + 0.010 + skew, t0 + 0.035 + skew,
                    tr(i), sid(i, 4), sid(i, 3)))
    d = tmp_path / "logs"
    d.mkdir()
    _write_synth_log(str(d / "router.jsonl"), "router", 100, None,
                     base, router)
    _write_synth_log(str(d / "replica-0.jsonl"), "replica", 200, 0,
                     base + skew0, rep0)
    _write_synth_log(str(d / "replica-1.jsonl"), "replica", 300, 1,
                     base + skew1, rep1)
    return str(d)


@pytest.mark.unit
def test_skew_alignment_monotone_causality(tmp_path):
    """Satellite: ±200 ms injected skew across 3 synthetic processes
    merges into a timeline where every child span starts >= its parent
    (and the recovered offsets match the injected skew)."""
    tm = _load_tool()
    d = _synth_fleet(tmp_path, skew0=0.2, skew1=-0.2)
    procs = tm.load_runlogs([d])
    assert len(procs) == 3
    offsets, info = tm.estimate_offsets(procs)
    labels = {i: p["label"] for i, p in enumerate(procs)}
    by_label = {labels[i]: offsets[i] for i in offsets}
    ref = labels[info["reference"]]
    assert ref.startswith("router")
    for label, want in (("replica-0", 0.2), ("replica-1", -0.2),
                        ("router", 0.0)):
        got = [v for k, v in by_label.items()
               if k.startswith(label)][0]
        assert abs(got - want) < 1e-3, (label, got)
    # monotone causality on CORRECTED times, across every parent link
    corrected = {}
    for i, p in enumerate(procs):
        for s in p["spans"]:
            corrected[s["span_id"]] = (s["t_start"] - offsets[i],
                                       s["t_end"] - offsets[i])
    checked = 0
    for i, p in enumerate(procs):
        for s in p["spans"]:
            par = s.get("parent_span_id")
            if par not in corrected:
                continue
            child_start = s["t_start"] - offsets[i]
            assert child_start >= corrected[par][0] - 1e-6
            checked += 1
    assert checked >= 16  # every hop + nested span verified
    # the merged Perfetto trace carries cross-process flow arrows
    trace = tm.merge_trace(procs)
    flows = [e for e in trace["traceEvents"] if e["ph"] in ("s", "f")]
    assert len(flows) >= 16
    assert len({e["pid"] for e in trace["traceEvents"]
                if e["ph"] == "X"}) == 3


@pytest.mark.unit
def test_skew_zero_pair_fallback(tmp_path):
    """Processes with NO request-response pair fall back to beat-file
    mtimes when available, else to the run_start wall clock."""
    tm = _load_tool()
    base = 1_700_000_000.0
    d = tmp_path / "logs"
    d.mkdir()
    # two processes, no cross links at all
    _write_synth_log(str(d / "a.jsonl"), "trainer", 100, 0, base,
                     [("online_step", "internal", base + 1, base + 2,
                       "a" * 32, "1" * 16, None)])
    _write_synth_log(str(d / "b.jsonl"), "io_worker", 200, 0,
                     base + 0.5,
                     [("load", "internal", base + 1.5, base + 2.5,
                       "b" * 32, "2" * 16, None)])
    procs = tm.load_runlogs([str(d)])
    offsets, info = tm.estimate_offsets(procs)
    assert info["pairs"] == {}
    assert set(info["fallback"].values()) == {"wall"}
    assert all(v == 0.0 for i, v in offsets.items()
               if i != info["reference"])
    # with beat files: payload-time-vs-mtime puts both on the shared
    # filesystem clock.  Process 200's wall clock runs 0.3 s ahead.
    hb = tmp_path / "hb"
    hb.mkdir()
    now = time.time()
    for rank, pid, ahead in ((0, 100, 0.0), (1, 200, 0.3)):
        p = str(hb / f"rank-{rank}.hb")
        with open(p, "w") as f:
            f.write(json.dumps({"rank": rank, "pid": pid,
                                "host": "x", "time": now + ahead}))
        os.utime(p, (now, now))
    offsets2, info2 = tm.estimate_offsets(procs, beats_dir=str(hb))
    assert set(info2["fallback"].values()) == {"beats"}
    vals = {procs[i]["pid"]: v for i, v in offsets2.items()}
    assert abs((vals[200] - vals[100]) - 0.3) < 5e-2


@pytest.mark.unit
def test_prom_aggregate_sums_counters_maxes_gauges(tmp_path):
    tm = _load_tool()
    a = str(tmp_path / "a.prom")
    b = str(tmp_path / "b.prom")
    with open(a, "w") as f:
        f.write("# TYPE mxnet_tpu_serve_requests counter\n"
                "mxnet_tpu_serve_requests 10\n"
                "# TYPE mxnet_tpu_serve_ready gauge\n"
                'mxnet_tpu_serve_ready{model="m"} 0\n')
    with open(b, "w") as f:
        f.write("# TYPE mxnet_tpu_serve_requests counter\n"
                "mxnet_tpu_serve_requests 5\n"
                "# TYPE mxnet_tpu_serve_ready gauge\n"
                'mxnet_tpu_serve_ready{model="m"} 1\n')
    body = tm.aggregate_textfiles([a, b])
    assert "mxnet_tpu_serve_requests 15" in body
    assert 'mxnet_tpu_serve_ready{model="m"} 1' in body
    assert body.count("# TYPE mxnet_tpu_serve_requests counter") == 1


# ------------------------------------------------------ THE fleet drill
def _export(tmp_path, name, batch=4, seed=11):
    onp.random.seed(seed)
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    net(nd.zeros((batch, 3)))
    path = str(tmp_path / f"{name}.mxje")
    mx.deploy.export_model(net, nd.zeros((batch, 3)), path,
                           platforms=("cpu",))
    return path, net


@pytest.mark.unit
def test_fleet_drill_one_causal_timeline(tmp_path):
    """THE round-20 acceptance drill: requests through a 2-replica
    fleet (one replica delay-injected) merge into traces crossing
    >= 2 processes with valid parent links; the queue/coalesce/compute
    decomposition sums to ~the end-to-end latency; doctor names the
    delayed replica as the bottleneck; the response echoes the trace
    header."""
    from mxnet_tpu.serving import FleetRouter

    tm = _load_tool()
    p1, _net = _export(tmp_path, "v1")
    logdir = tmp_path / "logs"
    logdir.mkdir()
    telemetry.reset(str(logdir / "router.jsonl"))
    slo_ms = 8000.0
    delay_s = 0.05
    router = FleetRouter.spawn(
        p1, replicas=2, slo_ms=slo_ms,
        env={"JAX_PLATFORMS": "cpu"}, runlog_dir=str(logdir),
        replica_env={1: {"MXNET_FAULT_SPEC":
                         f"serve.model:delay={delay_s}@1+"}},
        probe_interval=0.1)
    lats, errs = [], []
    try:
        x = onp.random.rand(3).astype("float32")

        def one():
            t0 = time.perf_counter()
            try:
                router.submit(x, deadline_ms=slo_ms)
                lats.append(time.perf_counter() - t0)
            except Exception as exc:  # pragma: no cover - diagnostics
                errs.append(repr(exc))

        # concurrent waves so BOTH replicas take traffic
        for _ in range(6):
            ts = [threading.Thread(target=one) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    finally:
        router.close(timeout=30)
    telemetry.close()
    assert not errs, errs[:3]
    assert len(lats) == 24

    procs = tm.load_runlogs([str(logdir)])
    assert len(procs) >= 3  # router + 2 replicas
    rep = tm.doctor(procs)
    assert rep["requests"] == 24
    # every request's decomposition fits inside (and fills) its e2e
    multi_proc_traces = 0
    span_index = {}
    for p in procs:
        for s in p["spans"]:
            span_index.setdefault(s["span_id"], p["path"])
    for r in rep["per_request"]:
        parts = sum(r["parts_ms"].values())
        assert parts <= r["e2e_ms"] + 1.0, r
        assert abs(parts + r["other_ms"] - r["e2e_ms"]) < 1e-6
    # valid parent links crossing >= 2 processes inside one trace
    by_trace = {}
    for p in procs:
        for s in p["spans"]:
            by_trace.setdefault(s["trace_id"], set()).add(p["path"])
            par = s.get("parent_span_id")
            if par is not None and par in span_index \
                    and span_index[par] != p["path"]:
                multi_proc_traces += 1
    assert any(len(files) >= 2 for files in by_trace.values()), \
        "no trace crossed a process boundary"
    assert multi_proc_traces >= 24  # every request hopped
    # the delayed replica dominates compute and is named
    assert rep["bottleneck_process"].startswith("replica-1"), rep
    ranking = {r["process"]: r["mean_compute_ms"]
               for r in rep["compute_ranking"]}
    slow = [v for k, v in ranking.items() if k.startswith("replica-1")]
    fast = [v for k, v in ranking.items() if k.startswith("replica-0")]
    assert slow and fast
    assert slow[0] >= delay_s * 1e3  # the injected floor
    assert slow[0] > 2 * fast[0]
    # the merged Perfetto trace: >= 3 track groups + flow arrows
    trace = tm.merge_trace(procs)
    pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert len(pids) >= 3
    assert any(e["ph"] == "s" for e in trace["traceEvents"])
    assert any(e["ph"] == "f" for e in trace["traceEvents"])


@pytest.mark.unit
def test_frontend_echoes_and_adopts_inbound_traceparent(tmp_path):
    """A caller-supplied traceparent is adopted (the replica's spans
    join the CALLER's trace) and echoed in the response headers."""
    import http.client

    from mxnet_tpu.serving import ModelServer
    from mxnet_tpu.serving.frontend import ServeFrontend

    path = str(tmp_path / "r.jsonl")
    telemetry.reset(path)
    srv = ModelServer(lambda xs: xs * 2.0, (3,), max_batch=4,
                      slo_ms=10000, coalesce_ms=1.0, name="m")
    srv.start(warm=True)
    fe = ServeFrontend(srv, port=0)
    fe.start()
    try:
        caller = tracing.mint()
        conn = http.client.HTTPConnection(fe.addr, fe.port, timeout=30)
        body = json.dumps({"inputs": [[0.1, 0.2, 0.3]]})
        conn.request("POST", "/v1/predict", body=body,
                     headers={"Content-Type": "application/json",
                              tracing.TRACEPARENT_HEADER:
                              caller.to_header()})
        resp = conn.getresponse()
        assert resp.status == 200
        echoed = resp.getheader(tracing.TRACEPARENT_HEADER)
        resp.read()
        conn.close()
        parsed = tracing.from_header(echoed)
        assert parsed is not None
        assert parsed.trace_id == caller.trace_id
    finally:
        fe.close()
        srv.close()
    telemetry.close()
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:5]
    spans = [r for r in recs if r["type"] == "span"]
    names = {s["name"] for s in spans}
    assert "replica_request" in names
    assert all(s["trace_id"] == caller.trace_id for s in spans), spans
    # queue/coalesce/model siblings landed under the request context
    for want in ("serve_queue", "serve_coalesce", "serve_model"):
        assert want in names, names


@pytest.mark.unit
def test_trace_anchor_links_swap_to_export(tmp_path):
    """The v2 artifact's trace_anchor: an export made under a trace
    carries the exporting span's context, and a rolling-swap-style
    reader recovers it."""
    path = str(tmp_path / "r.jsonl")
    telemetry.reset(path)
    from mxnet_tpu.online.loop import OnlineTrainer

    t = OnlineTrainer(str(tmp_path / "w"), steps=2, export_every=2,
                      seed=3, batch=4, features=3)
    t.run()
    telemetry.close()
    arts = [f for f in os.listdir(t.publish_dir)
            if f.endswith(".mxje")]
    assert arts
    meta = mx.deploy.read_artifact_meta(
        os.path.join(t.publish_dir, arts[0]))
    anchor = tracing.from_header(meta.get("trace_anchor"))
    assert anchor is not None
    # the anchor IS the online_export span's context
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:5]
    exports = [r for r in recs if r["type"] == "span"
               and r["name"] == "online_export"]
    assert exports
    assert anchor.span_id in {e["span_id"] for e in exports}
    steps = [r for r in recs if r["type"] == "span"
             and r["name"] == "online_step"]
    assert steps  # the per-cursor entry point
    assert exports[0]["parent_span_id"] in {s["span_id"]
                                            for s in steps}
    # manifests carry the anchor too (the supervisor's view)
    mans = [f for f in os.listdir(t.publish_dir)
            if f.endswith(".json")]
    assert mans
    with open(os.path.join(t.publish_dir, mans[0])) as f:
        man = json.load(f)
    assert tracing.from_header(man.get("trace_anchor")) is not None
