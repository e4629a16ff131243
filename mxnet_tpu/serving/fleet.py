"""Elastic serving fleet (round 15): replicated ModelServers behind a
fault-tolerant router.

The reference serves millions of users with a model-server fleet over
ps-lite (SURVEY §L5/§L7): many replicas, a front that routes around
dead ones, models upgraded under live traffic.  This module composes
the pieces earlier rounds built — PR 8's in-process ModelServer (the
submit/deadline/breaker core), PR 7's elastic runtime (topology
stamps, reshard verdicts, PreemptionDrain) and the PR 5/6 telemetry —
into that story, with the same contract training earned in round 12:
**a replica dying, a model upgrade, or traffic doubling is a
routed-around / drained / resized event — never dropped work or
downtime.**

* :class:`ModelHost` — multi-model residency on one replica with an
  explicit HBM budget: a ``.mxje`` artifact is admitted only when its
  ``describe_program()`` memory_analysis reserved bytes fit
  ``MXNET_FLEET_HBM_BUDGET_MB`` next to the residents, otherwise a
  structured ``ServeRejected(reason='hbm_budget')``.  Zero-downtime
  :meth:`ModelHost.swap`: the next CRC-framed artifact loads BESIDE
  the live one, a warm probe must return finite outputs, the router
  pointer cuts over between batches, the old server drains — a failed
  probe rolls back with the old model still serving.
* :class:`FleetRouter` — spreads requests across N replica server
  processes: least-queue-depth routing fed by per-replica health
  probes; structured failover (a replica whose breaker opens or whose
  process dies is ejected and the request retried on a sibling inside
  its ORIGINAL deadline via ``retry.retry_call(deadline_sec=)``);
  queue-depth-EWMA autoscaling that triggers the round-12
  reshard-not-restart resize (``reshard_verdict`` + ``resize`` event +
  ``reshards`` counter) — scale-up spawns a replica, scale-down
  SIGTERMs one, which drains through ``PreemptionDrain`` while the
  router has already stopped routing to it, so the fleet sheds
  nothing; :meth:`FleetRouter.rolling_swap` upgrades the fleet one
  replica at a time while the others keep serving.
* :func:`replica_main` — the replica worker process
  (``python -m mxnet_tpu.serving.fleet --artifact model=path ...``):
  ModelHost + the :mod:`.frontend` HTTP front on an ephemeral port
  (written to a port file), draining cleanly on SIGTERM (rc -15).

Fault points (registered here at import, so ``MXNET_FAULT_SPEC``
drills validate): ``fleet.route`` fires inside every routing
decision, ``fleet.replica`` inside every replica predict request (a
``crash`` is the deterministic mid-burst replica death), and
``fleet.swap`` inside every model swap (a ``crash`` is the mid-swap
death the rolling upgrade must survive).
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as onp

from ..base import MXNetError
from ..resilience import faultsim
from ..resilience.retry import retry_call
from ..telemetry import tracing as _tracing
from .frontend import ServeFrontend, http_call
from .server import ModelServer, ServeRejected

__all__ = ["ModelHost", "FleetRouter", "SwapRolledBack",
           "GenerativeHostServer", "artifact_reserved_bytes",
           "replica_main"]


class SwapRolledBack(MXNetError):
    """A model swap failed AFTER it started (bad artifact, failed warm
    probe) and the previous artifact kept serving.  Distinct from the
    refusals that never touch the live model (unknown name, a swap
    already in flight), which raise plain MXNetError — an operator
    must be able to tell 'your artifact is bad' from 'retry in a
    moment'."""

faultsim.register_point(
    "fleet.route", "FleetRouter.submit, inside every routing decision")
faultsim.register_point(
    "fleet.replica", "replica frontend, inside every predict request "
                     "(crash = deterministic mid-burst replica death)")
faultsim.register_point(
    "fleet.swap", "ModelHost.swap, before the next artifact loads "
                  "(crash = mid-swap death)")


def _artifact_identity(path):
    """The v2 header's metadata (quantized / param_dtypes / signature)
    for the residency report — strictly a header+metadata read (a few
    hundred bytes), never the payload, never a deserialize: the load
    path already read and CRC-verified the artifact through
    ``from_artifact``, so a third full read here would sit on the
    load/swap critical path for nothing.  Pre-round-18 artifacts
    (no metadata segment) report None."""
    try:
        from .. import deploy

        return deploy.read_artifact_meta(path)
    except Exception:
        return None


def artifact_reserved_bytes(path):
    """Reserved device bytes of a ``.mxje`` artifact's program — the
    HBM-budget admission input.  Preferred source: the round-10
    ``describe_program()`` memory_analysis of the exported call
    (argument + output + temp bytes, recorded as a ``program_report``
    in any armed run log); backends without memory stats fall back to
    the in/out aval byte sizes.  Returns ``(reserved_bytes,
    exported)`` so admission does not read the artifact twice."""
    from .. import deploy

    exp = deploy.load_exported(path)
    reserved = 0
    try:
        import jax

        from .. import telemetry

        args = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                for a in exp.in_avals]
        rep = telemetry.describe_program(
            jax.jit(exp.call), *args,
            program=f"serve_admit:{os.path.basename(str(path))}")
        mem = rep.get("memory") or {}
        reserved = sum(int(mem.get(k, 0)) for k in
                       ("argument_bytes", "output_bytes",
                        "temp_bytes"))
    except Exception:
        reserved = 0
    if not reserved:
        avals = tuple(exp.in_avals) + tuple(exp.out_avals)
        reserved = sum(
            int(onp.prod([int(s) for s in a.shape]) or 1)
            * onp.dtype(a.dtype).itemsize for a in avals)
    return int(reserved), exp


class GenerativeHostServer:
    """The ModelServer-shaped adapter a :class:`ModelHost` wraps
    around a *generative* ``.mxje`` artifact (round 18 — PR 17's
    fleet-swap leftover): builds a
    :class:`~mxnet_tpu.serving.generate.GenerativeServer` from the
    artifact's param payload + ``gen`` header config and exposes the
    submit / health / drain / close surface the host, the HTTP
    frontend and the rolling swap drive.

    Requests are rows of token ids (the swap's zeros warm probe is a
    legal all-``<token 0>`` prompt of the smallest bucket); results
    are generated token lists.  A swap cuts the routing pointer
    between SEQUENCES and drains this server: in-flight decode
    sequences finish on the old version — never a mid-sequence
    version change — and any sequence outliving the drain budget is
    finished with the structured shutdown rejection at close
    (evict-and-resubmit on the new version is the caller's move);
    both counts are reported on the swap event.
    """

    #: host/server kwargs that map onto the GenerativeServer (the
    #: dense-server knobs like coalesce_ms are dropped, not errors:
    #: one replica process serves both artifact classes)
    _GEN_KW = ("slots", "page_tokens", "pool_budget", "kv_dtype",
               "agreement_floor", "slo_ms", "queue_depth",
               "breaker_limit", "evict_after_ms", "eos_id", "max_new",
               "kv_gate")

    generative = True

    def __init__(self, path, name="model", **kw):
        from .. import deploy
        from .generate import GenerativeServer

        params, gen = deploy.load_generative(path)
        # the npz payload deserializes to numpy; the decode programs
        # index the embed table with traced token ids, so params must
        # live as device arrays
        import jax

        params = jax.tree_util.tree_map(jax.numpy.asarray, params)
        srv_kw = {k: v for k, v in kw.items() if k in self._GEN_KW}
        buckets = tuple(int(b) for b in
                        (gen.get("prompt_buckets") or (4, 8, 16)))
        max_new = int(srv_kw.pop("max_new", gen.get("max_new", 16)))
        self._srv = GenerativeServer(
            params=params, vocab=int(gen["vocab"]),
            layers=int(gen["layers"]), heads=int(gen["heads"]),
            head_dim=int(gen["head_dim"]), prompt_buckets=buckets,
            max_new=max_new, name=name, **srv_kw)
        self.name = name
        #: warm-probe signature (ModelHost.swap probes
        #: ``zeros(item_shape, dtype)``)
        self.item_shape = (buckets[0],)
        self.dtype = onp.int32
        self._suppress_health_gauges = True

    def start(self, warm=True):
        self._srv.start(warm=warm)
        return self

    def submit(self, x, deadline_ms=None):
        toks = [int(t) for t in onp.asarray(x).reshape(-1)]
        return self._srv.submit(toks, deadline_ms=deadline_ms)

    def in_flight(self):
        return self._srv.in_flight()

    def report(self):
        return self._srv.report()

    @property
    def stats(self):
        st = {k: (dict(v) if isinstance(v, dict) else v)
              for k, v in self._srv.stats.items()}
        # the host's metrics aggregation reads the dense counter
        # names; a generative "batch" is one prefill dispatch
        st.setdefault("batches", st.get("prefills", 0))
        return st

    def health(self):
        s = self._srv
        with s._lock:
            live = bool(s._started and not s._stop)
            ready = bool(live and not s._draining
                         and not s._breaker_open)
            return {"ready": ready, "live": live,
                    "queue_depth": len(s._queue),
                    "inflight": s.in_flight()}

    def drain(self, timeout=30.0):
        return self._srv.drain(timeout=timeout)

    def close(self):
        self._srv.close()


class ModelHost:
    """Multi-model residency on one serving replica, HBM-budgeted.

    ``hbm_budget_mb`` (None = ``MXNET_FLEET_HBM_BUDGET_MB``; 0 =
    unlimited) bounds the summed reserved bytes of every resident
    model; :meth:`load` refuses past it with a structured
    ``ServeRejected(reason='hbm_budget')`` — a loud admission verdict,
    never an OOM mid-batch.  :meth:`swap` performs the zero-downtime
    rolling upgrade of ONE model: the budget gates the incoming
    artifact against the OTHER residents (the swapped model's old and
    new programs briefly co-reside by design — leave one model's
    headroom when budgeting a host that swaps under load).
    """

    def __init__(self, hbm_budget_mb=None, server_kw=None):
        from ..config import get_env

        mb = float(hbm_budget_mb if hbm_budget_mb is not None
                   else get_env("MXNET_FLEET_HBM_BUDGET_MB"))
        self.budget_bytes = int(mb * (1 << 20)) if mb > 0 else 0
        self._server_kw = dict(server_kw or {})
        self._lock = threading.RLock()
        self._models = {}     # name -> live ModelServer
        self._reserved = {}   # name -> reserved bytes
        self._paths = {}      # name -> artifact path
        self._info = {}       # name -> artifact_info header metadata
        self._load_kw = {}    # name -> per-model load() overrides
        self._pending = {}    # name -> reserved bytes mid-load/swap
        self.stats = {"loads": 0, "hbm_rejected": 0, "swaps": 0,
                      "rollbacks": 0, "unloads": 0}

    # ------------------------------------------------------ residency
    def used_bytes(self, exclude=None):
        """Resident + in-admission bytes (concurrent loads reserve
        BEFORE they start, so two admits cannot both squeeze past the
        budget)."""
        with self._lock:
            return sum(v for k, v in self._reserved.items()
                       if k != exclude) + \
                sum(v for k, v in self._pending.items()
                    if k != exclude)

    def residency(self):
        """Per-model reserved bytes vs the budget.  With the budget
        unlimited (0) the sizing compile is skipped entirely and
        every model reports 0 reserved bytes."""
        with self._lock:
            return {
                "budget_bytes": self.budget_bytes or None,
                "used_bytes": self.used_bytes(),
                "models": {
                    name: {
                        "reserved_bytes": self._reserved[name],
                        "path": self._paths[name],
                        # round 18: the artifact header's identity —
                        # an operator (or the swap admission below)
                        # tells an int8 artifact from fp32 without
                        # deserializing any program
                        "quantized": (self._info.get(name) or
                                      {}).get("quantized"),
                        "param_dtypes": (self._info.get(name) or
                                         {}).get("param_dtypes"),
                    }
                    for name in sorted(self._models)},
            }

    def _admit_locked(self, name, reserved, exclude=None):
        """Budget-gate + reservation, atomically: a passing admit
        records ``reserved`` under ``_pending`` so a concurrent admit
        sees it.  Caller must hold the lock."""
        used = self.used_bytes(exclude=exclude)
        if self.budget_bytes and used + reserved > self.budget_bytes:
            self.stats["hbm_rejected"] += 1
            ModelServer._telemetry_event(
                "fleet_model_reject", model=name, reserved=reserved,
                resident=used, budget=self.budget_bytes)
            raise ServeRejected(
                "hbm_budget",
                f"model {name!r} reserves {reserved} bytes; "
                f"{used} bytes already resident of a "
                f"{self.budget_bytes}-byte host budget")
        self._pending[name] = reserved

    def _size_artifact(self, path, info):
        """Reserved-bytes sizing for admission: the exported-program
        memory analysis for a dense artifact, the summed param bytes
        for a generative one (its programs only build at start).
        With the budget unlimited (the default) the sizing read gates
        nothing — skipped entirely, admit at 0 bytes."""
        if not self.budget_bytes:
            return 0, None
        if (info or {}).get("generative"):
            from .. import deploy

            params, _ = deploy.load_generative(path)
            flat = deploy._flatten_params(params)
            return sum(int(onp.asarray(a).nbytes)
                       for a in flat.values()), None
        return artifact_reserved_bytes(path)

    def _make_server(self, name, path, info, exp, kw):
        """Construct (not started) the server class the artifact's
        header identity asks for — a GenerativeServer adapter for a
        ``"generative": true`` export, the dense ModelServer
        otherwise.  One replica process serves both classes."""
        if (info or {}).get("generative"):
            return GenerativeHostServer(path, name=name,
                                        **{**self._server_kw, **kw})
        return ModelServer.from_artifact(
            path, exported=exp, name=name,
            **{**self._server_kw, **kw})

    def load(self, name, path, **kw):
        """Admit + start one artifact (budget-gated); returns the live
        server.  The admission read doubles as the warm handle: the
        server below re-verifies the CRC on its own load, so a torn
        artifact fails HERE, before anything is evicted or started."""
        info = _artifact_identity(path)
        reserved, exp = self._size_artifact(path, info)
        with self._lock:
            # name-claim + budget reservation in ONE lock scope: two
            # concurrent loads of the same name (or two models racing
            # the last budget bytes) cannot both pass
            if name in self._models or name in self._pending:
                raise MXNetError(f"model {name!r} already resident "
                                 "(use swap for an upgrade)")
            self._admit_locked(name, reserved)
        try:
            srv = self._make_server(name, path, info, exp, kw)
            srv._suppress_health_gauges = True  # the host aggregates
            srv.start(warm=True)
        except BaseException:
            with self._lock:
                self._pending.pop(name, None)
            raise
        with self._lock:
            self._pending.pop(name, None)
            self._models[name] = srv
            self._reserved[name] = reserved
            self._paths[name] = str(path)
            self._info[name] = info
            self._load_kw[name] = dict(kw)  # swaps must keep these
            self.stats["loads"] += 1
        ModelServer._telemetry_event(
            "fleet_model_load", model=name, reserved=reserved,
            resident=self.used_bytes(), budget=self.budget_bytes)
        return srv

    def unload(self, name):
        with self._lock:
            if name in self._pending:
                raise MXNetError(
                    f"model {name!r} has a load/swap in flight — "
                    "retry the unload once it resolves")
            srv = self._models.pop(name, None)
            self._reserved.pop(name, None)
            self._paths.pop(name, None)
            self._info.pop(name, None)
            self._load_kw.pop(name, None)
        if srv is None:
            raise MXNetError(f"model {name!r} not resident "
                             f"(resident: {sorted(self._models)})")
        srv.drain(timeout=10.0)
        srv.close()
        with self._lock:
            self.stats["unloads"] += 1
        ModelServer._telemetry_event("fleet_model_unload", model=name)

    def get(self, model=None):
        with self._lock:
            if model is None:
                if len(self._models) == 1:
                    return next(iter(self._models.values()))
                if "model" in self._models:
                    return self._models["model"]
                raise MXNetError(
                    "multi-model host needs an explicit model name "
                    f"(resident: {sorted(self._models)})")
            srv = self._models.get(model)
            if srv is None:
                raise MXNetError(
                    f"unknown model {model!r} "
                    f"(resident: {sorted(self._models)})")
            return srv

    # ------------------------------------------------------- serving
    def submit(self, x, deadline_ms=None, model=None):
        return self.get(model).submit(x, deadline_ms=deadline_ms)

    # ---------------------------------------------------------- swap
    def swap(self, model, path, probe_timeout=60.0):
        """Zero-downtime model swap: load ``path`` beside the live
        server, warm it, require ONE finite probe answer, then cut the
        routing pointer over between batches and drain the old server.
        Any failure before the cutover closes the new server and
        KEEPS the old one serving (rollback) — raised as MXNetError so
        the caller knows the fleet still runs the previous artifact.
        Returns the swap wall time in milliseconds."""
        faultsim.inject("fleet.swap")
        t0 = time.perf_counter()
        with self._lock:
            old = self.get(model)
            name = old.name
            if name in self._pending:
                raise MXNetError(
                    f"model {name!r} already has a load/swap in "
                    "flight")
            # claim the name NOW (zero bytes while the artifact is
            # sized): a concurrent load/swap/unload of it refuses
            # until this swap resolves — without the claim, an unload
            # landing during the warm probe would be silently
            # resurrected by the cutover below
            self._pending[name] = 0
            kw = dict(self._load_kw.get(name, {}))
        info = _artifact_identity(path)
        new = None
        try:
            # unlimited budget skips the sizing compile — it would sit
            # on the critical path of exactly the swap latency this
            # feature exists to minimize, gating nothing
            reserved, exp = self._size_artifact(path, info)
            with self._lock:
                # exclude=name: the swapped model's old and new
                # programs briefly co-reside by design (module
                # docstring); the reservation still blocks
                # CONCURRENT admits
                self._pending.pop(name)
                self._admit_locked(name, reserved, exclude=name)
            # per-model load() overrides (slo_ms, queue bounds, ...)
            # survive the upgrade — a swap changes the ARTIFACT, not
            # the model's admission contract
            new = self._make_server(name, path, info, exp, kw)
            new._suppress_health_gauges = True  # the host aggregates
            new.start(warm=True)
            probe = onp.zeros(new.item_shape, new.dtype)
            # the probe carries the swap's own budget, not the model's
            # SLO: a warm latency over the SLO must not shed it
            out = new.submit(probe, deadline_ms=probe_timeout * 1e3) \
                .result(timeout=probe_timeout)
            out = onp.asarray(out)
            if onp.issubdtype(out.dtype, onp.floating) \
                    and not onp.isfinite(out).all():
                raise MXNetError("warm probe returned non-finite "
                                 "outputs")
        except Exception as exc:
            if isinstance(exc, ServeRejected) \
                    and exc.reason == "hbm_budget":
                # the budget refusal never touched the live model:
                # structured passthrough, not a rollback.  Every
                # OTHER ServeRejected here came from the NEW server's
                # warm probe (a non-finite output rejects the probe
                # request) — that IS a failed swap attempt
                with self._lock:
                    self._pending.pop(name, None)
                raise
            if new is not None:
                new.close()
            with self._lock:
                self._pending.pop(name, None)
                self.stats["rollbacks"] += 1
            ModelServer._telemetry_event(
                "fleet_swap_rollback", model=name, path=str(path),
                error=repr(exc))
            raise SwapRolledBack(
                f"swap of {name!r} to {path!r} rolled back "
                f"({exc}); the previous artifact keeps serving") \
                from exc
        # cutover between batches: new submits route to the new
        # server the moment the pointer moves; the old server's
        # in-flight batches finish in its drain
        with self._lock:
            self._pending.pop(name, None)
            self._models[name] = new
            self._reserved[name] = reserved
            self._paths[name] = str(path)
            self._info[name] = info
            self.stats["swaps"] += 1
        gen_extra = {}
        if getattr(old, "generative", False):
            # the satellite-2 contract: in-flight decode sequences at
            # cutover ride out on the OLD version (no mid-sequence
            # version change); whether they all finished inside the
            # drain budget is REPORTED, never assumed — stragglers
            # are finished with the structured shutdown rejection at
            # close and may re-prefill on the new version
            gen_extra["gen_inflight_at_cutover"] = old.in_flight()
        drained = old.drain(timeout=30.0)
        if gen_extra:
            gen_extra["gen_drained"] = bool(drained)
            gen_extra["gen_inflight_at_close"] = old.in_flight()
        old.close()
        swap_ms = (time.perf_counter() - t0) * 1e3
        try:
            from .. import telemetry

            telemetry.count("fleet_swaps")
        except Exception:
            pass
        ModelServer._telemetry_event(
            "fleet_swap", model=name, path=str(path),
            swap_ms=round(swap_ms, 3), reserved=reserved, **gen_extra)
        return swap_ms

    # -------------------------------------------------------- health
    def health(self):
        with self._lock:
            servers = dict(self._models)
        per = {name: srv.health() for name, srv in servers.items()}
        ready = bool(per) and all(h["ready"] for h in per.values())
        live = bool(per) and all(h["live"] for h in per.values())
        payload = {
            "ready": ready, "live": live,
            "queue_depth": sum(h["queue_depth"] for h in per.values()),
            "inflight": sum(h["inflight"] for h in per.values()),
            "models": per,
        }
        # the host's AGGREGATE is the replica's probe truth: it wins
        # over the per-server writes health() just made
        ModelServer._telemetry_gauge("serve_ready", int(ready))
        ModelServer._telemetry_gauge("serve_live", int(live))
        return payload

    def metrics_text(self):
        from .frontend import _metrics_text

        with self._lock:
            servers = dict(self._models)
        h = self.health()
        counters = {"serve_requests": 0, "serve_shed": 0,
                    "serve_batches": 0, "serve_breaker_trips": 0}
        for srv in servers.values():
            counters["serve_requests"] += srv.stats["requests"]
            counters["serve_shed"] += srv.stats["shed"]
            counters["serve_batches"] += srv.stats["batches"]
            counters["serve_breaker_trips"] += \
                srv.stats["breaker_trips"]
        return _metrics_text(
            h["ready"], h["live"], counters,
            gauges={"serve_queue_depth": h["queue_depth"],
                    "serve_inflight": h["inflight"]})

    # ------------------------------------------------------ lifecycle
    def drain_all(self, timeout=30.0):
        with self._lock:
            servers = list(self._models.values())
        return all(srv.drain(timeout=timeout) for srv in servers)

    def close_all(self):
        with self._lock:
            servers = list(self._models.values())
            self._models.clear()
            self._reserved.clear()
            self._paths.clear()
        for srv in servers:
            srv.close()


# ======================================================== the router
class _Failover(Exception):
    """One routing attempt failed in a way a sibling can absorb."""


class _Replica:
    __slots__ = ("idx", "addr", "port", "proc", "state", "last_health",
                 "outstanding", "routed", "port_file", "probe_misses",
                 "log_path", "t_spawn")

    def __init__(self, idx, addr=None, port=None, proc=None,
                 port_file=None, log_path=None):
        self.idx = idx
        self.addr = addr or "127.0.0.1"
        self.port = port
        self.proc = proc
        self.port_file = port_file
        self.log_path = log_path
        self.t_spawn = time.monotonic()
        self.state = "starting" if port is None else "ready"
        self.last_health = {}
        self.outstanding = 0
        self.routed = 0
        self.probe_misses = 0

    @property
    def live(self):
        return self.state not in ("dead", "drained")


class FleetRouter:
    """Fault-tolerant front over N replica serving processes (module
    docstring).  Replicas are HTTP endpoints — either spawned worker
    processes (:meth:`spawn`) or endpoints attached by the caller
    (in-process frontends in tests, remote hosts in deployment).

    Parameters
    ----------
    endpoints : iterable of (addr, port)
        Pre-existing replicas to attach (not lifecycle-managed).
    slo_ms : float
        Fleet-level default deadline (None = ``MXNET_SERVE_SLO_MS``).
    probe_interval : float
        Seconds between health-probe sweeps.
    scale_up_depth / scale_down_depth / min_replicas / max_replicas
        Queue-depth-EWMA autoscaler: when the EWMA of per-ready-replica
        queue depth crosses ``scale_up_depth`` a replica is spawned
        (the round-12 resize, reshard-not-restart); below
        ``scale_down_depth`` one is SIGTERM-drained.  ``scale_up_depth
        None`` disables autoscaling (``resize()`` stays available).
    scale_ewma : float
        EWMA smoothing factor (None = ``MXNET_FLEET_SCALE_EWMA``).
    scale_cooldown_s : float
        Minimum seconds between autoscale decisions — a replica being
        spawned must get a chance to absorb load before the EWMA can
        demand another.
    """

    def __init__(self, endpoints=(), *, slo_ms=None,
                 probe_interval=0.25, scale_up_depth=None,
                 scale_down_depth=None, min_replicas=1,
                 max_replicas=8, scale_ewma=None,
                 scale_cooldown_s=10.0, name="fleet"):
        from ..config import get_env

        self.name = str(name)
        self.slo_ms = float(slo_ms if slo_ms is not None
                            else get_env("MXNET_SERVE_SLO_MS"))
        self.probe_interval = float(probe_interval)
        self._alpha = float(scale_ewma if scale_ewma is not None
                            else get_env("MXNET_FLEET_SCALE_EWMA"))
        self.scale_up_depth = scale_up_depth
        self.scale_down_depth = scale_down_depth
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.scale_cooldown_s = float(scale_cooldown_s)
        #: bring-up budget for ANY spawned replica (autoscale/resize
        #: spawns included, not just the initial _wait_ready): one
        #: wedged 'starting' child must not pause the autoscaler
        #: forever.  spawn() overrides it with its ready_timeout.
        self.bringup_timeout = 120.0
        self._lock = threading.RLock()
        self._replicas = []
        self._next_idx = 0
        self._spawn_spec = None
        self._dir = None
        self._stop = threading.Event()
        self._probe_thread = None
        self._probe_n = 0
        self._last_scale = 0.0
        self.queue_ewma = 0.0
        #: last artifact the WHOLE fleet committed to (rollback
        #: target of a refused rolling swap) and its header
        #: model_version (the freshness-monotonicity floor) — None
        #: until a spawn/swap stamps them
        self._prev_artifact = None
        self._committed_version = None
        self.stats = {"requests": 0, "completed": 0, "shed": 0,
                      "failovers": 0, "ejected": 0, "resizes": 0,
                      "swaps": 0, "swap_rollbacks": 0}
        for addr, port in endpoints:
            self._replicas.append(_Replica(self._next_idx, addr=addr,
                                           port=int(port)))
            self._next_idx += 1

    # ---------------------------------------------------- spawn mode
    @classmethod
    def spawn(cls, artifact, replicas=None, *, model="model",
              env=None, replica_env=None, runlog_dir=None,
              hbm_budget_mb=None, ready_timeout=120.0,
              coalesce_ms=1.0, drain_timeout=30.0, **kw):
        """Launch ``replicas`` worker processes serving ``artifact``
        (the fleet's lifecycle-managed mode) and return the router
        once every replica reports ready.

        ``env`` merges into every replica's environment;
        ``replica_env`` is ``{idx: {...}}`` per-replica overrides (the
        drills arm ``MXNET_FAULT_SPEC`` on exactly one replica this
        way); ``runlog_dir`` arms ``MXNET_RUNLOG`` per replica at
        ``<dir>/replica-<idx>.jsonl`` so the drill can assert each
        replica's retrace counter."""
        from ..config import get_env

        n = int(replicas if replicas is not None
                else get_env("MXNET_FLEET_REPLICAS"))
        if n < 1:
            raise MXNetError(f"fleet needs >= 1 replica, got {n}")
        router = cls(**kw)
        router.bringup_timeout = float(ready_timeout)
        router._dir = tempfile.mkdtemp(prefix="mxnet_tpu_fleet_")
        router._spawn_spec = {
            "artifact": str(artifact), "model": str(model),
            "env": dict(env or {}),
            "replica_env": {int(k): dict(v) for k, v in
                            (replica_env or {}).items()},
            "runlog_dir": str(runlog_dir) if runlog_dir else None,
            "hbm_budget_mb": hbm_budget_mb,
            "coalesce_ms": float(coalesce_ms),
            "drain_timeout": float(drain_timeout),
        }
        router._prev_artifact = str(artifact)
        v = (_artifact_identity(artifact) or {}).get("model_version")
        if v is not None:
            router._committed_version = int(v)
        try:
            for _ in range(n):
                router._spawn_replica()
            router._wait_ready(ready_timeout)
        except BaseException:
            # a half-up fleet must not leak worker processes
            router.close(timeout=10.0)
            raise
        router.start_probes()
        return router

    def _spawn_replica(self):
        spec = self._spawn_spec
        if spec is None:
            raise MXNetError(
                "this router attached existing endpoints — it cannot "
                "spawn replicas (use FleetRouter.spawn for a "
                "lifecycle-managed fleet)")
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
        port_file = os.path.join(self._dir, f"replica-{idx}.port")
        log_path = os.path.join(self._dir, f"replica-{idx}.log")
        cmd = [sys.executable, "-m", "mxnet_tpu.serving.fleet",
               "--artifact", f"{spec['model']}={spec['artifact']}",
               "--port", "0", "--port-file", port_file,
               "--slo-ms", str(self.slo_ms),
               "--coalesce-ms", str(spec["coalesce_ms"]),
               "--drain-timeout", str(spec["drain_timeout"])]
        if spec["hbm_budget_mb"] is not None:
            cmd += ["--hbm-budget-mb", str(spec["hbm_budget_mb"])]
        env = dict(os.environ)
        # a parent's armed fault spec must not leak into every child
        # (drills arm replicas EXPLICITLY via env/replica_env) — and
        # neither may its telemetry sinks: N replicas appending into
        # the parent's run log breaks the one-run-per-file contract,
        # and each child's change-triggered textfile rewrite would
        # clobber the parent's.  runlog_dir is the per-replica
        # replacement; env/replica_env can still opt a child in.
        for leak in ("MXNET_FAULT_SPEC", "MXNET_RUNLOG",
                     "MXNET_METRICS_TEXTFILE"):
            env.pop(leak, None)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in [os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
                env.get("PYTHONPATH")] if p)
        # round 20: identity + trace stamp BEFORE env/replica_env so a
        # drill can still override them.  The child's run_start carries
        # role=replica/rank=idx and its spans parent onto this spawn.
        _tracing.stamp_env(env, "replica", rank=idx)
        env.update(spec["env"])
        if spec["runlog_dir"]:
            env["MXNET_RUNLOG"] = os.path.join(
                spec["runlog_dir"], f"replica-{idx}.jsonl")
        env.update(spec["replica_env"].get(idx, {}))
        logf = open(log_path, "ab")
        try:
            proc = subprocess.Popen(cmd, stdout=logf, stderr=logf,
                                    env=env)
        finally:
            logf.close()  # the child holds its own fd
        rep = _Replica(idx, proc=proc, port_file=port_file,
                       log_path=log_path)
        with self._lock:
            self._replicas.append(rep)
        self._telemetry_event("fleet_spawn", replica=idx,
                              pid=proc.pid)
        return rep

    def _wait_ready(self, timeout):
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            self._probe_once(record=False)
            with self._lock:
                pending = [r for r in self._replicas
                           if r.live and r.state != "ready"]
                dead = [r for r in self._replicas
                        if r.state == "dead"]
            if not pending:
                if dead:
                    # ALL-or-error: a replica dying at bring-up must
                    # not silently hand the caller a smaller fleet
                    # than it asked for (resize() raises the same way)
                    raise MXNetError(
                        f"{len(dead)} replica(s) died during "
                        "bring-up " + self._death_report(dead))
                return
            time.sleep(0.1)
        raise MXNetError(
            f"fleet not ready within {timeout}s "
            + self._death_report([r for r in self._replicas
                                  if r.state != "ready"]))

    def _death_report(self, reps):
        notes = []
        for r in reps:
            rc = r.proc.poll() if r.proc else None
            tail = ""
            if r.log_path and os.path.exists(r.log_path):
                with open(r.log_path, "rb") as f:
                    tail = f.read()[-800:].decode(errors="replace")
            notes.append(f"replica {r.idx} state={r.state} rc={rc} "
                         f"log: ...{tail}")
        return "; ".join(notes) or "(no replicas)"

    # --------------------------------------------------- health probe
    def start_probes(self):
        if self._probe_thread is not None:
            return self
        self._stop.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop,
            name=f"mxnet_tpu-fleet-probe-{self.name}", daemon=True)
        self._probe_thread.start()
        return self

    def _probe_loop(self):
        while not self._stop.is_set():
            try:
                self._probe_once()
            except Exception:  # noqa: BLE001 — the probe loop is the
                pass           # router's heartbeat; it must not die
            self._stop.wait(self.probe_interval)

    def _probe_once(self, record=True):
        with self._lock:
            reps = [r for r in self._replicas if r.live]
        # probe CONCURRENTLY: serial sweeps would let one wedged
        # replica (accepts TCP, never answers — the 2 s per-probe
        # timeout) stall failure detection and the autoscaler signal
        # for the whole fleet
        threads = [threading.Thread(target=self._probe_replica,
                                    args=(rep,), daemon=True)
                   for rep in reps]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)  # > the 2 s http timeout: only a
            #                      truly stuck probe is abandoned
        # ---- the autoscaler's signal: queue depth per ready replica
        with self._lock:
            ready = [r for r in self._replicas if r.state == "ready"]
            depth = sum(r.outstanding
                        + int(r.last_health.get("queue_depth", 0))
                        for r in ready)
            x = depth / max(1, len(ready))
            self.queue_ewma = (self._alpha * x
                               + (1.0 - self._alpha) * self.queue_ewma)
            self._probe_n += 1
            sampled = record and self._probe_n % 20 == 0
        if record:
            # bring-up sweeps (_wait_ready) must not autoscale a
            # fleet that has not finished converging
            self._maybe_scale()
        if sampled:
            self._fleet_record("probe")

    def _probe_replica(self, rep):
        """One replica's health sweep (runs on a short-lived probe
        thread — every exit path just returns)."""
        if rep.proc is not None and rep.proc.poll() is not None:
            rc = rep.proc.poll()
            if rep.state == "draining":
                # a drained scale-down/close exit is CLEAN: the
                # router stopped routing before the SIGTERM
                rep.state = "drained"
                self._telemetry_event("fleet_drained",
                                      replica=rep.idx, rc=rc)
            else:
                self._eject(rep, f"process exited rc={rc}")
            return
        if rep.state == "starting" and time.monotonic() \
                - rep.t_spawn > self.bringup_timeout:
            # a child alive but wedged in bring-up (never wrote its
            # port file): without this budget it would pause
            # _maybe_scale's 'starting' gate forever
            if rep.proc is not None:
                try:
                    rep.proc.kill()
                except OSError:
                    pass
            self._eject(rep, "bring-up timed out after "
                             f"{self.bringup_timeout}s")
            return
        if rep.port is None and not self._read_port(rep):
            return
        try:
            status, health = http_call(rep.addr, rep.port, "GET",
                                       "/healthz", timeout=2.0)
        except Exception:  # connection refused / reset / timeout
            rep.probe_misses += 1
            self._bench(rep)
            # an attached endpoint (no proc to poll) that misses
            # several probes in a row is gone — eject it like a
            # dead process
            if rep.probe_misses >= 4 and rep.proc is None:
                self._eject(rep, "endpoint unreachable")
            return
        rep.probe_misses = 0
        if isinstance(health, dict):
            rep.last_health = health
        with self._lock:
            # check-and-promote under the lock: _drain_one flips to
            # 'draining' under it, and an unlocked promotion here
            # could resurrect a SIGTERM'd replica into the routing
            # pool mid-scale-down
            if rep.state in ("starting", "ready", "unready"):
                rep.state = "ready" if status == 200 else "unready"

    def _read_port(self, rep):
        pf = rep.port_file
        if not pf or not os.path.exists(pf):
            return False
        try:
            with open(pf) as f:
                rep.port = int(f.read().strip())
        except (OSError, ValueError):
            return False
        return True

    def _ready_replicas(self):
        with self._lock:
            return [r for r in self._replicas if r.state == "ready"]

    def _bench(self, rep):
        """Pull a replica out of the routing pool until a health probe
        re-verifies it — WITHOUT clobbering a draining/dead state (a
        scale-down drain must still be recorded as drained, not
        ejected).  Check-and-set under the lock: _drain_one flips
        'ready' -> 'draining' under it, and an unlocked write here
        could land after that flip."""
        with self._lock:
            if rep.state == "ready":
                rep.state = "unready"

    def _eject(self, rep, why):
        with self._lock:
            # guard-and-set under the lock: a dying replica is often
            # observed by several submit threads AND the probe loop
            # at once — exactly one of them ejects.  A replica the
            # probe loop already recorded as cleanly DRAINED must not
            # be re-counted as an ejection by a straggling request
            if rep.state in ("dead", "drained"):
                return
            rep.state = "dead"
            self.stats["ejected"] += 1
        self._telemetry_event("fleet_eject", replica=rep.idx,
                              reason=str(why))
        self._fleet_record("eject")

    # -------------------------------------------------------- routing
    def submit(self, x, deadline_ms=None, model=None):
        """Route one request (returns the output row as numpy).  Sheds
        raise :class:`ServeRejected` — structured, like the in-process
        server.  A replica that fails mid-request (death, open
        breaker, drain) is ejected/benched and the request retries on
        a sibling INSIDE its original deadline
        (``retry_call(deadline_sec=)``)."""
        faultsim.inject("fleet.route")
        budget_ms = self.slo_ms if deadline_ms is None \
            else float(deadline_ms)
        deadline = time.monotonic() + budget_ms / 1e3
        x = onp.asarray(x)
        with self._lock:
            self.stats["requests"] += 1
        self._telemetry_count("fleet_requests")
        # round-20 trace root: one fleet_request span per submit when
        # telemetry is armed (or a caller-bound context exists); each
        # routing attempt sends a child hop in the traceparent header
        # so the replica's spans link back here
        req_ctx = t_req0 = None
        if _tracing.enabled() or _tracing.current_context() is not None:
            parent = _tracing.current_context()
            req_ctx = parent.child() if parent is not None \
                else _tracing.mint()
            t_req0 = time.perf_counter()
        last = {"reason": "no_replica",
                "detail": "no ready replica to route to",
                "failover": False}
        tried = set()

        def attempt():
            if last["failover"]:
                # the PREVIOUS attempt's replica failure is being
                # retried now — count the failover at the start of
                # the retry, not in on_retry: retry_call may call
                # on_retry and then still give up on the deadline
                # without ever dispatching to a sibling
                with self._lock:
                    self.stats["failovers"] += 1
                self._telemetry_count("fleet_failovers")
            last["failover"] = False
            rep = self._pick(exclude=tried)
            if rep is None:
                raise _Failover
            tried.add(rep.idx)
            remaining_ms = (deadline - time.monotonic()) * 1e3
            if remaining_ms <= 0:
                last.update(reason="deadline",
                            detail="fleet budget exhausted before "
                                   "dispatch")
                raise _Failover
            hop = hdrs = t_hop0 = None
            if req_ctx is not None:
                hop = req_ctx.child()
                hdrs = {_tracing.TRACEPARENT_HEADER: hop.to_header()}
                t_hop0 = time.perf_counter()
            with self._lock:
                rep.outstanding += 1
            try:
                status, body = http_call(
                    rep.addr, rep.port, "POST", "/v1/predict",
                    body={"inputs": [x.tolist()],
                          "deadline_ms": remaining_ms,
                          "model": model},
                    timeout=remaining_ms / 1e3 + 5.0,
                    headers=hdrs)
            except Exception as exc:  # connection-level death
                if rep.proc is not None \
                        and rep.proc.poll() is not None:
                    # a DRAINING/DRAINED replica exiting is the clean
                    # scale-down path — the probe loop records it as
                    # drained; only an unexpected death ejects
                    if rep.state not in ("draining", "drained"):
                        self._eject(rep,
                                    f"died mid-request rc="
                                    f"{rep.proc.poll()}")
                else:
                    self._bench(rep)  # probe re-verifies
                last.update(reason="model_error",
                            detail=f"replica {rep.idx}: {exc!r}",
                            failover=True)
                raise _Failover from exc
            finally:
                with self._lock:
                    rep.outstanding -= 1
                    rep.routed += 1
            if status == 200:
                if hop is not None:
                    _tracing.emit_span("route_attempt", t_hop0,
                                       time.perf_counter(), hop,
                                       kind="client",
                                       replica=int(rep.idx))
                return onp.asarray(body["outputs"][0])
            reason = body.get("error", "model_error") \
                if isinstance(body, dict) else "model_error"
            detail = body.get("detail", "") \
                if isinstance(body, dict) else str(body)[:200]
            if reason in ("breaker_open", "draining", "shutdown"):
                # not routable until a probe says otherwise — the
                # ejection contract for an opened breaker
                self._bench(rep)
            last.update(reason=reason,
                        detail=f"replica {rep.idx}: {detail}",
                        # a REPLICA failure (died, 500, benched) is a
                        # failover when retried; queue_full/deadline/
                        # expired are back-pressure sheds, not replica
                        # failures — counting them would mask the real
                        # signal this metric exists for
                        failover=reason in ("model_error",
                                            "breaker_open",
                                            "draining", "shutdown"))
            raise _Failover

        with self._lock:
            n_live = sum(1 for r in self._replicas if r.live)
        try:
            out = retry_call(
                attempt, retry_on=(_Failover,),
                attempts=max(2, n_live + 1), base_delay=0.005,
                max_delay=0.05, jitter=0.2,
                deadline_sec=max(0.01,
                                 deadline - time.monotonic()))
        except _Failover:
            with self._lock:
                self.stats["shed"] += 1
            self._telemetry_count("fleet_shed")
            raise ServeRejected(last["reason"], last["detail"]) \
                from None
        with self._lock:
            self.stats["completed"] += 1
        if req_ctx is not None:
            _tracing.emit_span("fleet_request", t_req0,
                               time.perf_counter(), req_ctx,
                               kind="server", model=str(model or ""))
        return out

    def _pick(self, exclude=()):
        """Least-queue-depth routing: the ready replica with the
        fewest (router-local outstanding + last-probed queued)
        requests.  ``exclude`` holds replicas already tried for THIS
        request; when every ready replica has been tried the exclusion
        resets (a second try beats a shed)."""
        with self._lock:
            ready = [r for r in self._replicas if r.state == "ready"]
            fresh = [r for r in ready if r.idx not in exclude]
            pool = fresh or ready
            if not pool:
                return None
            return min(pool, key=lambda r: (
                r.outstanding
                + int(r.last_health.get("queue_depth", 0))))

    # ---------------------------------------------------- autoscaling
    def _maybe_scale(self):
        if self.scale_up_depth is None or self._spawn_spec is None:
            return
        now = time.monotonic()
        with self._lock:
            if now - self._last_scale < self.scale_cooldown_s:
                return
            live = [r for r in self._replicas
                    if r.live and r.state != "draining"]
            n = len(live)
            ready_n = sum(1 for r in live if r.state == "ready")
            ewma = self.queue_ewma
            if any(r.state == "starting" for r in live):
                # a spawned replica is still converging: give it a
                # chance to absorb (or shed) load before the EWMA can
                # demand another decision either way
                return
        if ewma > float(self.scale_up_depth) \
                and n < self.max_replicas:
            self._spawn_replica()
            with self._lock:
                self._last_scale = now
            self._resize_event(n, n + 1, "queue_ewma_high")
        elif self.scale_down_depth is not None \
                and ewma < float(self.scale_down_depth) \
                and ready_n > self.min_replicas:
            # the floor counts ROUTABLE replicas: with a sibling
            # benched (open breaker, missed probes) the drain would
            # take the only ready replica and the fleet would shed
            # 'no_replica' — exactly what scale-down must never do
            # the event is emitted only for a drain that actually
            # started — a momentarily-empty ready pool must not
            # record a resize that never happened
            if self._drain_one() is not None:
                with self._lock:
                    self._last_scale = now
                self._resize_event(n, n - 1, "queue_ewma_low")

    def resize(self, n):
        """Explicit resize to ``n`` replicas (the autoscaler's manual
        twin): spawns or SIGTERM-drains one replica at a time, routing
        untouched throughout."""
        n = int(n)
        if self._spawn_spec is None:
            raise MXNetError("resize needs a spawned fleet")
        if not self.min_replicas <= n <= self.max_replicas:
            raise MXNetError(
                f"resize to {n} outside the fleet bounds "
                f"[{self.min_replicas}, {self.max_replicas}]")
        spawns = 0
        while True:
            with self._lock:
                live = [r for r in self._replicas
                        if r.live and r.state != "draining"]
            if len(live) == n:
                return n
            if len(live) < n:
                if spawns >= n + 4:
                    # spawned children keep dying before counting as
                    # live (unreadable artifact, broken env): refuse
                    # with the evidence instead of churning processes
                    raise MXNetError(
                        f"resize to {n} gave up after {spawns} "
                        "spawn attempts: "
                        + self._death_report(
                            [r for r in self._replicas
                             if r.state == "dead"][-3:]))
                spawns += 1
                self._spawn_replica()
                self._resize_event(len(live), len(live) + 1,
                                   "explicit")
            else:
                if self._drain_one() is None:
                    # nothing ready to drain (every live replica is
                    # starting/unready): refuse rather than spin —
                    # the caller retries once the fleet converges
                    raise MXNetError(
                        f"cannot scale down to {n}: no ready replica "
                        f"to drain ({self.health()['per_replica']})")
                self._resize_event(len(live), len(live) - 1,
                                   "explicit")

    def _resize_event(self, old_n, new_n, trigger):
        """The round-12 composition: a serving resize is the SAME
        reshard-not-restart event training resizes emit — topology
        blocks diffed by ``reshard_verdict``, a ``resize`` run-log
        event, the ``reshards`` counter — so one dashboard reads both
        worlds."""
        from ..resilience import elastic

        verdict = elastic.reshard_verdict(
            elastic.topology_block(world_size=old_n,
                                   sharding="serving"),
            elastic.topology_block(world_size=new_n,
                                   sharding="serving"))
        with self._lock:
            self.stats["resizes"] += 1
        self._telemetry_count("fleet_resizes")
        self._telemetry_count("reshards")
        self._telemetry_event(
            "resize", old_world=old_n, new_world=new_n,
            reasons=verdict["reasons"], scope="serving_fleet",
            trigger=str(trigger),
            queue_ewma=round(self.queue_ewma, 3))
        self._fleet_record("resize")

    def _drain_one(self):
        """Scale down by one: the least-loaded ready replica leaves
        the routing pool FIRST, then gets SIGTERM — PreemptionDrain in
        the worker finishes its admitted work, so the fleet sheds
        nothing on the way down."""
        with self._lock:
            ready = [r for r in self._replicas if r.state == "ready"]
            if not ready:
                return None
            rep = min(ready, key=lambda r: r.outstanding)
            rep.state = "draining"
        self._telemetry_event("fleet_scale_down", replica=rep.idx)
        if rep.proc is not None:
            try:
                rep.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
        return rep

    # --------------------------------------------------- rolling swap
    def _served_identity(self, rep, model=None, timeout=5.0):
        """One replica's served artifact path (via ``/v1/models``) —
        what the post-swap consistency assertion compares across the
        fleet.  None when the replica cannot answer."""
        try:
            status, body = http_call(rep.addr, rep.port, "GET",
                                     "/v1/models", timeout=timeout)
        except Exception:
            return None
        if status != 200 or not isinstance(body, dict):
            return None
        models = body.get("models") or {}
        if model is None and len(models) == 1:
            entry = next(iter(models.values()))
        else:
            entry = models.get(model or "model")
        return entry.get("path") if isinstance(entry, dict) else None

    def rolling_swap(self, path, model=None, probe_timeout=120.0):
        """Upgrade the whole fleet to the artifact at ``path`` one
        replica at a time — each replica loads the new program beside
        the live one, warm-probes it, and cuts over between batches
        while its siblings keep serving.

        Commit/rollback protocol (round 18): a replica that REFUSES
        its swap while alive (bad artifact / failed warm probe — the
        frontend's non-200 answer) aborts the rollout and rolls the
        already-swapped replicas BACK to the previous artifact, so a
        partial failure can never leave the fleet straddling two
        versions.  A replica that dies mid-swap (connection-level
        failure) is ejected and the rollout continues — its siblings
        upgrade and its traffic fails over, exactly as before.  When
        the new artifact's header carries a ``model_version``, a swap
        below the last fully-committed version is refused outright
        (freshness monotonicity).  The result reports per-replica
        timings/errors plus ``committed`` / ``rolled_back`` and the
        post-rollout ``identities`` consistency check (every live
        replica must answer with ONE artifact path)."""
        t0 = time.perf_counter()
        meta = _artifact_identity(path) or {}
        version = meta.get("model_version")
        # round 20: the v2 header's trace_anchor is the trainer's
        # export-span context — parenting the swap span on it links
        # the serve-side cutover back to the training step that
        # produced these weights, across processes and hosts
        swap_ctx = None
        if _tracing.enabled():
            anchor = _tracing.from_header(meta.get("trace_anchor"))
            if anchor is not None:
                swap_ctx = anchor.child()
            else:
                cur = _tracing.current_context()
                swap_ctx = cur.child() if cur is not None \
                    else _tracing.mint()
        with self._lock:
            committed_version = self._committed_version
            prev_path = self._prev_artifact
        if version is not None and committed_version is not None \
                and int(version) < int(committed_version):
            self._telemetry_event(
                "fleet_swap_refused", path=str(path),
                version=int(version),
                committed_version=int(committed_version),
                reason="version_regression")
            raise MXNetError(
                f"rolling swap to {path!r} (model_version {version}) "
                f"would regress the fleet below the last committed "
                f"version {committed_version} — refused")
        per, errors = {}, {}
        rolled_back = []
        abort = False
        with self._lock:
            # future spawns (autoscale, resize) must serve the NEW
            # artifact — the rolling swap changes the fleet's desired
            # state, not just the replicas alive right now
            if self._spawn_spec is not None:
                self._spawn_spec["artifact"] = str(path)
            # every live replica is a target, not just the ready
            # ones: a replica benched by one missed probe (or an open
            # breaker) rejoins the pool later and must NOT rejoin
            # serving the previous artifact
            targets = [r for r in self._replicas
                       if r.live and r.state != "draining"]
        for rep in targets:
            if rep.port is None:
                # spawned before the swap, not up yet: it may come up
                # on the previous artifact — surface it, never hide it
                errors[rep.idx] = ("replica still starting; spawned "
                                   "before the swap")
                continue
            try:
                status, body = http_call(
                    rep.addr, rep.port, "POST", "/admin/swap",
                    body={"model": model, "path": str(path)},
                    timeout=probe_timeout)
            except Exception as exc:  # mid-swap death
                errors[rep.idx] = repr(exc)
                if rep.proc is not None \
                        and rep.proc.poll() is not None:
                    self._eject(rep, f"died mid-swap "
                                     f"rc={rep.proc.poll()}")
                continue
            if status == 200:
                per[rep.idx] = body["swap_ms"]
            else:
                # the replica is ALIVE and refused: the artifact is
                # bad for every sibling too — abort the rollout and
                # roll the swapped prefix back to one version
                errors[rep.idx] = f"{status}: {body}"
                abort = True
                break
        if abort:
            with self._lock:
                if self._spawn_spec is not None and prev_path:
                    self._spawn_spec["artifact"] = str(prev_path)
                self.stats["swap_rollbacks"] += 1
            self._telemetry_count("fleet_swap_rollbacks")
            for rep in targets:
                if rep.idx not in per or not prev_path:
                    continue
                try:
                    status, body = http_call(
                        rep.addr, rep.port, "POST", "/admin/swap",
                        body={"model": model, "path": str(prev_path)},
                        timeout=probe_timeout)
                except Exception as exc:
                    errors[rep.idx] = f"rollback failed: {exc!r}"
                    continue
                if status == 200:
                    rolled_back.append(rep.idx)
                    del per[rep.idx]
                else:
                    errors[rep.idx] = (f"rollback failed: {status}: "
                                       f"{body}")
            self._telemetry_event(
                "fleet_rolling_swap_rollback", path=str(path),
                prev=str(prev_path), rolled_back=sorted(rolled_back),
                errors=errors)
            self._fleet_record("swap_rollback")
        committed = not abort
        if committed:
            with self._lock:
                self._prev_artifact = str(path)
                if version is not None:
                    self._committed_version = int(version)
        # consistency assertion: after a commit OR a rollback every
        # live replica must report ONE artifact identity — a fleet
        # straddling two versions is the exact bug this protocol
        # exists to prevent, so check it, loudly
        with self._lock:
            live = [r for r in self._replicas
                    if r.live and r.state != "draining"
                    and r.port is not None]
        identities = {}
        for rep in live:
            ident = self._served_identity(rep, model=model)
            if ident is not None:
                identities[rep.idx] = ident
        consistent = len(set(identities.values())) <= 1
        if not consistent:
            self._telemetry_event(
                "fleet_swap_inconsistent", path=str(path),
                identities=identities)
        with self._lock:
            self.stats["swaps"] += 1
        self._telemetry_count("fleet_swaps")
        self._telemetry_event(
            "fleet_rolling_swap", path=str(path),
            swapped=sorted(per), errors=errors,
            committed=committed, version=version)
        self._fleet_record("swap")
        if swap_ctx is not None:
            _tracing.emit_span(
                "rolling_swap", t0, time.perf_counter(), swap_ctx,
                kind="internal", committed=bool(committed),
                version=int(version) if version is not None else None,
                replicas=len(per))
        return {"per_replica": per, "errors": errors,
                "committed": committed,
                "rolled_back": sorted(rolled_back),
                "identities": identities, "consistent": consistent,
                "version": version,
                "swap_ms": round((time.perf_counter() - t0) * 1e3, 3)}

    # ------------------------------------------------------ lifecycle
    def health(self):
        with self._lock:
            reps = list(self._replicas)
            return {
                "replicas": sum(1 for r in reps if r.live),
                "ready": sum(1 for r in reps if r.state == "ready"),
                "queue_ewma": round(self.queue_ewma, 4),
                "per_replica": {
                    r.idx: {"state": r.state, "port": r.port,
                            "outstanding": r.outstanding,
                            "routed": r.routed,
                            "queue_depth": int(
                                r.last_health.get("queue_depth", 0))}
                    for r in reps},
                "stats": dict(self.stats),
            }

    def close(self, timeout=30.0):
        """Stop probing, SIGTERM every spawned replica (they drain:
        admitted work finishes, exits are rc -15), reap, and clean the
        scratch dir.  Attached endpoints are left to their owners."""
        self._stop.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=5.0)
            self._probe_thread = None
        self._fleet_record("close")
        with self._lock:
            reps = [r for r in self._replicas if r.proc is not None]
        for rep in reps:
            if rep.proc.poll() is None:
                try:
                    rep.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + float(timeout)
        rcs = {}
        for rep in reps:
            left = max(0.1, deadline - time.monotonic())
            try:
                rcs[rep.idx] = rep.proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                rep.proc.kill()
                rcs[rep.idx] = rep.proc.wait(timeout=10.0)
            rep.state = "dead" if rep.state != "drained" else "drained"
        self._telemetry_event("fleet_close", rcs=rcs)
        if self._dir:
            import shutil

            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return rcs

    # ------------------------------------------------------ telemetry
    def _fleet_record(self, action):
        try:
            from .. import telemetry

            rl = telemetry.current()
            if rl is None:
                return
            with self._lock:
                # snapshot under the lock, WRITE outside it: the
                # run-log flush is disk IO and the submit hot path
                # takes this lock several times per request
                reps = list(self._replicas)
                snap = {
                    "replicas": sum(1 for r in reps if r.live),
                    "ready": sum(1 for r in reps
                                 if r.state == "ready"),
                    "queue_depth": sum(
                        r.outstanding
                        + int(r.last_health.get("queue_depth", 0))
                        for r in reps if r.state == "ready"),
                    "queue_ewma": self.queue_ewma,
                    "requests": self.stats["requests"],
                    "failovers": self.stats["failovers"],
                    "shed": self.stats["shed"],
                }
            rl.fleet(action=action, **snap)
        except Exception:
            pass

    # one swallow-all telemetry shim serves the whole serving stack —
    # ModelHost reuses these too (via ModelServer); a second copy
    # would drift
    _telemetry_count = staticmethod(ModelServer._telemetry_count)
    _telemetry_event = staticmethod(ModelServer._telemetry_event)


# ================================================== the replica worker
def replica_main(argv=None):
    """Entry point of one fleet replica process
    (``python -m mxnet_tpu.serving.fleet ...``): ModelHost + HTTP
    frontend on an ephemeral port (published through ``--port-file``),
    serving until SIGTERM/SIGINT, then draining through
    ``PreemptionDrain`` — admitted work finishes, the run log closes
    with its final counters, and the exit is the clean signal death
    (rc -15) the router's scale-down/close path expects."""
    import argparse

    from ..resilience.preempt import PreemptionDrain

    ap = argparse.ArgumentParser(description="fleet replica worker")
    ap.add_argument("--artifact", action="append", required=True,
                    help="model=path of a .mxje artifact (repeat for "
                         "multi-model residency)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--port-file", default=None)
    ap.add_argument("--hbm-budget-mb", type=float, default=None)
    ap.add_argument("--slo-ms", type=float, default=None)
    ap.add_argument("--coalesce-ms", type=float, default=1.0)
    ap.add_argument("--drain-timeout", type=float, default=30.0)
    args = ap.parse_args(argv)

    server_kw = {"coalesce_ms": args.coalesce_ms}
    if args.slo_ms is not None:
        server_kw["slo_ms"] = args.slo_ms
    host = ModelHost(hbm_budget_mb=args.hbm_budget_mb,
                     server_kw=server_kw)
    for spec in args.artifact:
        name, _, path = spec.partition("=")
        if not path:
            name, path = "model", name
        host.load(name, path)
    fe = ServeFrontend(host, port=args.port)
    fe.start()
    if args.port_file:
        # write-to-temp + rename: the router's port read can never
        # see a half-written number
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{fe.port}\n")
        os.replace(tmp, args.port_file)
    print(f"[replica] serving on {fe.port} pid={os.getpid()}",
          flush=True)
    with PreemptionDrain() as pd:
        while pd.requested is None:
            time.sleep(0.05)
        try:
            from .. import telemetry

            telemetry.event("serve_preempt", scope="fleet_replica",
                            signum=int(pd.requested))
        except Exception:
            pass
        host.drain_all(timeout=args.drain_timeout)
        fe.close()
        host.close_all()
        try:
            from .. import telemetry

            telemetry.close()  # run_end (final counters) hits disk
        except Exception:
            pass
        pd.reraise()


if __name__ == "__main__":
    replica_main()
