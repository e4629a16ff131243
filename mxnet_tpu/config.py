"""Environment-variable registry + declarative parameter structs.

Reference parity: SURVEY.md §5.6 — the ~100 ``MXNET_*``/``DMLC_*``
knobs read via dmlc::GetEnv (docs env_var.md) and the
``dmlc::Parameter`` declarative structs every op/iterator uses for
kwarg parsing, defaults, range checks and doc generation.

TPU-native: XLA owns scheduling/memory, so engine-thread and
memory-pool knobs are accepted for compatibility but documented as
no-ops; the live knobs configure the host-side data plane, profiler
autostart and distributed bootstrap.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional

from .base import MXNetError

__all__ = ["register_env", "get_env", "list_env", "describe_env",
           "compilation_cache_dir", "setup_compilation_cache",
           "ParamStruct", "field"]

_ENV: dict[str, "EnvVar"] = {}


@dataclasses.dataclass
class EnvVar:
    name: str
    default: Any
    type: Callable
    doc: str
    live: bool = True  # False = accepted for reference compat, no-op


def register_env(name, default, typ=str, doc="", live=True):
    _ENV[name] = EnvVar(name, default, typ, doc, live)
    return _ENV[name]


def get_env(name):
    """Typed read of a registered env var (dmlc::GetEnv analog)."""
    if name not in _ENV:
        raise MXNetError(f"env var {name} is not registered")
    ev = _ENV[name]
    raw = os.environ.get(name)
    if raw is None:
        return ev.default
    try:
        if ev.type is bool:
            return raw.lower() in ("1", "true", "yes", "on")
        return ev.type(raw)
    except (TypeError, ValueError) as e:
        raise MXNetError(f"invalid value {raw!r} for {name}") from e


def list_env():
    return sorted(_ENV)


def describe_env():
    """The env_var.md-style table, generated from the registry."""
    lines = ["| Variable | Default | Live | Description |",
             "|---|---|---|---|"]
    for name in list_env():
        ev = _ENV[name]
        lines.append(f"| {name} | {ev.default!r} | "
                     f"{'yes' if ev.live else 'compat no-op'} | "
                     f"{ev.doc} |")
    return "\n".join(lines)


# ----------------------------------------------------- the framework knobs
register_env("MXNET_CPU_WORKER_NTHREADS", 0, int,
             "Host decode/augment worker threads (0 = all cores); feeds "
             "ImageRecordIter preprocess_threads default.")
register_env("MXNET_TPU_PREFETCH_BUFFER", 4, int,
             "Batches kept ready ahead of the training loop "
             "(ImageRecordIter prefetch_buffer default).")
register_env("MXNET_IO_WORKERS", 0, int,
             "Decode/augment worker pool size behind ImageRecordIter/"
             "ImageDetRecordIter (round 17).  0 (default) preserves "
             "the single-producer-thread behavior; N>0 runs N workers "
             "behind a sequence-ordered emitter — batch assembly is "
             "by index plan, so worker count, respawns and stragglers "
             "never perturb which sample lands in which batch row.")
register_env("MXNET_IO_WORKER_RESPAWN", 2, int,
             "Respawn budget of the io worker pool: a worker that "
             "dies holding a batch or wedges past the per-batch "
             "deadline is replaced (its batch re-dispatched) at most "
             "this many times per iterator; exhausting the budget "
             "fails LOUDLY with the quarantine manifest attached.")
register_env("MXNET_IO_MAX_SKIP_FRAC", 0.1, float,
             "Quarantine ceiling: the fraction of a .rec shard's "
             "records that may be skipped (framing resyncs + "
             "unpack/decode quarantines) before the data plane "
             "refuses to continue — corrupt records degrade "
             "structurally (skip + counter + manifest) up to this "
             "bound, but the pipeline never silently trains on a "
             "substantially shrunken dataset.")
register_env("MXNET_PROFILER_AUTOSTART", False, bool,
             "Start the profiler at import (reference knob; wired to "
             "mx.profiler.set_state('run')).")
register_env("MXNET_PROFILER_MODE", "imperative", str,
             "Default profiler scope (symbolic/imperative/all).")
register_env("MXNET_ENFORCE_DETERMINISM", False, bool,
             "Force full fp32 matmul precision on the MXU (slower, "
             "reproducible to the ulp).")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000, int,
             "Flat-bucket split threshold (elements) for the sharded-"
             "server gradient exchange (optimizer_sharding='ps', "
             "parallel.zero): a bucket closes once the next parameter "
             "would push it past this many elements — the authentic "
             "ps-lite bound above which arrays are sliced across "
             "servers.  Fewer, larger buckets mean fewer collective "
             "launches; the collectives-budget CI gate runs at 4e6.")
register_env("MXNET_ZERO_STAGE", "", str,
             "ZeRO stage of the sharded-server exchange "
             "(optimizer_sharding='ps', parallel.zero.resolve_stage): "
             "'1' = classic ZeRO-1 (per-bucket all-reduce, grads "
             "replicated, optimizer state sharded), '2' = gradient "
             "shards (per-bucket reduce-scatter: the 'ps' program), "
             "'3' = parameter shards (params live sharded by bucket; "
             "the forward all-gathers each bucket and nothing gathers "
             "back).  A stage overrides the caller's "
             "zero_stage/optimizer_sharding and opts every meshed "
             "make_train_step in (for Module, whose updater is ZeRO-1 "
             "whatever the stage, it forces the sharded updater); '0' "
             "forces the replicated step and updater over every opt-in "
             "and over the kvstore='dist_sync' mapping; unset defers to "
             "the caller.  Unknown values raise.")
register_env("MXNET_COLLECTIVES_BUDGET", 8, int,
             "Per-step collective-launch budget the dp dryrun verdict "
             "gates against under optimizer_sharding='ps': at most "
             "this many reduce-scatters and all-gathers (and <=2 "
             "stray all-reduces) in the compiled step's HLO.")
register_env("MXNET_ENGINE_TYPE", "XLA", str,
             "Reference engine selector; the XLA async runtime is the "
             "only engine.", live=False)
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
             "Reference bulking knob; XLA fusion subsumes op bulking.",
             live=False)
register_env("MXNET_GPU_MEM_POOL_TYPE", "Naive", str,
             "Reference allocator strategy; XLA owns HBM pooling.",
             live=False)
register_env("JAX_COMPILATION_CACHE_DIR", "", str,
             "Persistent XLA compilation cache directory.  Every "
             "jitted program (train step, CachedOp, executor, "
             "predictor) is cached on disk keyed by HLO, so re-binds "
             "and bench recaptures skip recompilation entirely.  Set: "
             "that directory and no other.  Empty: the fixed, "
             "git-ignored <checkout>/.cache/xla.  The reference analog "
             "is the cuDNN algo registry persisting autotune winners "
             "across Bind calls.")
register_env("MXNET_CONV_1X1_DOT", False, bool,
             "Lower channel-last 1x1 convolutions to dot_general "
             "(native MXU matmul, no layout change).  Off by default; "
             "bench.py's --conv-ab switch measures the step-level A/B. "
             "When set explicitly it overrides any autotuned winner.")
register_env("MXNET_AUTOTUNE", 1, int,
             "In-step variant autotuner (mxnet_tpu.autotune; the "
             "cudnn_tune/cudnn_algoreg analog): 0 = off, 1 = consult "
             "the persisted winner cache and tune where sample data "
             "is provided, 2 = re-tune even on a cache hit "
             "(cudnn_tune='fastest' on every bind).")
register_env("MXNET_AUTOTUNE_CACHE_DIR", "", str,
             "Directory for autotune.json (persisted variant winners). "
             "Empty = beside the XLA compilation cache "
             "(config.compilation_cache_dir).")
register_env("MXNET_PALLAS_OPT", "", str,
             "Hand override for the 'fused_bucket_opt' autotune "
             "variant (round 14): 1 forces the Pallas fused-bucket "
             "optimizer kernels (ops/pallas_opt.py — prep + update + "
             "loss-scale check in one VMEM pass), 0 forces the jnp "
             "fused_bucket_update.  Unset: the in-step race decides "
             "per (shape, dtype, platform, mesh).")
register_env("MXNET_FLASH_ATTENTION", "", str,
             "Hand override for the 'flash_attention' autotune "
             "variant (round 14): naive/0, pallas/1, pallas_b256 "
             "(256x256 blocks), or pallas_pad (tile-align by padding "
             "+ masked keys).  Unset: cached winner, then the "
             "TPU+tiling heuristic.")
register_env("MXNET_DTYPE_LADDER", "", str,
             "The dtype-ladder knob (round 14; fp8 rung round 19). "
             "Unset/0: the ladder never races or applies (a dtype "
             "change is not numerics-neutral, so it is opt-in).  "
             "1/auto: make_train_step races fp32 vs bf16 compute "
             "in-step (compute_dtype=None steps only) and applies the "
             "cached per-program winner.  A comma roster like "
             "'fp32,bf16,fp8' races exactly those rungs — fp8 (e4m3 "
             "fwd / e5m2 grad, delayed per-tensor scaling in "
             "opt_state) only ever joins by being named.  "
             "bf16/fp32/fp8: hand-pin the arm.")
register_env("MXNET_FP8_AMAX_HISTORY", 16, int,
             "Length of the rolling amax history behind the fp8 "
             "rung's delayed scaling (round 19): each quantized "
             "tensor class (input / weights / grads) carries this "
             "many steps of observed |t|_inf in opt_state['_fp8'], "
             "and the next step's scale is fp8_max / (2 * max "
             "(history)) — in-graph, no host sync "
             "(ops/pallas_opt.fp8_delayed_scale).")
register_env("MXNET_BNRELUCONV_VARIANT", "", str,
             "Hand override for the 'pallas_bnreluconv' autotune "
             "variant: stock (unfused layer path), jnp (fused op, jnp "
             "backward), pallas (fused op, one-pass Pallas backward). "
             "Unset: cached per-shape winner, then "
             "MXNET_FUSED_BNRELUCONV.")
register_env("MXNET_DEVICE_FEED", True, bool,
             "Async double-buffered device feed: DataLoader / "
             "Module.fit / bench.py wrap their batch source in "
             "io.DeviceFeedIter so host batch assembly and the "
             "host->HBM transfer overlap the running step.  0 restores "
             "the blocking per-step device_put.")
register_env("MXNET_DEVICE_FEED_DEPTH", 2, int,
             "Batches DeviceFeedIter keeps already device_put (and "
             "mesh-sharded) ahead of the consumer.")
register_env("MXNET_EXEC_DONATE", True, bool,
             "Donate dead executor state buffers (updated BatchNorm "
             "moving stats in the CachedOp/Executor jit paths) back to "
             "XLA for in-place reuse — the TPU-native analog of the "
             "reference's static_alloc memory sharing.")
register_env("MXNET_PS_DEADLINE_SEC", 600.0, float,
             "Parameter-server wait deadline (seconds) for sync "
             "round-skew waits and pull/spull readiness waits — was "
             "four hard-coded 600 s constants in _ps.py.  Lower it so "
             "fault-injection tests fail in seconds; raise it for "
             "slow-merge real deployments.")
register_env("MXNET_FAULT_SPEC", "", str,
             "Deterministic fault injection spec for "
             "resilience.faultsim, e.g. "
             "'ckpt.write:crash@3;ps.push:delay=2.0@7' — "
             "point:action[=value]@hits clauses armed by per-point "
             "hit count.  Empty = disarmed (counters only).")
register_env("MXNET_BAD_STEP_LIMIT", 0, int,
             "Step-level NaN/Inf guard: >0 arms it — a non-finite "
             "step is skipped (params/optimizer state held, like "
             "dynamic loss scaling) and after this many CONSECUTIVE "
             "bad steps Module.fit restores the last good checkpoint "
             "and raises a diagnostic error.  0 disables the guard "
             "(no per-step device sync on the fast path).")
register_env("MXNET_CKPT_KEEP", 3, int,
             "Checkpoint versions Module.fit's internal manager "
             "retains (resilience.checkpoint keep_n); older "
             "params/states/manifest files are pruned after each "
             "save.  Explicit CheckpointManager users choose their "
             "own keep_n (None = keep all).")
register_env("MXNET_FEED_JOIN_TIMEOUT_SEC", 10.0, float,
             "Bound on DeviceFeedIter.close()'s producer-thread join: "
             "a wedged producer is abandoned (daemon) after this many "
             "seconds so a preemption drain can never hang fit "
             "teardown.")
register_env("MXNET_RUNLOG", "", str,
             "Path of the per-step JSONL run log (telemetry.RunLog). "
             "Empty = telemetry off entirely: every wire point takes "
             "the no-op fast exit and the fit loop performs no "
             "per-step device syncs.  Set it and every subsystem "
             "(step timing, device feed, compile/retrace causes, "
             "checkpoints, PS retries, NaN guard, fault injections) "
             "reports into one line-buffered JSONL file, plus a crash "
             "flight recorder at <path>.flight.json.")
register_env("MXNET_TELEMETRY_SAMPLE", 25, int,
             "Device-sync sampling period for telemetry: the fit loop "
             "reads the loss/metric (one device sync) only every this "
             "many steps; unsampled step records keep wall timing but "
             "loss=null so the hot path stays async.")
register_env("MXNET_FLIGHTREC_DEPTH", 64, int,
             "Crash flight recorder ring depth: the last N step "
             "records (plus config/env/compile fingerprints) dumped "
             "atomically on SIGTERM drain, NaN-abort, fault-injection "
             "crash or an unhandled exception inside Module.fit.  "
             "0 disables the recorder (run log still written).")
register_env("MXNET_WATCHDOG_SEC", 0.0, float,
             "Hang watchdog (telemetry.Watchdog): >0 arms a background "
             "thread per bench phase / per Module.fit that, when the "
             "heartbeat goes quiet for this many seconds — even with "
             "the main thread blocked inside an uninterruptible XLA "
             "call — appends an all-thread faulthandler stack dump, "
             "flushes the crash flight recorder with reason 'stall', "
             "and emits a 'watchdog' run-log record.  It observes, it "
             "never kills.  0 (default) = no thread, zero hot-path "
             "cost.")
register_env("MXNET_NUMERICS", False, bool,
             "In-graph numerics monitor (telemetry.numerics, Monitor "
             "2.0): compile per-gradient summary reductions "
             "(l2/min/max/NaN/Inf counts/zero fraction) into the "
             "train step and record sampled 'tensor_stats' run-log "
             "records — so a NaN step is EXPLAINED (which tensor, "
             "which step) before the bad-step guard aborts.  Off by "
             "default: the traced program is bit-identical to a build "
             "without the monitor.")
register_env("MXNET_NUMERICS_SAMPLE", 0, int,
             "Steps between numerics-monitor tensor_stats emissions "
             "(each costs one device readback of the summary "
             "vectors).  0 = follow MXNET_TELEMETRY_SAMPLE.")
register_env("MXNET_METRICS_TEXTFILE", "", str,
             "Prometheus-textfile export path (node_exporter textfile "
             "collector convention): telemetry counters + last "
             "throughput/loss, atomically rewritten on every sampled "
             "step.  Empty = off.")
register_env("MXNET_TRACE_CONTEXT", "", str,
             "Inbound W3C traceparent stamp "
             "('00-<32hex trace>-<16hex span>-01') set by a spawner "
             "(fleet replica launch, online-loop trainer, healing "
             "relaunch) so the child's spans parent onto the spawn "
             "(telemetry.tracing).  Empty = this process roots its "
             "own traces.", live=False)
register_env("MXNET_PROCESS_ROLE", "", str,
             "Process identity stamped by spawners into the child's "
             "run_start record (trainer|replica|router|io_worker|"
             "bench|fit) — the track-group label tools/tracemerge.py "
             "uses for the merged timeline.", live=False)
register_env("MXNET_PROCESS_RANK", "", str,
             "Numeric rank within the role (replica index, trainer "
             "attempt), stamped next to MXNET_PROCESS_ROLE into "
             "run_start.", live=False)
register_env("MXNET_ELASTIC", False, bool,
             "Elastic multi-host runtime (resilience.elastic): arms "
             "runtime.init_distributed()/elastic_init() multi-process "
             "bring-up over jax.distributed, dp x tp meshes spanning "
             "hosts, topology-stamped checkpoints, and reshard-on-"
             "resize resume — a job resumed at a different world size "
             "re-plans buckets and re-shards optimizer state instead "
             "of dying.")
register_env("MXNET_COORDINATOR", "", str,
             "jax.distributed coordinator address as host:port "
             "(process 0 binds it).  Empty falls back to the DMLC_* "
             "launcher contract (DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT "
             "when DMLC_NUM_WORKER > 1); unresolvable = single-process "
             "bring-up.")
register_env("MXNET_NUM_PROCESSES", 0, int,
             "Process count of the elastic job (0 = fall back to "
             "DMLC_NUM_WORKER, then single-process).")
register_env("MXNET_PROCESS_ID", -1, int,
             "This process's id in the elastic job (-1 = fall back to "
             "DMLC_WORKER_ID).")
register_env("MXNET_DIST_INIT_ATTEMPTS", 4, int,
             "Bounded-retry attempts around jax.distributed.initialize "
             "in elastic_init (backoff + jitter via resilience.retry; "
             "the dist.init fault point fires inside every attempt).")
register_env("MXNET_DIST_INIT_TIMEOUT_SEC", 120.0, float,
             "Total time budget (seconds) for elastic_init's "
             "initialize retry loop — the deadline_sec cap, so attempt "
             "counts cannot overshoot the bring-up SLA once backoff "
             "grows.")
register_env("MXNET_PEER_TIMEOUT_SEC", 10.0, float,
             "Peer liveness timeout (resilience.healing): a peer "
             "whose heartbeat file goes stale for this many seconds "
             "is declared DEAD by every survivor's FailureDetector "
             "(a same-host peer whose pid vanished is declared dead "
             "immediately — the SIGKILL fast path).  Also sets the "
             "Heartbeater's default beat interval (timeout/4).")
register_env("MXNET_HEARTBEAT_DIR", "", str,
             "Shared directory of per-rank heartbeat files "
             "(resilience.healing).  Set on a multi-process elastic "
             "job and Module.fit arms the self-healing loop: this "
             "rank beats, the failure detector polls at step "
             "boundaries, and a declared peer death fires the "
             "emergency checkpoint + PeerDeadError instead of "
             "wedging in a collective.  Empty = healing unarmed.")
register_env("MXNET_CKPT_ASYNC", True, bool,
             "Snapshot checkpoints write asynchronously "
             "(CheckpointManager.save_async: device->host capture at "
             "the step boundary, serialization + atomic write on a "
             "background thread with a bounded back-pressure queue). "
             "0 forces the MXNET_SNAPSHOT_EVERY cadence writes "
             "synchronous — the A/B arm and a debugging escape "
             "hatch.")
register_env("MXNET_SNAPSHOT_EVERY", 0, int,
             "Batches between async snapshot checkpoints in "
             "Module.fit (needs checkpoint=).  0 (default) keeps the "
             "epoch-boundary-only cadence; N>0 makes the recovery "
             "point at most N batches old — the freshest snapshot is "
             "also what an emergency checkpoint (peer death, "
             "watchdog abort) flushes without any collective.")
register_env("MXNET_HEAL_MAX_RELAUNCH", 2, int,
             "Respawn bound of the self-healing supervisor "
             "(python -m mxnet_tpu.resilience.healing --relaunch): a "
             "training command dying with a healable status (peer "
             "death rc 83, any signal kill, the faultsim crash 87) "
             "is relaunched at most this many times with "
             "MXNET_HEAL_ATTEMPT exported; anything else is final.")
register_env("MXNET_WATCHDOG_ABORT", False, bool,
             "Hang-watchdog escalation (round 16, default OFF — the "
             "observe-only contract is unchanged): after max_dumps "
             "stall dumps with the heartbeat still dead a full "
             "timeout later, flush the flight ring + the emergency "
             "checkpoint (freshest snapshot) and os._exit(85), so a "
             "permanently wedged job is rescheduled instead of "
             "burning its whole wall budget.")
register_env("MXNET_SERVE_SLO_MS", 100.0, float,
             "Default per-request deadline (milliseconds) of the "
             "serving runtime (mxnet_tpu.serving.ModelServer): a "
             "submit() without an explicit deadline_ms gets this SLO. "
             "Admission control sheds requests the latency EWMA says "
             "cannot finish inside it.")
register_env("MXNET_SERVE_QUEUE_DEPTH", 256, int,
             "Serving request-queue bound: submits beyond this many "
             "waiting requests are rejected with a structured "
             "ServeRejected(reason='queue_full') instead of growing "
             "an unbounded backlog.")
register_env("MXNET_SERVE_MAX_INFLIGHT", 0, int,
             "Bound on admitted-but-unfinished serving requests "
             "(queued + in the running batch).  0 = queue depth plus "
             "one max-size batch.")
register_env("MXNET_SERVE_BREAKER_LIMIT", 3, int,
             "Serving circuit breaker: after this many CONSECUTIVE "
             "model-invocation failures (exceptions or non-finite "
             "outputs — the bad-step machinery's serving analog) the "
             "breaker opens: requests get fast structured rejections "
             "while the batcher re-warms on probe batches; a probe "
             "success closes it.")
register_env("MXNET_FLEET_REPLICAS", 2, int,
             "Default replica-process count of a spawned serving "
             "fleet (serving.FleetRouter.spawn); the queue-depth "
             "autoscaler grows/shrinks from here within its "
             "min/max bounds.")
register_env("MXNET_FLEET_PORT", 0, int,
             "Default bind port of the serving HTTP frontend "
             "(serving.ServeFrontend); 0 = ephemeral (replica "
             "workers publish the chosen port through their "
             "--port-file).")
register_env("MXNET_FLEET_HBM_BUDGET_MB", 0.0, float,
             "Per-host model-residency budget in MiB for "
             "serving.ModelHost: a .mxje artifact is admitted only "
             "if its describe_program() memory_analysis reserved "
             "bytes fit next to the resident models, else a "
             "structured ServeRejected(reason='hbm_budget').  "
             "0 = unlimited.")
register_env("MXNET_QUANTIZE", "", str,
             "Hand override of the quantized-inference adoption "
             "race (mxnet_tpu.quantization; autotune variant ops "
             "quantized_conv/quantized_fc): 0/off/fp32 pins every "
             "rewritten layer to its fp32 fallback arm, 1/on/int8 "
             "pins the int8 program, fp8 pins the fp8 program "
             "(e4m3 operands, f32 accumulation — round 19).  "
             "Unset/auto: the in-step race's persisted winner "
             "decides per (op, shape, platform).")
register_env("MXNET_QUANT_CALIB_MODE", "naive", str,
             "Default calibration mode of quantization.calibrate: "
             "'naive' (running min/max per observed tensor) or "
             "'entropy' (KL-divergence-optimal symmetric threshold "
             "over an absolute-value histogram — the reference's "
             "calib_mode='entropy' contract, robust to rare "
             "outliers).")
register_env("MXNET_QUANT_CALIB_BATCHES", 10, int,
             "Default number of calibration batches "
             "quantization.calibrate folds through the range "
             "collector when the caller does not pass num_batches.")
register_env("MXNET_KV_PAGE_TOKENS", 16, int,
             "Tokens per KV-cache page of the generative decode "
             "server (serving.kvcache.PagedKVPool): sequences hold "
             "ceil(tokens/page_tokens) pages, so smaller pages waste "
             "less tail HBM per sequence but grow the page table the "
             "decode step walks.")
register_env("MXNET_KV_POOL_BUDGET", 4194304, int,
             "HBM byte budget of the paged KV-cache pool "
             "(serving.kvcache.PagedKVPool), the generative analog of "
             "MXNET_FLEET_HBM_BUDGET_MB: the pool sizes its physical "
             "page count to fit under this many bytes and admission "
             "is by TOKEN budget (prompt + max_new reserved up "
             "front), not request count.")
register_env("MXNET_DECODE_SLOTS", 8, int,
             "Decode-slot capacity of the generative server "
             "(serving.generate.GenerativeServer): the token-level "
             "continuous-batching step is compiled ONCE over this "
             "fixed slot tensor; sequences are admitted/evicted by "
             "in-place slot updates, never by retrace.")
register_env("MXNET_KV_DTYPE", "float32", str,
             "KV-cache storage dtype of the generative server: "
             "'float32' or 'int8' (per-(token, head) symmetric "
             "scales riding the quantization/ machinery).  int8 is "
             "adopted only if the warmup agreement probe clears the "
             "output-agreement floor, else the pool falls back to "
             "fp32 and stats['kv_dtype_effective'] says so.")
register_env("MXNET_PAGED_ATTENTION", "", str,
             "Hand override for the 'paged_decode_attention' autotune "
             "variant (round 17): gather/0 (materialize the page "
             "table's K/V then one fused softmax) or paged/1 (page-"
             "blockwise online-softmax walk).  Unset: the cached "
             "winner from the generative server's warmup race.")
register_env("MXNET_FLEET_SCALE_EWMA", 0.2, float,
             "EWMA smoothing factor of the fleet autoscaler's "
             "queue-depth signal (serving.FleetRouter): each health-"
             "probe sweep folds the per-ready-replica queue depth in "
             "with this weight; crossing scale_up_depth/"
             "scale_down_depth triggers the reshard-not-restart "
             "resize.")
register_env("MXNET_ONLINE_EXPORT_STEPS", 10, int,
             "Export cadence of the online learning loop "
             "(online.OnlineLoop): every N trainer steps the loop "
             "checkpoints, exports a v2 .mxje artifact stamped with "
             "the monotonic model version + stream cursor, and "
             "rolling-swaps it into the serving fleet.")
register_env("MXNET_FRESHNESS_SLO_MS", 60000.0, float,
             "Freshness SLO of the online loop: maximum allowed "
             "stream-sample-to-served-model latency.  Each committed "
             "swap measures newest-ingested-sample-time -> fleet-"
             "commit-time; p99 over the fault-free windows must stay "
             "under this bound (gated in benchdiff, violations "
             "counted loudly in telemetry).")
register_env("DMLC_NUM_WORKER", 1, int,
             "Distributed worker count (tools/launch.py contract).")
register_env("DMLC_WORKER_ID", 0, int, "This worker's rank.")
register_env("DMLC_PS_ROOT_URI", "127.0.0.1", str,
             "Coordinator address (worker 0).")
register_env("DMLC_PS_ROOT_PORT", "9091", str, "Coordinator port.")


# ------------------------------------------- persistent compilation cache
_CC_STATE = {"dir": None}

#: where the caches live when JAX_COMPILATION_CACHE_DIR does not say:
#: one fixed, git-ignored directory inside the checkout.  The path is
#: part of the cache key, so it is never a temporary, per-pid or
#: per-run name.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "xla")


def compilation_cache_dir():
    """The one directory the persistent caches use (XLA programs, and
    ``autotune.json`` beside them): ``JAX_COMPILATION_CACHE_DIR`` if
    set, and then no other; else ``<checkout>/.cache/xla``."""
    return get_env("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def setup_compilation_cache():
    """Enable jax's persistent compilation cache at
    :func:`compilation_cache_dir` and return that directory.

    Called by bench.py, ``Module.bind``, ``make_train_step``, the
    parallel predictor and chip_smoke.py, so a recapture/re-bind of an
    already-seen program costs a disk read instead of an XLA compile
    (the cuDNN algo-registry persistence analog,
    src/operator/nn/cudnn/cudnn_algoreg-inl.h).

    The min-compile-time/min-entry-size thresholds are dropped to zero
    so even small programs (the smoke-bench net, the K1 loop) hit the
    cache — bench recapture robustness matters more here than cache
    hygiene.
    """
    p = compilation_cache_dir()
    if _CC_STATE["dir"] == p:
        return p  # already active — config.update churn is not free
    import jax

    os.makedirs(p, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", p)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the scopes a program names its operations by (mx_forward, a
    # block's name, ...) are metadata, which jax leaves out of the
    # cache key unless told: an executable cached before a scope was
    # added or renamed would be loaded with its old names, and every
    # trace of it would show those
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    _CC_STATE["dir"] = p
    return p


# ------------------------------------------------------------ ParamStruct
_MISSING = object()


def field(default=_MISSING, *, doc="", low=None, high=None, choices=None):
    """Declare one parameter (DMLC_DECLARE_FIELD analog)."""
    return {"default": default, "doc": doc, "low": low, "high": high,
            "choices": choices}


class ParamStruct:
    """Declarative parameter struct (dmlc::Parameter analog).

    Subclasses declare fields as class attributes via ``field()``;
    ``__init__(**kwargs)`` parses with defaults/range/choice checks and
    ``describe()`` generates the doc table — the same triple duty the
    reference structs serve (parse, validate, document).
    """

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        cls._fields = {}
        for base in reversed(cls.__mro__):
            for k, v in vars(base).items():
                if isinstance(v, dict) and "default" in v and "doc" in v:
                    cls._fields[k] = v

    def __init__(self, **kwargs):
        for name, spec in self._fields.items():
            if name in kwargs:
                val = kwargs.pop(name)
            elif spec["default"] is not _MISSING:
                val = spec["default"]
            else:
                raise MXNetError(
                    f"{type(self).__name__}: required parameter "
                    f"{name!r} missing")
            if spec["low"] is not None and val < spec["low"]:
                raise MXNetError(
                    f"{type(self).__name__}.{name}={val} below minimum "
                    f"{spec['low']}")
            if spec["high"] is not None and val > spec["high"]:
                raise MXNetError(
                    f"{type(self).__name__}.{name}={val} above maximum "
                    f"{spec['high']}")
            if spec["choices"] is not None and val not in spec["choices"]:
                raise MXNetError(
                    f"{type(self).__name__}.{name}={val!r} not in "
                    f"{spec['choices']}")
            setattr(self, name, val)
        if kwargs:
            raise MXNetError(
                f"{type(self).__name__}: unknown parameters "
                f"{sorted(kwargs)}")

    @classmethod
    def describe(cls):
        lines = [f"Parameters of {cls.__name__}:"]
        for name, spec in cls._fields.items():
            d = "" if spec["default"] is _MISSING else \
                f" (default {spec['default']!r})"
            lines.append(f"  {name}{d}: {spec['doc']}")
        return "\n".join(lines)

    def as_dict(self):
        return {k: getattr(self, k) for k in self._fields}
