#!/usr/bin/env bash
# CI entrypoints (reference: ci/docker/runtime_functions.sh) — each
# function is one matrix cell; the tiers mirror pytest.ini markers.
set -euo pipefail

unittest_cpu_unit() {
    # fast correctness gate (<60 s)
    python -m pytest -m unit -q
}

unittest_cpu_train() {
    # training loops / model zoo / ONNX (~12 min)
    python -m pytest -m train -q
}

unittest_cpu_dist() {
    # multi-process jax.distributed workers (reference:
    # launch.py -n 3 --launcher local dist_sync_kvstore.py)
    python -m pytest -m dist -q
}

multichip_dryrun() {
    # the five-axis parallelism compile check on a virtual 8-dev mesh
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python __graft_entry__.py
}

sanity_bench() {
    # the headline bench, on a machine with a TPU (prints one JSON
    # line; heartbeats on stderr, internal deadline degrades instead of
    # dying; exits non-zero on any other platform or when a phase
    # raised — see bench.py)
    python bench.py
}

sanity_bench_smoke() {
    # full bench control flow on CPU in seconds; ALSO run inside
    # tier-1 (tests/test_bench_smoke.py) so a silent-hang regression
    # in the harness turns the unit suite red
    python bench.py --smoke
}

resilience_smoke() {
    # the fault-spec suite on CPU in seconds: atomic-checkpoint crash
    # safety (injected ckpt.write:crash), SIGTERM drain + bit-exact
    # resume_from, NaN-guard skip/abort/restore, PS client retry with
    # backoff + MXNET_PS_DEADLINE_SEC, DeviceFeedIter close/join
    # bounds.  Also collected by tier-1, so a regression turns the
    # unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q
}

opperf_smoke() {
    # per-op benchmark smoke on CPU: a representative slice of the
    # curated tables — including the r05 per-op input registries
    # (optimizer updates, zero-input samplers, npi tail, quantized,
    # detection) and the round-9 bucketed flat-tensor optimizer rows
    # (_fused_bucket_*, the multi_mp_sgd/multi_lars analog the
    # sharded-server exchange runs per step) — so expanded op coverage
    # keeps producing a committed OPPERF_*.jsonl artifact instead of
    # silently lapsing.  One JSON line per op lands in
    # OPPERF_smoke.jsonl (diffable across PRs).
    # round 18: the curated _contrib_quantized_{conv,fully_connected}
    # + _contrib_quantize_v2/_contrib_requantize rows run beside their
    # fp32 counterparts (Convolution, FullyConnected), so the
    # int8-vs-fp32 per-op ratio is visible in the benchdiff table.
    # round 16 (ZeRO stages): reduce_scatter/all_gather time the
    # bucket WIRE at the same 1M-element flat shape as the
    # _fused_bucket_* update rows (1-device copy floor on this smoke)
    JAX_PLATFORMS=cpu python benchmark/opperf.py --runs 8 --ops \
dot,Convolution,BatchNorm,FullyConnected,softmax,SyncBatchNorm,\
_contrib_BNReluConv,sgd_update,adam_update,multi_lars,\
_fused_bucket_sgd_mom_update,_fused_bucket_adam_update,\
_fused_bucket_lars_update,_pallas_bucket_sgd_mom_update,\
_pallas_bucket_adam_update,_pallas_bucket_lars_update,\
reduce_scatter,all_gather,\
_random_uniform,\
_npi_interp,_npi_full_like,_contrib_quantize,_contrib_quantize_v2,\
_contrib_requantize,_contrib_quantized_conv,\
_contrib_quantized_fully_connected,MultiBoxPrior \
        | tee OPPERF_smoke.jsonl
}

zero_smoke() {
    # ZeRO stage-ladder gate on the virtual 8-dev CPU mesh, seconds:
    # the stage 1/2/3 bit-identity drill over sgd/sgd-mom/adam/lars
    # (stage 3's AD-transposed reduce-scatter must equal stage 2's
    # explicit psum_scatter EXACTLY over flat buckets; where a
    # leaf-shaped bucket rides the ring, stages 1 and 2 exactly and
    # stage 3 within the sum's order), the RS+AG bytes <= 1.05x
    # analytic budget, per-chip param bytes = total/N, the compiled
    # forward's per-bucket all-gather/compute interleave + Perfetto
    # export, the stage-salted fingerprint refusing a stage-2 resume,
    # and the parameter-shard checkpoint round-trip.  Also collected
    # by tier-1 (tests/test_zero_stages.py), so a regression turns
    # the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_zero_stages.py -q
}

telemetry_smoke() {
    # observability gate on CPU in seconds: a smoke fit with
    # MXNET_RUNLOG armed must emit schema-valid JSONL (step records
    # with feed-wait/collective fields, compile events with concrete
    # retrace causes), a SIGTERM-killed fit must leave an untorn
    # flight-recorder dump, and telemetry-off must take the no-op
    # fast exit.  Also collected by tier-1 (tests/test_telemetry.py),
    # so a regression turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q
}

benchdiff_smoke() {
    # round-over-round trend gate on a SYNTHETIC history (the repo
    # commits no bench records; speed lives in PERF.md and the
    # driver's ledger): five rounds whose last lost its metric
    # (rc=124, parsed:null — the shape a killed run leaves).
    # 1) tools/benchdiff.py must parse every record without crashing
    #    and flag r05 as a REGRESSION with reason "missing metric";
    # 2) earlier rounds carry metric-backed verdicts;
    # 3) --fail-on-regression exits nonzero on the r05 gap.
    local d
    d=$(mktemp -d)
    python - "$d" <<'PY'
import json, sys
d = sys.argv[1]
for n, rc, parsed in [(1, 0, {"value": 2500.0}), (2, 0, {"value": 2600.0}),
                      (3, 0, {"value": 2812.5}), (4, 0, {"value": 2849.29}),
                      (5, 124, None)]:
    json.dump({"n": n, "cmd": "bench", "rc": rc, "parsed": parsed},
              open(f"{d}/BENCH_r{n:02d}.json", "w"))
for n, ms in [(3, 1.0), (4, 1.05)]:
    with open(f"{d}/OPPERF_r{n:02d}.jsonl", "w") as f:
        for k, op in enumerate(("BatchNorm", "Convolution"), 1):
            f.write(json.dumps({"op": op, "avg_time_ms": ms * k,
                                "runs": 5}) + "\n")
PY
    python tools/benchdiff.py --bench "$d/BENCH_r*.json" \
        --opperf "$d/OPPERF_r*.jsonl" > "$d/benchdiff_smoke.txt"
    cat "$d/benchdiff_smoke.txt"
    grep -Eq "r05 .*regression: missing metric" "$d/benchdiff_smoke.txt"
    grep -Eq "^r04 " "$d/benchdiff_smoke.txt"
    if grep -Eq "r04 .*missing metric" "$d/benchdiff_smoke.txt"; then
        echo "benchdiff_smoke: r04 must carry a metric-backed verdict"
        rm -rf "$d"
        return 1
    fi
    if python tools/benchdiff.py --bench "$d/BENCH_r*.json" \
            --opperf "$d/OPPERF_r*.jsonl" --fail-on-regression \
            > /dev/null 2>&1; then
        echo "benchdiff_smoke: expected nonzero exit on the r05 gap"
        rm -rf "$d"
        return 1
    fi
    rm -rf "$d"
}

pallas_smoke() {
    # fused-kernel gate (round 14) on CPU in seconds: every Pallas
    # kernel runs in interpret mode against its jnp baseline — the
    # fused-bucket optimizer updates (sgd bit-exact, adam ulp-tight,
    # lars allclose, the fused loss-scale verdict, the ZeRO step and
    # Module-updater plumbing, winner persistence across processes)
    # and flash attention fwd+bwd incl. causal, non-square, the
    # padding shim and the fallback telemetry event.  Also collected
    # by tier-1, so a regression turns the unit suite red between CI
    # runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_pallas_opt.py \
        tests/test_attention.py -q
}

watchdog_smoke() {
    # stall-proofing gate on CPU in seconds: the hang watchdog must
    # dump stacks for a wedged phase, the partial headline JSON must
    # survive a SIGKILL with every completed phase, and the unarmed
    # paths must stay no-ops.  Also collected by tier-1
    # (tests/test_watchdog.py, tests/test_numerics.py), so a
    # regression turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_watchdog.py \
        tests/test_numerics.py -q
}

collectives_budget() {
    # sharded-server launch-count gate: the dp(16) dryrun runs the
    # flat-bucketed exchange (optimizer_sharding="ps") and ASSERTS its
    # collective budget — <= MXNET_COLLECTIVES_BUDGET (default 8)
    # reduce-scatters and all-gathers and <= 2 all-reduces in the
    # compiled step's HLO (vs one all-reduce per tensor replicated,
    # 54+ launches in the r05 artifact).  A bucketing regression fails
    # this cell on the CPU mesh before it ever reaches a pod.
    # dp_elastic (round 12) adds the reshard-plan verdict: a resume at
    # 16 -> 8 shards must re-plan (old plan != new plan) while both
    # plans honor the budget, and a same-N resume must be a no-op.
    # dp_zero3 (ZeRO stages) adds the stage-3 structural A/B: one
    # RS + one AG per bucket within the budget, RS+AG bytes <= 1.05x
    # the analytic plan minimum, per-chip param bytes ~1/16 of the
    # replicated stage-1 arm.
    JAX_PLATFORMS=cpu MXNET_DRYRUN_SCALING=0 \
    MXNET_DRYRUN_CASES=dp,dp_elastic,dp_zero3 \
        python -c "import __graft_entry__ as g; g.dryrun_multichip(16)"
}

serving_smoke() {
    # fail-safe serving gate (round 13) on the CPU backend, seconds:
    # continuous-batching unit drills (bucketed coalescing, deadline
    # shed, breaker trip/re-warm, transient-fault retry inside the
    # deadline budget) plus the bursty-load SLO drill — admitted p99
    # inside the SLO while serve.model delay faults land mid-burst and
    # the overload is absorbed as structured rejections — plus the
    # SIGTERM drain and the crash->flight-dump->AOT-warm-relaunch
    # subprocess halves.  Also collected by tier-1
    # (tests/test_serving.py), so a regression turns the unit suite
    # red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q
}

fleet_smoke() {
    # elastic serving fleet gate (round 15): the tier-1 half runs the
    # HBM-budget/swap/frontend/router units plus THE 2-replica drill —
    # bursty load over HTTP through the fault-tolerant router with one
    # replica hard-killed mid-burst (fleet.replica crash fault, its
    # in-flight work retried on the sibling inside the deadline), a
    # queue-depth-EWMA scale-up resize (the round-12 reshard event),
    # and a rolling .mxje model swap leaving the replica run-log
    # retrace counter 0.  The `slow` half (run here, excluded from
    # tier-1 by the marker) adds the scale-down-under-load drill (the
    # SIGTERM'd replica drains via PreemptionDrain, the fleet sheds
    # NOTHING) and the mid-swap replica crash (fleet.swap crash fault:
    # the rest of the fleet still upgrades and serves).
    JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q
}

healing_smoke() {
    # self-healing gate (round 16): the tier-1 half runs the peer
    # liveness / guarded-collective / async-snapshot / supervisor /
    # coordinator-migration units plus the fit-level ghost-peer
    # stand-in drill (heal-exit rc 83, emergency checkpoint, resume
    # bit-exact); the `slow` half runs THE drill — a real 2-process
    # jax.distributed job with rank 1 SIGKILLed mid-step, the
    # survivor healing out in milliseconds and the supervisor
    # relaunch resuming at world size 1 from the async snapshot
    # (strictly fresher than the sync save) to match the
    # uninterrupted reference — and a short seeded chaos campaign.
    JAX_PLATFORMS=cpu python -m pytest tests/test_healing.py -q
}

io_smoke() {
    # fault-tolerant data plane gate (round 17) on CPU in seconds:
    # MXRecordIO resync-on-magic (torn frames / truncated tails /
    # decoy magic in payloads — every intact record recovered, every
    # gap named by byte offset), corrupt-record quarantine through
    # the MXNET_IO_WORKERS pool (skip + counter + manifest, the
    # MXNET_IO_MAX_SKIP_FRAC ceiling fails loudly), worker crash /
    # straggler detection with bounded respawn, THE corruption drill
    # (corrupt shard + 4 workers + io.worker:crash mid-epoch: epoch
    # completes with data_records_skipped == k, SIGTERM-drain + resume
    # at a different worker count sample-exact, ElasticHostIter
    # re-slice union-exact) and the worker-kill subprocess half.
    # Also collected by tier-1 (tests/test_dataplane.py), so a
    # regression turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_dataplane.py -q
}

quantize_smoke() {
    # quantized-inference gate (round 18) on CPU in seconds: the
    # quantize/dequantize/requantize error-bound units (uint8 affine +
    # int8 symmetric), quantized FC/conv vs fp32 within calibrated
    # tolerance, entropy-vs-naive calibration on a skewed-activation
    # distribution, the int8 avg-pool round-to-nearest regression,
    # the calibrated-vs-on-the-fly range parity, the adoption-race
    # winner persistence across processes, and THE drill — calibrate
    # a trained net on a synthetic corpus, rewrite to int8, export
    # the CRC+meta-framed .mxje, relaunch-serve it AOT (run-log
    # retrace counter 0) with top-1 agreement >= 99% vs the fp32 arm.
    # Also collected by tier-1 (tests/test_quantization.py), so a
    # regression turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_quantization.py -q
}

fp8_smoke() {
    # fp8 end-to-end gate (round 19) on CPU in seconds: the delayed-
    # scaling amax-history recurrence units (overflow halves the next
    # scale, growth re-expands it), the e4m3/e5m2 qdq straight-through
    # pair, the fp8 dtype-ladder rung — three-rung in-step race,
    # pinned-fp8 training with loss parity vs bf16 over >=6 steps,
    # scale backoff under injected overflow WITHOUT corrupting
    # opt_state, unarmed builds HLO bit-identical to round 18 — plus
    # the inference arm: fp8-pinned forward >=0.99 top-1 agreement vs
    # fp32, fp8 .mxje export identified by float8_e4m3fn in the
    # header's param_dtypes (no deserialization) and served AOT, and
    # the amp-lists/ladder eligibility agreement.  Also collected by
    # tier-1 (tests/test_fp8.py), so a regression turns the unit
    # suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_fp8.py -q
}

generate_smoke() {
    # generative decode serving gate (round 17) on CPU in seconds:
    # the paged KV pool's token-budget admission accounting (int8
    # pages >= 1.8x fp32 concurrency under the same byte budget), the
    # paged-decode-attention variants vs the dense reference with the
    # null-page masking contract, decode matching the autoregressive
    # full-forward reference token-for-token, the bursty continuous-
    # batching campaign with admits+evictions and ZERO post-warm
    # compiles, eviction-resume exactness, the serve.decode breaker
    # drill (pages reclaimed, model_error shed, recovery), the
    # telemetry record/counter/textfile contract, and the per-bucket
    # latency EWMA + causal ragged-tail units that ride along.  Also
    # collected by tier-1 (tests/test_generate.py), so a regression
    # turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_generate.py -q
    # the bench's generative INFERENCE phase end to end in --smoke
    # mode: tokens/s + TTFT p99 + capacity ratio smoke-asserted
    JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_bench_smoke.py::test_smoke_emits_valid_json_with_heartbeats" \
        -q
}

chaos_smoke() {
    # the seeded chaos campaign (rounds 16-18): >=27 reproducible
    # faults across all 13 scenario classes (SIGKILL at a seeded
    # delay, mid-epoch record corruption, the io-worker kill, the
    # ZeRO stage-3 mid-step ghost-peer death with its parameter-shard
    # emergency checkpoint, the round-17 generative decode-fault
    # breaker drill, plus the round-18 online-trainer mid-stream
    # death with its sample-exact resume and the rolling-swap
    # probe-failure rollback drill) on the CPU mesh, each run
    # supervised by the healing respawn policy and gated on the three
    # invariants — zero hangs, zero torn artifacts
    # (tools/ckpt_fsck.py --all clean after every run), every healed
    # run matching its uninterrupted reference allclose(1e-5).  The
    # fixed --seed makes a CI failure exactly reproducible on a
    # laptop.
    JAX_PLATFORMS=cpu python tools/chaos.py --seed 1234 --runs 30 \
        --min-faults 27 --out /tmp/chaos_ci
}

online_smoke() {
    # online learning gate (round 18) on CPU: the deterministic
    # replay stream purity unit, the faultsim-crash + relaunch
    # sample-exact-resume contract (healed params bit-equal the
    # uninterrupted run), checkpoint retention under every-step
    # exports (keep_n pruning + torn-latest + corrupt-newest
    # fallbacks), the rolling-swap partial-failure rollback
    # (probe fault on host 2 of 2 rolls host 1 back, ONE identity,
    # version regression refused), the generative host swap draining
    # in-flight decodes, and THE drill — 60-step trainer SIGKILL'd
    # between swaps under live load: relaunch, sample-exact resume,
    # monotonic served versions, shed swaps counted loudly, and the
    # fault-free freshness p99 within MXNET_FRESHNESS_SLO_MS.  Also
    # collected by tier-1 (tests/test_online.py), so a regression
    # turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_online.py -q
    # the bench's freshness phase end to end in --smoke mode: swap
    # count + freshness p99-vs-SLO smoke-asserted
    JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_bench_smoke.py::test_smoke_emits_valid_json_with_heartbeats" \
        -q
}

trace_smoke() {
    # distributed-tracing gate (round 20) on CPU: the W3C traceparent
    # mint/parse/propagate units, the unarmed A/B zero-cost contract
    # (no mint, no span, env stamp scrubbed), the synthetic 3-process
    # +-200ms clock-skew merge (NTP-pair offsets recovered, child
    # spans never start before their parent) plus the zero-pair
    # beat-file fallback, and THE drill — a live 2-replica FleetRouter
    # with a delay fault on replica 1, merged by tools/tracemerge.py
    # into ONE causal timeline (>=3 processes, cross-process parent
    # links on every request, queue/coalesce/compute + other summing
    # to e2e) whose doctor names the delay-injected replica.  Also
    # collected by tier-1 (tests/test_tracing.py), so a regression
    # turns the unit suite red between CI runs.
    JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q
    # the bench's trace phase end to end in --smoke mode: span counts,
    # skew table, doctor verdict + overhead ratio smoke-asserted
    JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_bench_smoke.py::test_smoke_emits_valid_json_with_heartbeats" \
        -q
}

elastic_smoke() {
    # elastic scale-out gate (round 12): the tier-1 half runs the
    # single-host resize drill — train dp(4) under optimizer sharding,
    # SIGTERM-drain mid-epoch, resume the SAME checkpoint at dp(2)
    # AND dp(8): both re-plan buckets, re-shard adam state (per-chip
    # state bytes ~ total/N at the new N), continue from the exact
    # batch cursor and match the uninterrupted run; plus the topology/
    # cursor-reslice/fallback-telemetry/crash-hook units.  The `slow`
    # half is the REAL 2-process jax.distributed drill (gloo CPU
    # collectives): elastic_init with an injected dist.init flake
    # (retried), a cross-process sharded step with a dist.collective
    # delay, SIGTERM drain on both ranks, relaunch at 1 process with a
    # reshard — excluded from tier-1 by the marker, run here.
    JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q
}

"$@"
