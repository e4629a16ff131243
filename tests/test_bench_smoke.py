"""bench.py harness contract (VERDICT r05: a silent rc=124 cost the
round its headline artifact — the harness itself is now under test).

``--smoke`` runs the full control flow (import / device_init / build /
compile / K1 / K2 / trials / conv A/B) on CPU with a tiny net; the
contract is ONE valid JSON line on stdout, heartbeats per phase on
stderr, and a ``degraded: true`` JSON (not silence) under deadline
pressure.
"""
import json
import os
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


# stable across CI invocations: repeat runs hit the persistent cache
# and skip the XLA compiles — which is exactly the feature under test
_CACHE_DIR = "/tmp/mxnet_tpu_xla_cache_ci"


def _run(extra_env=None, timeout=240, extra_args=()):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, _BENCH, "--smoke", *extra_args],
        capture_output=True, text=True, timeout=timeout, env=env)


def test_smoke_emits_valid_json_with_heartbeats():
    r = _run()
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line: {lines}"
    out = json.loads(lines[0])
    assert out["smoke"] is True
    assert out["degraded"] is False
    assert out["value"] and out["value"] > 0
    assert out["unit"] == "img/s/chip"
    assert out["ms_per_step"] > 0
    # the compilation cache was wired in and populated
    assert out["compilation_cache"] == _CACHE_DIR
    assert any(os.scandir(_CACHE_DIR))
    # the conv 1x1 A/B ran both arms
    ab = out["conv_1x1_ab"]
    assert ab["conv"] > 0 and ab["dot"] > 0 and "dot_speedup" in ab
    # the in-step autotuner ran (or reloaded) the conv1x1 race and
    # reported it
    tune = out["autotune"]
    assert tune["conv1x1_dot"]["winner"] in ("conv", "dot")
    assert set(tune["conv1x1_dot"]["timings"]) == {"conv", "dot"}
    # round 14: the bf16 dtype-ladder arm raced in the main step (the
    # bench arms MXNET_DTYPE_LADDER; smoke leaves compute_dtype free)
    assert tune["dtype_ladder"]["winner"] in ("fp32", "bf16")
    # round 14: the fused-kernels phase raced every new Pallas variant
    # through the autotune registry and reported winners + timings
    fk = out["fused_kernels"]
    assert sorted(fk["raced"]) == ["flash_attention",
                                  "fused_bucket_opt",
                                  "pallas_bnreluconv"]
    assert fk["fused_bucket_opt"]["winner"] in ("jnp", "pallas")
    assert fk["flash_attention"]["winner"] in (
        "naive", "pallas", "pallas_b256", "pallas_pad")
    assert fk["pallas_bnreluconv"]["winner"] in ("stock", "jnp",
                                                 "pallas")
    for op in fk["raced"]:
        assert fk[op].get("cached") or fk[op]["timings"]
    # the device-feed phase measured real steps both ways and reported
    # the per-phase feed/compute overlap
    feed = out["device_feed"]
    assert feed["batches"] > 0
    assert feed["blocking_ms_per_step"] > 0
    assert feed["feed_ms_per_step"] > 0
    assert "feed_wait_ms_per_step" in feed
    assert "overlap_frac" in feed
    # the per-phase atomic checkpoint writes ran and verified
    ck = out["checkpoint"]
    assert ck["verified"] is True
    assert ck["write_s"]["measure"] > 0
    assert ck["write_s"]["feed"] > 0
    assert out["resumed"] is False
    # the collectives phase compiled the dp step sharded vs replicated
    # on the CPU mesh and the sharded-server exchange kept its launch
    # budget: bucketed reduce-scatter/all-gather instead of one
    # all-reduce per tensor (round 9)
    col = out["collectives"]
    assert col["n"] == 8
    rep, shd = col["replicated"]["counts"], col["sharded"]["counts"]
    assert rep["all-reduce"] >= 5  # one per grad tensor
    assert 1 <= shd["reduce-scatter"] <= 8
    assert 1 <= shd["all-gather"] <= 8
    assert shd["all-reduce"] <= 2
    assert col["launches_sharded"] < col["launches_replicated"]
    # the telemetry phase armed a run log, reported real steps into
    # it, and re-read its own JSONL (round 10: the observability layer
    # validates itself every bench run)
    tm = out["telemetry"]
    assert tm["schema_valid"] is True, tm["schema_problems"]
    assert tm["steps"] > 0
    assert tm["records"]["step"] == tm["steps"]
    assert tm["records"]["run_start"] == 1
    assert tm["records"]["run_end"] == 1
    assert tm["synced_steps"] >= 1  # step 0 is always sampled
    assert tm["sample_period"] >= 1
    prog = tm["program_report"]
    assert prog is not None
    assert prog["flops"] > 0
    assert prog["memory"].get("argument_bytes", 0) > 0
    assert prog["collectives"] is not None
    # round 11: the aggregate opstats table (profiler.dumps() analog)
    # landed in the run log — per-op count/avg/p99/bytes rows
    assert tm["records"]["opstats"] == 1
    assert tm["opstats"]["ops"] >= 1
    assert tm["opstats"]["has_p99"] is True
    assert tm["opstats"]["has_bytes"] is True
    # and the numerics monitor recorded tensor_stats rows
    assert tm["records"]["tensor_stats"] >= 1
    assert tm["tensor_stats"]["tensors"] >= 1
    assert tm["tensor_stats"]["nonfinite"] is False
    # the healing phase (round 16): async-checkpoint steal A/B under
    # the <5% acceptance bar, the detect-to-resume drill, and an
    # fsck-clean artifact tree
    hl = out["healing"]
    ov = hl["overhead"]
    assert ov["plain_ms_per_step"] > 0
    assert ov["async_ms_per_step"] > 0
    assert ov["async_versions_written"] >= 1
    assert ov["overhead_ok"] is True, ov
    assert hl["detect_s"] >= 0
    assert hl["resume_s"] > 0
    assert hl["detect_to_resume_s"] >= hl["resume_s"]
    assert hl["reshard_verdict"] == {"reshard": True, "old_world": 2,
                                     "new_world": 1}
    assert hl["fsck_clean"] is True
    assert hl["fsck_versions"] >= 1
    # the data-plane phase (round 17): a multi-worker feed over a
    # shard with 3 seeded-corrupt records — the epoch completes with
    # every corruption quarantined and named, and the latency/
    # throughput evidence lands in the JSON
    dp = out["data_plane"]
    assert dp["records"] > 0
    assert dp["workers"] == 4
    assert dp["skipped"] == dp["corrupt"] == 3
    assert dp["manifest_entries"] == 3
    assert dp["throughput_img_s"] > 0
    # None only under deadline pressure (and then it says so)
    assert dp["single_thread_img_s"] is None and "note" in dp \
        or dp["single_thread_img_s"] > 0
    assert dp["p99_batch_ms"] >= dp["p50_batch_ms"] > 0
    assert dp["feed_wait_s"] >= 0
    assert dp["respawns"] == 0  # no worker faults armed in the bench
    # the INFERENCE serving phase (round 13) stood the continuous-
    # batching model server in front of the net and drove bursty load
    srv = out["serving"]
    assert srv["requests"] > 0
    assert srv["admitted"] > 0
    assert srv["batches"] >= 1
    assert srv["completed"] + srv["shed"] == srv["requests"]
    assert srv["p50_ms"] > 0 and srv["p99_ms"] >= srv["p50_ms"]
    assert srv["slo_ms"] > 0
    assert srv["buckets"], "bucketed batch shapes must be reported"
    # the microbatch race seeded the buckets: every bucket divides by
    # the winning chunk count and none exceeds the largest
    k = srv["microbatch"][0]
    assert all(b % k == 0 for b in srv["buckets"])
    assert srv["warm_start_s"] > 0
    # steady state re-pads to warmed buckets: no post-warm traces
    assert srv["steady_state_traces"] == 0
    assert srv["breaker"] == "closed"
    # the quantization INFERENCE phase (round 18): the calibrate ->
    # rewrite -> race -> export -> AOT-serve chain on a trained net
    qt = out["quantization"]
    assert qt["calib_mode"] == "entropy"
    assert qt["calib_batches"] >= 1
    assert qt["layers_quantized"] >= 2
    # the acceptance bar: int8 answers agree with the fp32 arm
    assert qt["agreement_top1"] >= 0.99, qt
    assert qt["accuracy_delta"] <= 0.01
    # the adoption race ran (or answered from cache) for both arms
    assert set(qt["autotune"]) == {"quantized_conv", "quantized_fc"}
    for op, rep in qt["autotune"].items():
        # fp8 joined the race in round 19 — any arm may win on CPU
        assert rep["winner"] in ("fp32", "int8", "fp8"), (op, rep)
    # the exported artifact identifies itself as int8 from the header
    assert qt["artifact"]["quantized"] is True
    assert qt["artifact"]["param_dtypes"].get("int8", 0) >= 2
    # both arms served AOT with latency/throughput measured (the fp32
    # arm is legitimately None only when the phase deadline expired
    # between arms — the data_plane precedent: degrade, don't crash)
    arms = ["int8"] + (["fp32"] if qt["fp32"] is not None else [])
    for arm in arms:
        assert qt[arm]["p50_ms"] > 0
        assert qt[arm]["p99_ms"] >= qt[arm]["p50_ms"]
        assert qt[arm]["throughput_req_s"] > 0
        assert qt[arm]["completed"] > 0
    if qt["fp32"] is not None:
        assert qt["speedup_p50"] is not None
    else:
        assert qt["speedup_p50"] is None
    # the generative decode INFERENCE phase (round 17): paged-KV
    # continuous batching under bursty ragged-prompt load
    gen = out["generate"]
    assert gen["requests"] > 0
    assert gen["completed"] + gen["shed"] == gen["requests"]
    assert gen["tokens"] > 0 and gen["tokens_s"] > 0
    assert gen["ttft_p99_ms"] >= gen["ttft_p50_ms"] > 0
    assert gen["max_in_flight"] >= 1
    # eviction/shed are always REPORTED (their values are load-shaped)
    assert gen["evictions"] >= 0 and gen["shed"] >= 0
    # the zero-retrace proof: the warm-started campaign, admits and
    # evictions included, compiled NOTHING new
    assert gen["compiles_after_warm"] == 0, gen
    assert gen["warm_traces"] >= 1
    # every page returned to the pool once the campaign drained
    assert gen["pages_in_use"] == 0
    # the int8 KV acceptance bar: >= 1.8x fp32 concurrent sequences
    # under the same budget (page-pool accounting), per-token
    # agreement at or above the adoption floor
    assert gen["capacity_ratio_int8"] >= 1.8, gen
    assert gen["capacity_int8_seqs"] >= gen["capacity_fp32_seqs"]
    assert gen["kv_dtype"] in ("int8", "float32")
    if gen["kv_dtype"] == "int8":
        assert gen["kv_agreement"] >= 0.99, gen
    # the fleet INFERENCE phase (round 15): 2 replica processes
    # behind the fault-tolerant router, bursty load over HTTP, a
    # rolling model swap, clean drain exits
    fl = out["fleet"]
    assert fl["replicas"] == 2
    assert fl["requests"] > 0
    assert fl["errors"] == 0, fl["error_sample"]
    assert fl["completed"] + fl["shed"] + fl["errors"] \
        == fl["requests"]
    assert fl["completed"] > 0
    assert fl["p50_ms"] > 0 and fl["p99_ms"] >= fl["p50_ms"]
    assert fl["slo_ms"] > 0
    assert fl["p99_within_slo"] is True
    assert fl["swap_ms"] > 0 and fl["swap_errors"] == 0
    # every replica exited as a clean SIGTERM drain
    assert sorted(fl["drain_rcs"].values()) == [-15, -15]
    # the online-learning freshness phase (round 18): the supervised
    # trainer→export→rolling-swap loop against a 2-replica fleet —
    # every export was swapped or shed (never silently dropped), the
    # served versions only moved forward, and the fault-free
    # sample-to-served p99 met the SLO
    fr = out["freshness"]
    assert fr["exports"] > 0
    assert fr["swaps"] > 0
    assert fr["exports"] == fr["swaps"] + fr["swaps_shed"]
    assert fr["relaunches"] == 0
    assert fr["monotonic"] is True
    assert fr["versions_served"] == sorted(fr["versions_served"])
    assert fr["p50_ms"] > 0 and fr["p99_ms"] >= fr["p50_ms"]
    assert fr["slo_ms"] > 0
    assert fr["p99_within_slo"] is True
    # the distributed-tracing phase (round 20): per-process runlogs
    # from a 2-replica fleet merged into ONE causal timeline — spans
    # crossed processes, the skew estimator ran, and doctor named the
    # delay-injected replica as the bottleneck
    tr = out["trace"]
    assert tr["errors"] == 0, tr["error_sample"]
    assert tr["completed"] > 0
    assert tr["processes"] >= 3  # router + 2 replicas
    assert tr["spans"] > 0
    assert tr["traced_requests"] == tr["completed"]
    assert tr["flow_links"] >= tr["completed"]  # every request hopped
    assert len(tr["skew_s"]) == tr["processes"]
    assert tr["dominant"] in ("queue", "coalesce", "compute",
                              "other", "swap-in-progress")
    assert tr["bottleneck_process"].startswith("replica-1"), tr
    assert set(tr["components_pct"]) == {"queue", "coalesce",
                                         "compute", "other"}
    assert tr["overhead_ratio"] is not None
    # the hang watchdog was armed (bench defaults it on) and quiet
    assert out["watchdog_sec"] > 0
    assert out["watchdog_stalls"] == 0
    # a heartbeat per phase, so a hang is attributable
    for phase in ("import", "device_init", "build", "autotune",
                  "compile", "K1", "K2", "trials", "feed",
                  "checkpoint", "collectives", "fused_kernels",
                  "healing", "data_plane", "serving", "quantization",
                  "generate", "fleet", "freshness", "trace",
                  "telemetry", "conv_ab", "done"):
        assert f"phase={phase}" in r.stderr, f"missing phase {phase}"


def test_smoke_checkpoint_resume_roundtrip(tmp_path):
    """--checkpoint then --resume-from: the second run restores the
    first run's trained params/opt state and says so in its JSON."""
    prefix = str(tmp_path / "bench_ck")
    r1 = _run(extra_args=("--checkpoint", prefix, "--no-autotune"))
    assert r1.returncode == 0, r1.stderr[-2000:]
    out1 = json.loads(r1.stdout.splitlines()[-1])
    assert out1["checkpoint"]["prefix"] == prefix
    assert out1["checkpoint"]["verified"] is True
    r2 = _run(extra_args=("--resume-from", prefix, "--no-autotune"))
    assert r2.returncode == 0, r2.stderr[-2000:]
    out2 = json.loads(r2.stdout.splitlines()[-1])
    assert out2["resumed"] is True
    assert out2["resumed_from_epoch"] == 2
    assert "phase=resume" in r2.stderr


def test_smoke_sigkill_leaves_partial_json_and_stack_dump(tmp_path):
    """Round 11 acceptance: the r05 shape of failure, reproduced and
    survived.  A bench wedged in an uninterruptible call (simulated by
    a bench.stall delay fault with NO heartbeats) and then SIGKILLed —
    the strongest kill, no handler runs — must leave:

    * the PARTIAL headline JSON, atomically rewritten per phase, with
      the measured value and every completed phase listed;
    * the watchdog's all-thread stack-dump file (the watchdog fired
      DURING the stall, from its own thread);
    * the stall stamped into the partial artifact.
    """
    import signal
    import time

    partial = str(tmp_path / "partial.json")
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    env["MXNET_FAULT_SPEC"] = "bench.stall:delay=90@1"
    # streams go to FILES, not pipes: nobody drains a pipe during the
    # 90 s stall, so a verbose child (JAX_LOG_COMPILES etc.) would
    # block on a full pipe buffer inside _heartbeat's print — before
    # the beat — and never reach the measure phase
    out_f = open(tmp_path / "child.out", "wb")
    err_f = open(tmp_path / "child.err", "wb")
    proc = subprocess.Popen(
        [sys.executable, _BENCH, "--smoke", "--no-autotune",
         "--watchdog", "1", "--partial-json", partial],
        stdout=out_f, stderr=err_f, env=env)
    try:
        stacks = partial + ".stacks.txt"
        deadline = time.monotonic() + 180

        def _ready():
            # the measure phase must have landed in the partial AND
            # the watchdog must have fired (inside the 90 s stall that
            # follows measure — or earlier on a slow box; both leave
            # the dump)
            if not os.path.exists(stacks):
                return False
            try:
                with open(partial) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                return False
            return "measure" in doc.get("phases_completed", ())

        while time.monotonic() < deadline:
            if _ready():
                break
            if proc.poll() is not None:
                err_f.flush()
                pytest.fail("bench exited before the stall: "
                            + (tmp_path / "child.err")
                            .read_bytes().decode()[-2000:])
            time.sleep(0.2)
        assert _ready(), "watchdog never fired during the stall"
        # give the on_stall partial rewrite a beat, then kill -9
        time.sleep(0.5)
        proc.kill()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        out_f.close()
        err_f.close()
    assert proc.returncode == -signal.SIGKILL

    # the partial artifact survived the SIGKILL, parses whole, and
    # carries the completed phases' results
    with open(partial) as f:
        out = json.load(f)
    assert out["partial"] is True
    assert out["degraded"] is True
    assert "measure" in out["phases_completed"]
    assert out["value"] and out["value"] > 0       # phase-1 result
    assert out["ms_per_step"] > 0
    assert "killed" in out["reason"]
    # the stall is attributed in the artifact, stacks linked
    assert out["stalled"]["quiet_s"] >= 1
    assert out["stalled"]["stacks"] == stacks
    text = open(stacks).read()
    assert "watchdog stall #1" in text
    assert "bench.py" in text  # the wedged main thread's frames
    # NOTE: a .tmp sibling MAY survive if the SIGKILL landed inside a
    # later watchdog re-fire's write window — that is the point of the
    # temp+rename protocol: the artifact itself (asserted parseable
    # above) can never be the torn one.


def test_bare_invocation_sigkill_leaves_parseable_partial(tmp_path):
    """The r05 runner invoked bare ``python bench.py`` (FULL mode, zero
    flags) and rc=124 left ``parsed: null`` — the partial headline JSON
    and the watchdog must be DEFAULT-armed on the bare flag set too, so
    an external ``timeout -k``/SIGKILL always leaves a parseable
    degraded JSON.

    A CPU host cannot get a full-mode run past ``device_init`` (it
    refuses there, see the next test), so the SIGKILL lands in the
    window it has: after the ``import`` phase is in the partial (the
    watchdog is armed by then) and before the refusal's final line
    clears it.  The window is the CPU backend's start-up; a kill that
    arrives late is seen (rc 2, or no partial left) and tried again.
    The bench is copied into a tmp dir: the default partial path is
    ``BENCH_partial.json`` beside bench.py."""
    import shutil
    import signal
    import time

    bench_copy = str(tmp_path / "bench.py")
    shutil.copy(_BENCH, bench_copy)
    partial = str(tmp_path / "BENCH_partial.json")  # the DEFAULT path
    env = dict(os.environ)
    env.pop("BENCH_PARTIAL_JSON", None)
    env.pop("MXNET_WATCHDOG_SEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    env["PYTHONPATH"] = os.path.dirname(_BENCH) + os.pathsep + \
        env.get("PYTHONPATH", "")

    def _doc():
        try:
            with open(partial) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    doc = None
    for _ in range(5):
        if os.path.exists(partial):
            os.remove(partial)
        proc = subprocess.Popen([sys.executable, bench_copy],
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL, env=env)
        try:
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline and proc.poll() is None:
                if "import" in (_doc() or {}).get("phases_completed", ()):
                    proc.kill()  # SIGKILL: no handler runs
                    break
                time.sleep(0.002)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        doc = _doc()
        if proc.returncode == -signal.SIGKILL and doc is not None:
            break
    else:
        pytest.fail("five bare runs all reached their final line before "
                    f"the SIGKILL landed (last rc {proc.returncode})")
    # the DEFAULT-armed artifact survived the SIGKILL and parses whole
    assert doc["degraded"] is True
    assert doc["partial"] is True
    assert "import" in doc["phases_completed"]
    assert "killed" in doc["reason"]
    # the watchdog was default-armed in FULL mode too (300 s)
    assert doc["watchdog_sec"] > 0


def test_bare_invocation_without_a_tpu_refuses_with_parseable_json(
        tmp_path):
    """The r05 runner invoked bare ``python bench.py`` (FULL mode, zero
    flags).  Full mode measures the chip: on any other platform it must
    say so and exit non-zero — ONE parseable JSON line naming the
    platform, never a CPU number under a device metric's name and never
    silence.  The bare flag set still default-arms the watchdog and the
    partial headline JSON (``BENCH_partial.json`` beside bench.py; the
    bench is copied into a tmp dir so the checkout stays clean), and
    the partial is cleared once the final line is out (what a SIGKILL
    before that line leaves behind is the test above)."""
    import shutil

    bench_copy = str(tmp_path / "bench.py")
    shutil.copy(_BENCH, bench_copy)
    env = dict(os.environ)
    env.pop("BENCH_PARTIAL_JSON", None)
    env.pop("MXNET_WATCHDOG_SEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    env["PYTHONPATH"] = os.path.dirname(_BENCH) + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, bench_copy], capture_output=True,
                       text=True, timeout=180, env=env)
    assert r.returncode == 2, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line: {lines}"
    doc = json.loads(lines[0])
    assert doc["degraded"] is True
    assert doc["value"] is None
    assert "no TPU" in doc["reason"] and "'cpu'" in doc["reason"]
    # refused in device_init, before any model was built
    assert "phase=device_init" in r.stderr
    assert "phase=build" not in r.stderr
    # the defaults were armed on the bare flag set (watchdog 300 s in
    # FULL mode; the cache placed from outside and no other)
    assert doc["watchdog_sec"] > 0
    assert doc["compilation_cache"] == _CACHE_DIR
    # the default partial artifact does not outlive the final line
    assert not os.path.exists(str(tmp_path / "BENCH_partial.json"))


def test_smoke_deadline_degrades_not_dies():
    """An exhausted internal deadline emits degraded JSON immediately
    instead of hanging into an external kill (the rc=124 failure
    mode)."""
    r = _run(extra_env={"BENCH_DEADLINE_S": "0.001"}, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["degraded"] is True
    assert out["value"] is None
    assert "deadline" in out["reason"]


@pytest.mark.slow  # the two tests above cover the tier-1 contract;
# this one re-pays the full smoke startup for the mid-run bite case
def test_smoke_tight_deadline_still_emits():
    """A deadline that bites mid-run (machine-speed dependent WHERE)
    must still produce the one JSON line: either a value measured
    under a reduced K plan or a null value with a deadline reason —
    silence is the only failure."""
    r = _run(extra_env={"BENCH_DEADLINE_S": "8"}, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["value"] is None or out["value"] > 0
    if out["degraded"]:
        assert out.get("reason")
