"""tools/benchdiff.py: a history of bench records becomes a trend.

The acceptance row: over a five-round history whose last round lost its
metric (``rc=124``, ``parsed: null`` — the shape a killed run leaves),
the differ must flag that round as a REGRESSION (not crash on the file)
and exit nonzero under ``--fail-on-regression`` — that is the
``benchdiff_smoke`` CI cell.  Every record is synthetic and written to
``tmp_path``: the repo commits no bench records (speed lives in
PERF.md and the driver's ledger).  Further synthetic artifacts cover
the p50/p99 tail-latency columns and the threshold arithmetic both
ways.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.unit

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "benchdiff.py")


def _load():
    spec = importlib.util.spec_from_file_location("benchdiff", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bd = _load()


# ------------------------------------- a history that ends in a lost round
def _lost_round_history(tmp_path):
    """Five headline rounds, the last killed before it printed a
    metric, plus two per-op rounds; returns the two globs."""
    for n, rc, parsed in [
            (1, 0, {"value": 2500.0}), (2, 0, {"value": 2600.0}),
            (3, 0, {"value": 2812.5}), (4, 0, {"value": 2849.29}),
            (5, 124, None)]:
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "cmd": "bench", "rc": rc, "parsed": parsed}))
    for n, ms in [(3, 1.0), (4, 1.05)]:
        (tmp_path / f"OPPERF_r{n:02d}.jsonl").write_text("".join(
            json.dumps({"op": op, "avg_time_ms": ms * k, "runs": 5})
            + "\n" for k, op in enumerate(("BatchNorm", "Convolution",
                                           "FullyConnected"), 1)))
    return (str(tmp_path / "BENCH_r0[1-5].json"),
            str(tmp_path / "OPPERF_r0[1-5].jsonl"))


def test_lost_round_is_flagged_as_regression(tmp_path, capsys):
    bench, opperf = _lost_round_history(tmp_path)
    rc = bd.main(["--bench", bench, "--opperf", opperf])
    out = capsys.readouterr().out
    assert rc == 0  # reporting mode never fails the build
    assert "r05" in out
    # the r05 shape of failure: flagged as a regression with the
    # reason, NOT a crash of the tool
    assert "regression: missing metric (rc=124)" in out
    assert "r01" in out and "baseline" in out
    # the opperf artifacts trended too
    assert "opperf trend" in out


def test_lost_round_fails_on_regression_exits_nonzero(tmp_path):
    bench, opperf = _lost_round_history(tmp_path)
    rc = bd.main(["--bench", bench, "--opperf", opperf,
                  "--fail-on-regression"])
    assert rc == 2


def test_cli_entrypoint_runs(tmp_path):
    bench, _ = _lost_round_history(tmp_path)
    r = subprocess.run(
        [sys.executable, _TOOL, "--json", "--bench", bench],
        capture_output=True, text=True, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-500:]
    doc = json.loads(r.stdout)
    assert doc["headline"]["r05"]["verdict"] == "regression"
    assert "missing metric" in doc["headline"]["r05"]["reason"]
    assert doc["headline"]["r04"]["value"] == 2849.29
    assert any("r05" in f for f in doc["failures"])


# ---------------------------------------------------------- synthetic
def _wrapper(n, rc, parsed):
    return {"n": n, "cmd": "bench", "rc": rc, "parsed": parsed}


def _write_rounds(tmp_path, rows):
    for n, rc, parsed in rows:
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps(_wrapper(n, rc, parsed)))
    return str(tmp_path / "BENCH_r*.json")


def test_threshold_splits_ok_improved_regression(tmp_path):
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),
        (2, 0, {"value": 1100.0}),   # +10% < 15% -> ok
        (3, 0, {"value": 1500.0}),   # +36% -> improved
        (4, 0, {"value": 1000.0}),   # -33% -> regression
    ])
    rounds = bd.headline_verdicts(
        bd.load_bench(sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["verdict"] == "baseline"
    assert rounds["r02"]["verdict"] == "ok"
    assert rounds["r03"]["verdict"] == "improved"
    assert rounds["r04"]["verdict"] == "regression"


def test_missing_metric_and_malformed_files_never_crash(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_wrapper(1, 0, {"value": 100.0})))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps(_wrapper(2, 124, None)))
    (tmp_path / "BENCH_r03.json").write_text("{not json")
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(str(p) for p in tmp_path.glob("BENCH_r*.json"))), 0.15)
    assert rounds["r02"]["verdict"] == "regression"
    assert "rc=124" in rounds["r02"]["reason"]
    assert rounds["r03"]["verdict"] == "regression"
    assert "unreadable" in rounds["r03"]["reason"]
    # a later round with a metric diffs against the last GOOD metric
    (tmp_path / "BENCH_r04.json").write_text(
        json.dumps(_wrapper(4, 0, {"value": 101.0})))
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(str(p) for p in tmp_path.glob("BENCH_r*.json"))), 0.15)
    assert rounds["r04"]["verdict"] == "ok"


def test_bare_headline_json_accepted(tmp_path):
    """bench.py's own stdout line (or a partial artifact) parses too —
    no driver wrapper required."""
    (tmp_path / "BENCH_r07.json").write_text(json.dumps(
        {"metric": "resnet50_train_throughput", "value": 3000.0,
         "mfu": 0.5, "ms_per_step": 42.0, "degraded": True}))
    rounds = bd.load_bench([str(tmp_path / "BENCH_r07.json")])
    assert rounds["r07"]["value"] == 3000.0
    assert rounds["r07"]["mfu"] == 0.5
    assert rounds["r07"]["degraded"] is True


def test_opperf_tail_latency_trend(tmp_path):
    rows3 = [{"op": "dot", "avg_time_ms": 1.0, "p50_time_ms": 0.9,
              "p99_time_ms": 1.2},
             {"op": "conv", "avg_time_ms": 5.0, "p50_time_ms": 4.8,
              "p99_time_ms": 5.5},
             {"op": "only_in_r3", "avg_time_ms": 1.0}]
    rows4 = [{"op": "dot", "avg_time_ms": 2.0, "p50_time_ms": 1.8,
              "p99_time_ms": 6.0},       # 2x slower, p99 5x
             {"op": "conv", "avg_time_ms": 2.0, "p50_time_ms": 1.9,
              "p99_time_ms": 2.2}]       # 2.5x faster
    for n, rows in ((3, rows3), (4, rows4)):
        with open(tmp_path / f"OPPERF_r{n:02d}.jsonl", "w") as f:
            f.write("\n".join(json.dumps(r) for r in rows) + "\n")
    diff = bd.opperf_diff(bd.load_opperf(
        sorted(str(p) for p in tmp_path.glob("OPPERF_r*.jsonl"))),
        0.15)
    assert diff["compared_ops"] == 2  # only_in_r3 dropped, no crash
    assert [e["op"] for e in diff["regressions"]] == ["dot"]
    assert diff["regressions"][0]["ratio"] == 2.0
    assert diff["regressions"][0]["p99_ratio"] == 5.0
    assert [e["op"] for e in diff["improvements"]] == ["conv"]


def test_fail_on_regression_threshold_is_configurable(tmp_path):
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),
        (2, 0, {"value": 900.0}),   # -10%
    ])
    # 15% threshold tolerates -10%...
    assert bd.main(["--bench", glob_b, "--opperf",
                    str(tmp_path / "none*.jsonl"),
                    "--fail-on-regression"]) == 0
    # ...a 5% threshold does not
    assert bd.main(["--bench", glob_b, "--opperf",
                    str(tmp_path / "none*.jsonl"),
                    "--threshold", "0.05",
                    "--fail-on-regression"]) == 2


def _fleet(p99, requests=100, shed=0, within=True):
    return {"p99_ms": p99, "p50_ms": p99 / 2.0, "requests": requests,
            "shed": shed, "p99_within_slo": within,
            "slo_ms": 8000.0}


def test_fleet_trend_verdicts_and_missing_metric(tmp_path):
    """Round 15: the fleet INFERENCE phase trends like the headline —
    baseline on first appearance, p99/shed/SLO regressions flagged,
    and a round that HAD fleet data losing it is the r05 failure
    shape ('missing fleet metric').  Rounds predating the phase carry
    no fleet verdict at all (old artifacts never gate)."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),                       # pre-fleet
        (2, 0, {"value": 1000.0, "fleet": _fleet(10.0)}),
        (3, 0, {"value": 1000.0, "fleet": _fleet(11.0)}),    # ok
        (4, 0, {"value": 1000.0, "fleet": _fleet(30.0)}),    # p99 3x
        (5, 0, {"value": 1000.0,
                "fleet": _fleet(30.0, shed=40)}),        # shed jump
        (6, 0, {"value": 1000.0,
                "fleet": _fleet(30.0, shed=40, within=False)}),
        (7, 0, {"value": 1000.0}),                   # lost the phase
    ])
    rounds = bd.fleet_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["fleet_verdict"] is None
    assert rounds["r02"]["fleet_verdict"] == "baseline"
    assert rounds["r03"]["fleet_verdict"] == "ok"
    assert rounds["r04"]["fleet_verdict"] == "regression"
    assert "p99" in rounds["r04"]["fleet_reason"]
    assert rounds["r05"]["fleet_verdict"] == "regression"
    assert "shed rate" in rounds["r05"]["fleet_reason"]
    assert rounds["r06"]["fleet_verdict"] == "regression"
    assert "SLO" in rounds["r06"]["fleet_reason"]
    assert rounds["r07"]["fleet_verdict"] == "regression"
    assert rounds["r07"]["fleet_reason"] == "missing fleet metric"


def test_fleet_regression_gates_with_fail_on_regression(tmp_path,
                                                        capsys):
    """A serving-robustness regression exits 2 under
    --fail-on-regression even when the headline throughput is clean,
    and the table carries the fleet section."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "fleet": _fleet(10.0)}),
        (2, 0, {"value": 1010.0, "fleet": _fleet(100.0)}),
    ])
    rc = bd.main(["--bench", glob_b, "--opperf",
                  str(tmp_path / "none*.jsonl"),
                  "--fail-on-regression"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "fleet serving trend" in out
    assert "fleet r02" in out
    # the headline itself stayed ok — only the fleet gate fired
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r02"]["verdict"] == "ok"


def _quant(agreement, p99=5.0, speedup=1.2):
    return {"agreement_top1": agreement,
            "accuracy_delta": round(1.0 - agreement, 4),
            "int8": {"p99_ms": p99, "p50_ms": p99 / 2.0},
            "fp32": {"p99_ms": p99 * 1.2, "p50_ms": p99 * 0.6},
            "speedup_p50": speedup}


def test_quantization_trend_verdicts_and_missing_metric(tmp_path):
    """Round 18: the quantization INFERENCE phase trends like the
    fleet's — baseline on first appearance, the int8 p99 rated
    inverted, agreement below 0.99 an ABSOLUTE regression, and a
    round that shipped the phase then lost it is 'missing
    quantization metric'.  Pre-phase rounds carry no verdict."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),                        # pre-phase
        (2, 0, {"value": 1000.0, "quantization": _quant(1.0)}),
        (3, 0, {"value": 1000.0,
                "quantization": _quant(0.995, p99=5.2)}),     # ok
        (4, 0, {"value": 1000.0,
                "quantization": _quant(0.995, p99=20.0)}),  # p99 4x
        (5, 0, {"value": 1000.0,
                "quantization": _quant(0.9)}),  # accuracy floor
        (6, 0, {"value": 1000.0}),                # lost the phase
    ])
    rounds = bd.quantization_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["quant_verdict"] is None
    assert rounds["r02"]["quant_verdict"] == "baseline"
    assert rounds["r03"]["quant_verdict"] == "ok"
    assert rounds["r04"]["quant_verdict"] == "regression"
    assert "p99" in rounds["r04"]["quant_reason"]
    assert rounds["r05"]["quant_verdict"] == "regression"
    assert "0.99" in rounds["r05"]["quant_reason"]
    assert rounds["r06"]["quant_verdict"] == "regression"
    assert rounds["r06"]["quant_reason"] == \
        "missing quantization metric"


def test_fp8_agreement_floor_and_missing_after_shipped(tmp_path):
    """Round 19: the fp8 arm is held to the SAME absolute 0.99
    agreement floor as int8, and once a round ships the fp8 metric a
    later round without it regresses — tracked independently of the
    int8 metric's shipping round."""

    def q(fp8=None, **kw):
        doc = _quant(kw.pop("agreement", 1.0), **kw)
        if fp8 is not None:
            doc["agreement_top1_fp8"] = fp8
        return doc

    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "quantization": q()}),  # int8 only
        (2, 0, {"value": 1000.0,
                "quantization": q(fp8=1.0)}),  # fp8 ships
        (3, 0, {"value": 1000.0,
                "quantization": q(fp8=0.98)}),  # fp8 floor
        (4, 0, {"value": 1000.0, "quantization": q()}),  # fp8 lost
    ])
    rounds = bd.quantization_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    # pre-fp8 rounds are not punished for the metric not existing yet
    assert rounds["r01"]["quant_verdict"] == "baseline"
    assert rounds["r02"]["quant_verdict"] == "ok"
    assert rounds["r03"]["quant_verdict"] == "regression"
    assert "fp8 agreement 0.980 < 0.99" in rounds["r03"]["quant_reason"]
    assert rounds["r04"]["quant_verdict"] == "regression"
    assert "missing fp8 quantization metric" in \
        rounds["r04"]["quant_reason"]


def test_quantization_regression_gates_with_fail_on_regression(
        tmp_path, capsys):
    """An int8 accuracy regression exits 2 under --fail-on-regression
    even with a clean headline, and the table carries the
    quantization section."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "quantization": _quant(1.0)}),
        (2, 0, {"value": 1010.0, "quantization": _quant(0.8)}),
    ])
    rc = bd.main(["--bench", glob_b, "--opperf",
                  str(tmp_path / "none*.jsonl"),
                  "--fail-on-regression"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "quantization trend" in out
    assert "quantization r02" in out
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r02"]["verdict"] == "ok"


def _gen(tokens_s, ttft_p99=50.0, agreement=1.0, compiles=0):
    return {"tokens_s": tokens_s, "ttft_p50_ms": ttft_p99 / 4.0,
            "ttft_p99_ms": ttft_p99, "kv_agreement": agreement,
            "compiles_after_warm": compiles, "kv_dtype": "int8",
            "evictions": 2, "shed": 0,
            "capacity_ratio_int8": 2.62}


def test_generate_trend_verdicts_and_missing_metric(tmp_path):
    """Round 17: the generate INFERENCE phase trends like the fleet's
    — baseline on first appearance, tokens/s rated like the headline
    (higher is better), TTFT p99 inverted, int8 KV agreement below
    0.99 and ANY post-warm compile ABSOLUTE regressions, and a round
    that shipped the phase then lost it is 'missing generate
    metric'.  Pre-phase rounds carry no verdict."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),                         # pre-phase
        (2, 0, {"value": 1000.0, "generate": _gen(200.0)}),
        (3, 0, {"value": 1000.0,
                "generate": _gen(190.0, ttft_p99=52.0)}),      # ok
        (4, 0, {"value": 1000.0,
                "generate": _gen(100.0)}),          # tokens/s halved
        (5, 0, {"value": 1000.0,
                "generate": _gen(200.0, ttft_p99=500.0)}),  # TTFT 10x
        (6, 0, {"value": 1000.0,
                "generate": _gen(200.0, agreement=0.9)}),  # KV floor
        (7, 0, {"value": 1000.0,
                "generate": _gen(200.0, compiles=3)}),     # retrace
        (8, 0, {"value": 1000.0}),                 # lost the phase
    ])
    rounds = bd.generate_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["gen_verdict"] is None
    assert rounds["r02"]["gen_verdict"] == "baseline"
    assert rounds["r03"]["gen_verdict"] == "ok"
    assert rounds["r04"]["gen_verdict"] == "regression"
    assert "tokens/s" in rounds["r04"]["gen_reason"]
    assert rounds["r05"]["gen_verdict"] == "regression"
    assert "TTFT" in rounds["r05"]["gen_reason"]
    assert rounds["r06"]["gen_verdict"] == "regression"
    assert "0.99" in rounds["r06"]["gen_reason"]
    assert rounds["r07"]["gen_verdict"] == "regression"
    assert "retrace" in rounds["r07"]["gen_reason"]
    assert rounds["r08"]["gen_verdict"] == "regression"
    assert rounds["r08"]["gen_reason"] == "missing generate metric"


def test_generate_regression_gates_with_fail_on_regression(
        tmp_path, capsys):
    """A decode tokens/s regression exits 2 under --fail-on-regression
    even with a clean headline, and the table carries the generate
    section."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "generate": _gen(200.0)}),
        (2, 0, {"value": 1010.0, "generate": _gen(80.0)}),
    ])
    rc = bd.main(["--bench", glob_b, "--opperf",
                  str(tmp_path / "none*.jsonl"),
                  "--fail-on-regression"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "generate serving trend" in out
    assert "generate r02" in out
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r02"]["verdict"] == "ok"


def _fresh(p99, slo=True, mono=True, swaps=5, shed=1):
    return {"steps": 30, "exports": swaps + shed, "swaps": swaps,
            "swaps_shed": shed, "swap_rollbacks": 0, "relaunches": 0,
            "versions_served": list(range(1, swaps + 1)),
            "monotonic": mono, "slo_ms": 60000.0, "violations": 0,
            "p50_ms": p99 / 2.0, "p99_ms": p99 * 1.2,
            "fault_free_p99_ms": p99, "p99_within_slo": slo}


def test_freshness_trend_verdicts_and_missing_metric(tmp_path):
    """Round 18: the freshness phase trends like the fleet's — the
    fault-free sample-to-served p99 inverted (lower is better), a
    served-version monotonicity violation and an SLO miss ABSOLUTE
    regressions (baseline round included), and a round that shipped
    the phase then lost it is 'missing freshness metric'.  Pre-phase
    rounds carry no verdict."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),                         # pre-phase
        (2, 0, {"value": 1000.0, "freshness": _fresh(500.0)}),
        (3, 0, {"value": 1000.0, "freshness": _fresh(520.0)}),   # ok
        (4, 0, {"value": 1000.0, "freshness": _fresh(900.0)}),  # p99 x1.7
        (5, 0, {"value": 1000.0,
                "freshness": _fresh(500.0, mono=False)}),  # BACKWARDS
        (6, 0, {"value": 1000.0,
                "freshness": _fresh(500.0, slo=False)}),   # SLO miss
        (7, 0, {"value": 1000.0}),                 # lost the phase
    ])
    rounds = bd.freshness_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["fresh_verdict"] is None
    assert rounds["r02"]["fresh_verdict"] == "baseline"
    assert rounds["r03"]["fresh_verdict"] == "ok"
    assert rounds["r04"]["fresh_verdict"] == "regression"
    assert "p99" in rounds["r04"]["fresh_reason"]
    assert rounds["r05"]["fresh_verdict"] == "regression"
    assert "BACKWARDS" in rounds["r05"]["fresh_reason"]
    assert rounds["r06"]["fresh_verdict"] == "regression"
    assert "SLO" in rounds["r06"]["fresh_reason"]
    assert rounds["r07"]["fresh_verdict"] == "regression"
    assert rounds["r07"]["fresh_reason"] == "missing freshness metric"


def test_freshness_monotonicity_regresses_at_baseline(tmp_path):
    """The absolute verdicts fire on the FIRST round that ships the
    phase too — a version-regressing fleet is broken at any speed."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "freshness": _fresh(500.0,
                                                     mono=False)}),
    ])
    rounds = bd.freshness_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r01"]["fresh_verdict"] == "regression"
    assert "BACKWARDS" in rounds["r01"]["fresh_reason"]


def test_freshness_regression_gates_with_fail_on_regression(
        tmp_path, capsys):
    """A freshness p99 blow-up exits 2 under --fail-on-regression even
    with a clean headline, and the table carries the freshness
    section."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0, "freshness": _fresh(500.0)}),
        (2, 0, {"value": 1010.0, "freshness": _fresh(2000.0)}),
    ])
    rc = bd.main(["--bench", glob_b, "--opperf",
                  str(tmp_path / "none*.jsonl"),
                  "--fail-on-regression"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "freshness trend" in out
    assert "freshness r02" in out
    rounds = bd.headline_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert rounds["r02"]["verdict"] == "ok"


def test_fleet_absent_everywhere_never_gates(tmp_path):
    """The committed pre-round-15 artifacts carry no fleet phase: the
    fleet gate must stay silent (the pinned r01–r05 CI window cannot
    change behavior)."""
    glob_b = _write_rounds(tmp_path, [
        (1, 0, {"value": 1000.0}),
        (2, 0, {"value": 1000.0}),
    ])
    assert bd.main(["--bench", glob_b, "--opperf",
                    str(tmp_path / "none*.jsonl"),
                    "--fail-on-regression"]) == 0
    rounds = bd.fleet_verdicts(bd.load_bench(
        sorted(__import__("glob").glob(glob_b))), 0.15)
    assert all(rounds[r]["fleet_verdict"] is None for r in rounds)


def test_regenerated_opperf_smoke_has_percentiles():
    """Satellite: the committed OPPERF_smoke.jsonl was regenerated with
    the p50/p99 columns benchdiff trends tail latency from."""
    rows = []
    with open(os.path.join(_REPO, "OPPERF_smoke.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            if "op" in row and "avg_time_ms" in row:
                rows.append(row)
    assert rows
    assert all("p50_time_ms" in r and "p99_time_ms" in r
               for r in rows), "regenerate OPPERF_smoke.jsonl"
    assert all(r["p99_time_ms"] >= r["p50_time_ms"] >= 0
               for r in rows)
