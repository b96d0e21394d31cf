"""bench.py harness logic — the pure functions behind the perf-evidence
layers (golden normalization, roofline models, slope summaries, the
best-round regression guard).  No device work: these tests pin the MATH
so a harness edit cannot silently change what the recorded numbers mean."""

from __future__ import annotations

import os
import sys

import pytest

# same import pattern as test_core_utils.py: ONE shared bench module
# instance across the suite (a second importlib spec would re-execute
# bench.py's top level and split monkeypatch targets)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def test_metric_value_headline_vs_aux():
    rec = {"metric": "kmeans_iter_per_sec", "value": 9500.0, "cdist_gb_per_sec": 1000.0}
    assert bench._metric_value(rec, "kmeans_iter_per_sec") == 9500.0
    assert bench._metric_value(rec, "cdist_gb_per_sec") == 1000.0
    assert bench._metric_value(rec, "missing_metric") is None


def test_vs_golden_div_and_mul():
    results = {
        "metric": "kmeans_iter_per_sec",
        "value": 9000.0,
        "eager_ops_per_sec": 1000.0,
        "qr_svd_tall_skinny_ms": 4.0,
    }
    golden = {
        "kmeans_iter_per_sec": {"reduce_gb_per_sec": 750.0},
        "eager_ops_per_sec": {"roundtrip_ms": 100.0},
        # qr_svd is single-dispatch compute as of r6: its control is the
        # matmul golden, combined multiplicatively (ms x TFLOP/s move in
        # opposite directions under a machine slowdown)
        "qr_svd_tall_skinny_ms": {"matmul_tflops": 165.0},
    }
    out = bench._vs_golden(results, golden)
    assert out["kmeans_iter_per_sec"] == pytest.approx(12.0)      # div
    assert out["eager_ops_per_sec"] == pytest.approx(100000.0)    # mul
    assert out["qr_svd_tall_skinny_ms"] == pytest.approx(660.0)   # mul (ms x tflops)
    # a missing golden never fabricates a ratio
    assert "cdist_gb_per_sec" not in out


def test_vs_golden_stable_under_uniform_slowdown():
    # the design property: a machine slowdown moves metric and golden
    # together, so vs_golden is unchanged; a code regression moves only
    # the metric
    fast = bench._vs_golden(
        {"metric": "kmeans_iter_per_sec", "value": 10000.0},
        {"kmeans_iter_per_sec": {"reduce_gb_per_sec": 800.0}},
    )
    slow = bench._vs_golden(
        {"metric": "kmeans_iter_per_sec", "value": 8000.0},
        {"kmeans_iter_per_sec": {"reduce_gb_per_sec": 640.0}},
    )
    assert fast["kmeans_iter_per_sec"] == pytest.approx(
        slow["kmeans_iter_per_sec"]
    )
    regressed = bench._vs_golden(
        {"metric": "kmeans_iter_per_sec", "value": 8000.0},
        {"kmeans_iter_per_sec": {"reduce_gb_per_sec": 800.0}},
    )
    assert regressed["kmeans_iter_per_sec"] < fast["kmeans_iter_per_sec"]


def test_roofline_rates_and_bounds():
    results = {
        "metric": "kmeans_iter_per_sec",
        "value": 9500.0,
        "attention_tokens_per_sec": 3.4e6,
        "cdist_gb_per_sec": 1000.0,
        "global_sum_gb_per_sec": 750.0,
    }
    peaks = bench.peaks_for("TPU v5 lite")
    roof = bench._roofline(results, peaks)
    km = roof["kmeans_iter_per_sec"]
    flops, bytes_, _, _ = bench._work_models()["kmeans_iter_per_sec"]
    assert km["achieved_tflops"] == pytest.approx(flops * 9500.0 / 1e12, rel=1e-2)
    assert km["achieved_gb_per_sec"] == pytest.approx(bytes_ * 9500.0 / 1e9, rel=1e-2)
    assert km["bound"] == "hbm"
    # attention: tokens/s -> forwards/s through ATTN_S
    at = roof["attention_tokens_per_sec"]
    aflops = bench._work_models()["attention_tokens_per_sec"][0]
    assert at["achieved_tflops"] == pytest.approx(
        aflops * 3.4e6 / bench.ATTN_S / 1e12, rel=1e-2
    )
    assert at["bound"] == "compute"
    # GB/s metrics back out reps/s through their measurement bytes
    gs = roof["global_sum_gb_per_sec"]
    assert gs["achieved_gb_per_sec"] == pytest.approx(750.0, rel=1e-2)
    # the hbm percentage always refers to the declared peak
    assert gs["pct_hbm_roofline"] == pytest.approx(
        100 * 750.0 / peaks["hbm_gb_per_sec"], rel=1e-2
    )
    # irregular metrics stay out, with reasons
    assert "kmedoids_iter_per_sec" in roof["not_modeled"]


def test_peaks_are_keyed_by_device_kind_and_unknown_kind_is_an_error():
    peaks = bench.peaks_for("TPU v5 lite")
    assert peaks["source"]
    assert peaks["f32_highest_tflops"] == pytest.approx(peaks["bf16_tflops"] / 6)
    with pytest.raises(KeyError, match="no peaks recorded"):
        bench.peaks_for("cpu")


def test_summary_median_and_spread_semantics():
    med, spread = bench._summary([10.0, 11.0, 9.0, 10.5, 9.5])
    assert med == 10.0
    assert spread is not None and spread > 0
    # fewer than 3 estimates: spread must be UNKNOWN (None), never 0.0
    med2, spread2 = bench._summary([10.0, 12.0])
    assert spread2 is None


def test_every_headline_has_group_and_disposition_coverage():
    # structural invariants the JSON consumers rely on
    for key in bench._HEADLINE:
        assert key in bench._METRIC_GROUP, key
        assert key in bench._GOLDEN_MAP, key
    models = bench._work_models()
    for key in bench._HEADLINE:
        assert key in models or key in bench._NOT_MODELED, (
            f"{key} neither roofline-modeled nor excluded-with-reason"
        )


def test_causal_attention_work_model_is_triangular():
    # the causal model must claim ~HALF the full forward's FLOPs (the
    # triangular schedule's visited tiles), not n^2 — the roofline % is
    # only meaningful against work actually launched
    models = bench._work_models()
    full = models["attention_tokens_per_sec"][0]
    causal = models["causal_attention_tokens_per_sec"][0]
    s = bench.ATTN_S
    assert causal == pytest.approx(full * (s + bench.ATTN_BQ) / (2 * s))
    # the f32 pair: same schedule (same FLOPs), f32 bytes, HIGHEST peak
    f32 = models["causal_attention_f32_tokens_per_sec"]
    assert f32[0] == causal
    assert f32[1] == 2 * models["causal_attention_tokens_per_sec"][1]
    assert f32[2] == "f32_highest_tflops"


def _fake_full_result():
    """A representative full result for the compact-line contract tests,
    with every headline populated at realistic magnitudes."""
    rec = {
        "metric": "kmeans_iter_per_sec",
        "value": 9888.25,
        "unit": "iter/s",
        "vs_baseline": 123.45,
        "cdist_gb_per_sec": 1354.12,
        "moments_gb_per_sec": 797.33,
        "global_sum_gb_per_sec": 694.01,
        "allreduce_q_gbps": 212.5,
        "allreduce_exact_gb_per_sec": 80.3,
        "allreduce_q_vs_exact": 2.646,
        "resplit_gbps": 310.4,
        "resplit_monolithic_gb_per_sec": 96.7,
        "resplit_vs_monolithic": 3.21,
        "summa2d_tflops": 41.2,
        "summa1d_tflops": 37.8,
        "matmul_replicated_tflops": 44.1,
        "summa2d_vs_replicated": 0.934,
        "qr2d_tflops": 18.4,
        "qr1d_tflops": 15.2,
        "qr2d_vs_1d": 1.21,
        "svd2d_tflops": 22.7,
        "kmedians_iter_per_sec": 1063.5,
        "kmedians_churn_iter_per_sec": 143.21,
        "kmedoids_iter_per_sec": 10466.7,
        "eager_ops_per_sec": 3021.9,
        "fused_pipeline_ms": 0.42,
        "eager_pipeline_ms": 2.31,
        "autoshard_speedup": 1.29,
        "lasso_sweeps_per_sec": 1318.6,
        "serve_predictions_per_sec": 9919.9,
        "serve_p99_ms": 27.32,
        "replica_cold_start_ms": 24.6,
        "scale_event_p99_ms": 36.6,
        "fleet_aggregate_pps": 8212.4,
        "hedged_tail_p99_ms": 48.7,
        "unhedged_tail_p99_ms": 262.4,
        "stream_fit_rows_per_sec": 2100000.5,
        "stream_overlap_efficiency": 1.62,
        "qr_svd_tall_skinny_ms": 2.87,
        "attention_tokens_per_sec": 3400000.0,
        "causal_attention_tokens_per_sec": 3700000.0,
        "causal_attention_f32_tokens_per_sec": 620000.0,
        "ring_overlap_efficiency": 0.87,
        "spread_pct": {k: 12.3 for k in bench._HEADLINE},
        "golden": {
            "health": {
                "matmul_tflops": 0.843,
                "reduce_gb_per_sec": 0.852,
                "roundtrip_ms": 1.113,
            }
        },
        "platform": "tpu",
    }
    rec["vs_golden"] = {k: 123.456 for k in bench._GOLDEN_MAP}
    rec["roofline"] = bench._roofline(rec, bench.peaks_for("TPU v5 lite"))
    return rec


def test_compact_line_is_self_contained_and_small():
    import json

    rec = _fake_full_result()
    line = bench._compact_line(rec)
    text = json.dumps(line, separators=(",", ":"))
    # the driver-facing contract: one line, < ~1500 chars
    assert len(text) < 1500, f"compact line too long: {len(text)}"
    # headline contract keys survive
    assert line["metric"] == "kmeans_iter_per_sec"
    assert line["value"] == rec["value"]
    # every headline carries its [value, vs_golden, roofline_pct?] triple
    for key in bench._HEADLINE:
        assert key in line, key
        entry = line[key]
        expect = rec["value"] if key == rec["metric"] else rec[key]
        assert entry[0] == expect, key
        assert entry[1] == round(rec["vs_golden"][key], 2), key
    assert line["golden_health"] == rec["golden"]["health"]
    # modeled metrics get the roofline %-of-peak third slot; dispositioned
    # ones (bench._NOT_MODELED) stay a pair
    assert len(line["attention_tokens_per_sec"]) == 3
    assert line["attention_tokens_per_sec"][2] is not None
    assert len(line["serve_predictions_per_sec"]) == 2
    assert line["full_report"] == "BENCH_FULL.json"
    # the verbose layers stay OUT of the line
    assert "spread_pct" not in line and "roofline" not in line
    assert "vs_golden" not in line and "roofline_pct" not in line


def test_regression_guard_uses_best_round(tmp_path, monkeypatch):
    import json

    d = tmp_path
    (d / "BENCH_r01.json").write_text(json.dumps(
        {"metric": "kmeans_iter_per_sec", "value": 9000.0,
         "cdist_gb_per_sec": 1300.0}
    ))
    (d / "BENCH_r02.json").write_text(json.dumps(
        {"metric": "kmeans_iter_per_sec", "value": 9500.0,
         "cdist_gb_per_sec": 1000.0}
    ))
    # patch glob on the bench instance (test_core_utils.py convention):
    # zero process-global footprint, unlike patching os.path.dirname
    import glob as _glob

    real = sorted(_glob.glob(os.path.join(str(d), "BENCH_r*.json")))
    monkeypatch.setattr(bench.glob, "glob", lambda pat: real)
    flagged = bench.regression_check(
        {"metric": "kmeans_iter_per_sec", "value": 9400.0,
         "cdist_gb_per_sec": 900.0}
    )
    # kmeans 9400 vs best 9500 is within 10% -> not flagged
    assert "kmeans_iter_per_sec" not in flagged
    # cdist 900 vs BEST round (1300, r1 — not the latest round) -> flagged
    assert flagged["cdist_gb_per_sec"]["best"] == 1300.0
    assert flagged["cdist_gb_per_sec"]["best_round"] == 1
