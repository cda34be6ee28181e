"""Drift doctor: ranked attribution over bench pairs and history windows."""

import json

from mythril_tpu.observability.drift import (
    attribute,
    diff_history_windows,
    diff_tables,
    format_drift,
    load_bench_table,
)


def _row(production=100.0, baseline=50.0, **over):
    row = {
        "unit": "states/sec",
        "baseline": baseline,
        "production": production,
        "speedup": round(production / baseline, 3),
        "reps": 3,
        "spread": {"production": [production * 0.95, production * 1.05]},
        "ttfe_s": {"baseline": 2.0, "production": 1.0},
        "harvest_share_pct": 20.0,
        "harvest_phase_s": {
            "ingest": 0.1, "solver": 1.0, "replay": 0.5, "commit": 0.1,
        },
        "device_residency_pct": 80.0,
    }
    row.update(over)
    return row


def test_ranked_regression_tops_synthetic_pair():
    prior = {"fast": _row(), "slow": _row()}
    current = {
        "fast": _row(),
        # halve the rate, double solver wall: both should rank, rate first
        "slow": _row(production=50.0,
                     harvest_phase_s={"ingest": 0.1, "solver": 2.0,
                                      "replay": 0.5, "commit": 0.1}),
    }
    report = diff_tables(prior, current, "A.json", "B.json")
    assert report["mode"] == "bench"
    assert report["workloads_compared"] == ["fast", "slow"]
    top = report["ranked"][0]
    assert top["workload"] == "slow"
    assert top["direction"] == "regressed"
    # headline names the violator
    assert "slow" in report["headline"]
    metrics = {f["metric"] for f in report["ranked"]}
    assert "harvest_phase_s.solver" in metrics


def test_regression_outranks_equal_improvement():
    # +50% coverage vs -50% coverage, same weight: the regression wins
    prior = {"up": _row(exploration={"coverage_pct": 40.0}),
             "down": _row(exploration={"coverage_pct": 40.0})}
    current = {"up": _row(exploration={"coverage_pct": 60.0}),
               "down": _row(exploration={"coverage_pct": 20.0})}
    report = diff_tables(prior, current)
    cov = [f for f in report["ranked"]
           if f["metric"] == "exploration.coverage_pct"]
    assert [f["workload"] for f in cov] == ["down", "up"]
    assert cov[0]["direction"] == "regressed"
    assert cov[1]["direction"] == "improved"
    assert cov[0]["score"] > cov[1]["score"]


def test_movement_below_noise_floor_is_dropped():
    prior = {"w": _row(production=100.0)}
    current = {"w": _row(production=101.0)}  # +1% < 2% floor
    report = diff_tables(prior, current)
    assert not any(f["metric"] == "production_rate"
                   for f in report["ranked"])
    empty = diff_tables({"w": _row()}, {"w": _row()})
    assert empty["ranked"] == []
    assert empty["headline"] == "drift: no metric moved beyond noise"


def test_relative_movement_is_capped():
    # 0.001 -> 10: a 10000x transition must not drown everything; the
    # rel is clipped to +300%
    prior = {"w": _row(harvest_share_pct=0.001)}
    current = {"w": _row(harvest_share_pct=10.0)}
    report = diff_tables(prior, current)
    f = next(f for f in report["ranked"]
             if f["metric"] == "harvest_share_pct")
    assert f["rel_pct"] == 300.0


def test_torn_inputs_are_data_not_errors():
    prior = {"gone": _row(), "shared": _row(),
             "broken": "not-a-row"}
    current = {"shared": {"production": "NaN-ish", "baseline": None},
               "new": _row()}
    report = diff_tables(prior, current)
    assert report["only_in_prior"] == ["broken", "gone"]
    assert report["only_in_current"] == ["new"]
    # the shared row's non-numeric values are skipped, not fatal
    assert all(f["workload"] == "shared" or False
               for f in report["ranked"]) or report["ranked"] == []
    # wholly non-dict inputs degrade to an empty comparison
    assert diff_tables(None, [1, 2])["workloads_compared"] == []


def test_attribute_filters_by_workload():
    prior = {"a": _row(), "b": _row()}
    current = {"a": _row(production=20.0), "b": _row(production=99.0)}
    report = diff_tables(prior, current)
    assert "a" in attribute(report, workload="a")
    line_b = attribute(report, workload="b")
    assert "b" in line_b or line_b.startswith("drift: no metric")
    assert attribute(report, workload="nope").startswith(
        "drift: no metric moved")


def test_format_drift_renders_ranked_table():
    prior = {"w": _row()}
    current = {"w": _row(production=10.0)}
    text = format_drift(diff_tables(prior, current, "old", "new"), limit=3)
    assert "drift report  old -> new" in text
    assert "production_rate" in text
    assert "REGRESSED" in text
    assert text.strip().endswith(attribute(diff_tables(prior, current)))


def test_load_bench_table_all_formats(tmp_path):
    table = {"w": _row()}
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({"workloads": table, "metric": "x"}))
    assert load_bench_table(str(snap)) == table

    wrapper = tmp_path / "wrapper.json"
    wrapper.write_text(json.dumps({"rc": 0, "parsed": {"workloads": table}}))
    assert load_bench_table(str(wrapper)) == table

    # torn tail: last parseable snapshot line wins
    torn = tmp_path / "torn.json"
    torn.write_text(json.dumps({
        "rc": 124, "parsed": None,
        "tail": "garbage\n" + json.dumps({"workloads": table})
        + "\n{\"workloads\": {truncated",
    }))
    assert load_bench_table(str(torn)) == table

    assert load_bench_table(str(tmp_path / "missing.json")) == {}
    bad = tmp_path / "bad.json"
    bad.write_text("not json at all")
    assert load_bench_table(str(bad)) == {}


def test_history_window_mode_ranks_counter_acceleration():
    # counter: 1/s in the prior window, 5/s in the recent one;
    # histogram: avg 10ms -> 40ms; labeled map: flat
    samples = []
    total = 0.0
    hist_c, hist_s = 0, 0.0
    for t in range(0, 121, 10):
        rate = 1.0 if t <= 60 else 5.0
        total += rate * 10
        hist_c += 10
        hist_s += (0.01 if t <= 60 else 0.04) * 10
        samples.append((float(t), {
            "service.requests": total,
            "frontier.segment_device_s": {
                "c": hist_c, "s": round(hist_s, 4), "mn": 0.001,
                "mx": 0.1, "bc": [hist_c, 0, 0],
            },
            "device.cache_hits_by_bucket": {"1x2x3x4": 7},
        }))
    report = diff_history_windows(samples, window_s=60.0)
    assert report["mode"] == "history"
    by_metric = {f["metric"]: f for f in report["ranked"]}
    assert by_metric["service.requests"]["direction"] == "moved"
    assert by_metric["service.requests"]["current"] > \
        by_metric["service.requests"]["prior"]
    assert "frontier.segment_device_s.avg_s" in by_metric
    # the flat labeled map did not move
    assert "device.cache_hits_by_bucket.total" not in by_metric
    assert report["headline"].startswith("drift: most-moved")


def test_history_window_mode_empty():
    report = diff_history_windows([], window_s=60.0)
    assert report["ranked"] == []
    assert report["headline"] == "drift: history is empty"

