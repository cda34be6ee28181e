"""Whole runs of the harness on the CPU at a test's size, with the look for
a chip skipped: a cell made of new files alone, the control and the
faults that must turn ``correct`` false, and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark.corpus as corpus
import benchmark.loadgen as loadgen
import benchmark.metrics as metrics
from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

NEW_METRIC = '''"""Issues reported per request: a metric added as a file alone."""


def read(run):
    return sum(len(r.issues) for r in run.requests) / max(1, len(run.requests))
'''


@pytest.fixture
def bench_root(tmp_path, monkeypatch):
    """A copy of the benchmark's data with a new configuration, traffic
    mix, family, metric and cell added as files and entries only."""
    src = os.path.join(REPO, "benchmark")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(src, sub), tmp_path / sub)
    shutil.copytree(os.path.join(src, "corpus", "families"), tmp_path / "families")
    shutil.copy(tmp_path / "families" / "killable.py", tmp_path / "families" / "killable2.py")
    (tmp_path / "metrics" / "test.issues_per_request.py").write_text(NEW_METRIC)
    (tmp_path / "traffic" / "tiny.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "families": {"killable2": 1}, "pool": 0}))
    (tmp_path / "traffic" / "tiny_serve.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "families": {"killable2": 1}, "pool": 0}))
    audit = json.load(open(os.path.join(src, "configs", "audit_t2.json")))
    audit["options"] = ["64" if o == "1024" else o for o in audit["options"]]
    (tmp_path / "configs" / "audit_tiny.json").write_text(json.dumps(audit))
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec["configs"] += [
        {"name": "audit_tiny", "source": "test", "file": "configs/audit_tiny.json",
         "reduced": [], "why": "test"},
        {"name": "scan_t2", "source": "test", "file": "configs/scan_t2.json",
         "reduced": [], "why": "test"},
    ]
    spec["workloads"] += [
        {"name": "test.kill", "config": "audit_tiny", "traffic": "tiny",
         "chips": 1, "why": "test"},
        {"name": "test.scan", "config": "scan_t2", "traffic": "tiny_serve",
         "chips": 1, "why": "test"},
    ]
    for cell in ("test.kill", "test.scan"):
        for m in spec["end_to_end"] + spec["per_layer"]:
            m.setdefault("workloads", []).append(cell)
    spec["per_layer"].append({
        "name": "test.issues_per_request", "unit": "issues", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "ttfe_p50_s",
        "workloads": ["test.kill"]})
    spec["configs"] = [dict(c, file=c["file"].replace("benchmark/", "")) for c in spec["configs"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(loadgen, "TRAFFIC_DIR", str(tmp_path / "traffic"))
    monkeypatch.setattr(corpus, "FAMILY_DIR", str(tmp_path / "families"))
    monkeypatch.setattr(metrics, "METRIC_DIR", str(tmp_path / "metrics"))
    corpus.load_family.cache_clear()
    yield tmp_path
    corpus.load_family.cache_clear()


def run(cell, seconds=1.0, trace=False, **kw):
    return harness.execute(cell, 2 ** 31 + 11, seconds, trace, 0.0, device=CPU, **kw)


def test_new_files_alone_make_a_cell(bench_root):
    res = run("test.kill")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"ttfe_p50_s", "contracts_per_min", "setup_s"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    assert res["device"]["memory_peak_bytes"] >= 0
    traced = run("test.kill", trace=True)
    assert traced["metrics"]["test.issues_per_request"]["value"] == 1.0
    assert "ttfe_p50_s" not in traced["metrics"]


def test_control_with_a_module_left_out_is_not_correct(bench_root):
    from benchmark.readings import modules_without

    res = run("test.kill", modules=modules_without("AccidentallyKillable"))
    assert not res["correct"]
    assert res["checks"]["missed_issues"]["value"] >= res["attempted"] >= 1


def test_an_answer_altered_where_it_is_produced_is_not_correct(bench_root, monkeypatch):
    from mythril_tpu.analysis import report

    produce = report.Report.as_swc_standard_format

    def altered(self):
        out = json.loads(produce(self))
        for issue in out[0]["issues"]:
            pc, rest = issue["locations"][0]["sourceMap"].split(":", 1)
            issue["locations"][0]["sourceMap"] = f"{int(pc) + 1}:{rest}"
        return json.dumps(out)

    monkeypatch.setattr(report.Report, "as_swc_standard_format", altered)
    res = run("test.kill")
    assert not res["correct"]
    assert res["checks"]["unexpected_issues"]["value"] >= 1


def test_serve_cell_and_half_of_each_batch_left_out(bench_root, monkeypatch):
    sound = run("test.scan", seconds=1.0)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 2

    from mythril_tpu.analysis import cooperative

    batch = cooperative.run_cooperative_batch

    def half(jobs, *a, **kw):
        issues, errors, states = batch(jobs, *a, **kw)
        for name, _code in list(jobs)[::2]:
            issues[name] = []  # half of the batch's answers left out
        return issues, errors, states

    monkeypatch.setattr(cooperative, "run_cooperative_batch", half)
    broken = run("test.scan", seconds=1.0)
    assert not broken["correct"]
    assert broken["checks"]["missed_issues"]["value"] >= 1


def test_a_device_that_is_not_a_tpu_is_refused():
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.check_device(1)


def test_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "audit.wide",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refused" in proc.stderr
