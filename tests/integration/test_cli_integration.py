"""CLI integration tests: the analyzer driven as a subprocess.

Reference parity: tests/integration_tests/analysis_tests.py:9-60 and
tests/cmd_line_test.py:17-60 — golden-output style assertions on the jsonv2
report produced by the real command-line entry point, including the
concrete exploit calldata the solver synthesizes.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
KILL_SIMPLE = REPO / "tests" / "testdata" / "inputs" / "kill_simple.bin-runtime"


def _run_cli(*argv, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "mythril_tpu", *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=str(REPO),
    )


def test_analyze_jsonv2_selfdestruct():
    proc = _run_cli(
        "analyze",
        "-f", str(KILL_SIMPLE), "--bin-runtime",
        "-t", "1",
        "-m", "AccidentallyKillable",
        "-o", "jsonv2",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    issues = report[0]["issues"]
    assert len(issues) == 1
    issue = issues[0]
    assert issue["swcID"] == "SWC-106"
    assert issue["severity"] == "High"
    # exploit synthesis: the test case must call kill() (selector 0x41c0e1b5)
    steps = issue["extra"]["testCases"][0]["steps"]
    assert steps[-1]["input"].startswith("0x41c0e1b5")


def test_analyze_clean_contract_no_issues():
    # PUSH1 0; PUSH1 0; RETURN — nothing to report
    proc = _run_cli(
        "analyze", "-c", "0x60006000f3", "--bin-runtime", "-t", "1", "-o", "json"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["issues"] == []


def test_disassemble():
    proc = _run_cli("disassemble", "-c", "0x6001600101")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "PUSH1" in out and "ADD" in out


def test_list_detectors_names_all_14():
    proc = _run_cli("list-detectors")
    assert proc.returncode == 0, proc.stderr[-2000:]
    for name in [
        "ArbitraryJump", "ArbitraryStorage", "ArbitraryDelegateCall",
        "PredictableVariables", "TxOrigin", "EtherThief", "Exceptions",
        "ExternalCalls", "IntegerArithmetics", "MultipleSends",
        "StateChangeAfterCall", "AccidentallyKillable", "UncheckedRetval",
        "UserAssertions",
    ]:
        assert name in proc.stdout, f"missing detector {name}"


def test_function_to_hash():
    proc = _run_cli("function-to-hash", "transfer(address,uint256)")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "0xa9059cbb" in proc.stdout


def test_version():
    proc = _run_cli("version")
    assert proc.returncode == 0
    assert proc.stdout.strip()


def test_safe_functions():
    proc = _run_cli(
        "safe-functions", "-f", str(KILL_SIMPLE), "--bin-runtime"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_frontier_failure_fails_analyze(monkeypatch, capsys):
    """An exception inside the frontier segment fails ``myth analyze
    --frontier`` (non-zero exit); the host engine never silently takes
    over the paths the device was meant to run."""
    from mythril_tpu.frontier import engine
    from mythril_tpu.interfaces import cli
    from mythril_tpu.support.support_args import args

    def broken_segment(*_a, **_k):
        def segment(*_args):
            raise RuntimeError("injected segment failure")
        return segment

    monkeypatch.setattr(engine, "cached_segment", broken_segment)
    saved = dict(vars(args))
    monkeypatch.setattr(sys, "argv", [
        "myth", "analyze", "-f", str(KILL_SIMPLE), "--bin-runtime",
        "-t", "1", "-m", "AccidentallyKillable", "--frontier",
        "--frontier-force", "-o", "jsonv2",
    ])
    try:
        with pytest.raises(SystemExit) as exc:
            cli.main()
    finally:
        vars(args).clear()
        vars(args).update(saved)
    assert exc.value.code != 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["success"] is False
    assert "injected segment failure" in out["error"]
