"""Per-analysis accounts, the drain and the kernels' trace names.

Two ``myth analyze`` calls in one process: a ``flags`` contract whose
2 s execution timeout fires mid-exploration, and a ``killable`` contract
that ends well before its deadline."""

import json
import random

import numpy as np
import pytest

from benchmark.corpus import build_variant
from benchmark.entries.analyze import fresh_scope, run_cli
from mythril_tpu.observability.metrics import MetricsRegistry, get_registry

OPTIONS = ["-t", "2", "--frontier", "--frontier-width", "64", "-o", "jsonv2"]
CASES = {"timed_out": ("flags", {"k": 7}, "2"), "ends_early": ("killable", {}, "60")}


@pytest.fixture(scope="module")
def analysed():
    """name -> (jsonv2 report, the account the registry closed for it)."""
    out = {}
    for name, (family, params, timeout) in CASES.items():
        variant = build_variant(family, random.Random(5), 0, params)
        fresh_scope()
        rc, text = run_cli(["analyze", "-c", variant.hex, "--bin-runtime",
                            "--execution-timeout", timeout, *OPTIONS])
        assert rc == 0
        out[name] = (json.loads(text.strip().splitlines()[-1])[0],
                     get_registry().accounts()[-1])
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_report_carries_its_account(analysed, name):
    report, account = analysed[name]
    assert report["meta"]["account"] == account
    metrics = report["meta"]["observability"]["metrics"]
    assert account["metrics"] and all(
        metrics[k] == v for k, v in account["metrics"].items())
    assert account["start_ns"] < account["end_ns"]
    assert [(i["swc_id"], i["pc"]) for i in account["issues"]] == [
        (i["swcID"].replace("SWC-", ""), int(i["locations"][0]["sourceMap"].split(":")[0]))
        for i in report["issues"]]


@pytest.mark.parametrize("name, drains", [("timed_out", True), ("ends_early", False)])
def test_the_drain_is_counted_from_the_deadline(analysed, name, drains):
    drain = analysed[name][1]["metrics"]["frontier.drain_s"]
    assert (drain > 0) if drains else drain == 0


def test_two_analyses_close_two_disjoint_accounts(analysed):
    first, second = analysed["timed_out"][1], analysed["ends_early"][1]
    assert first["end_ns"] <= second["start_ns"]
    assert get_registry().accounts(second["start_ns"]) == [second]


def test_an_issue_carries_the_solver_time_before_it(analysed):
    (issue,) = analysed["ends_early"][1]["issues"]
    assert issue["swc_id"] == "106"
    assert 0 <= issue["solver_time_s"] <= analysed["ends_early"][1]["metrics"][
        "solver.solver_time_s"]
    start, end = (analysed["ends_early"][1][k] for k in ("start_ns", "end_ns"))
    assert start <= issue["discovery_ns"] <= end


def test_the_ring_of_accounts_is_bounded():
    reg = MetricsRegistry(account_ring=4)
    reg.counter("x").inc()
    for _ in range(10):
        reg.open_account()
        reg.close_account()
    accounts = reg.accounts()
    assert len(accounts) == 4
    assert all(a["metrics"]["x"] == 1 for a in accounts)
    assert [a["start_ns"] for a in accounts] == sorted(a["start_ns"] for a in accounts)


@pytest.mark.parametrize("module, name", [
    ("mythril_tpu.absdomain.device", "jit_absdomain_known_bits"),
    ("mythril_tpu.devsolver.device", "jit_devsolver_search"),
])
def test_the_solver_kernels_carry_their_own_names(module, name):
    """A device trace names an XLA module after the jitted function, so
    the two solver kernels must not share one (both were ``jit__run``)."""
    import importlib

    from mythril_tpu.absdomain import tape
    from mythril_tpu.devsolver import kernel
    from mythril_tpu.smt import terms

    mod = importlib.import_module(module)
    if name == "jit_absdomain_known_bits":
        x = terms.var("kernel_name_x", 256)
        args = mod.chunk_args(tape.pack([[terms.eq(x, terms.const(1, 256))]]), [0])
    else:
        plane = kernel.pack_plane([([[4]], [2])], n_vars=3)
        args = (plane.lits, plane.dec, np.zeros((plane.n_vars,), np.int8), np.int32(8))
    text = mod._get_jitted().lower(*args).as_text()
    assert f"module @{name}" in text
