#!/usr/bin/env python3
"""Chip smoke: drive mythril-tpu's main path once on a TPU, in ONE process.

    python chip_smoke.py              # one chip: phases 1-5
    python chip_smoke.py --chips 4    # the path-sharded frontier vs one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

Phases (one line each, with wall time, compile time and counters):

1. devices — what JAX reports; anything but a TPU fails (a rehearsal runs on,
   but still ends non-zero with no ``ok`` line).
2. ``myth analyze`` of the BECToken-shaped contract at ``-t 2`` (the
   reference default), ``--frontier --frontier-width 1024``: SWC-101
   reported, device instructions and segments > 0, the probe dispatched to
   the device, ``meta.device.backend == "tpu"``.
3. the 1024-path wide contract, device-resident at width 1024 with every
   host-routing policy bypassed: none may fire; issue set identical to a
   host-engine run.
4. device kernels against their host twins at production batch sizes:
   Pallas keccak (compiled) vs the native keccak, the tape-VM probe, the
   absdomain known-bits pass and the devsolver kernel, bit for bit.
5. ``myth serve --workers 1`` on this process's main thread: three requests
   through ``service/client.py`` each equal a solo ``analyze``, then a
   SIGTERM drain returns 0.

Everything runs in this one process: a chip belongs to one process, so no
child may touch it.  ``--rehearse`` only shrinks the widths and runs the
Pallas kernel in interpret mode.  The last line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import random
import signal
import socket
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------


def run_cli(argv):
    """``python -m mythril_tpu <argv>`` in this process: (exit code, stdout)."""
    from mythril_tpu.interfaces import cli

    out = io.StringIO()
    saved = sys.argv
    sys.argv = ["myth", *argv]
    code = 0
    try:
        with contextlib.redirect_stdout(out):
            try:
                cli.main()
            except SystemExit as e:
                code = e.code if isinstance(e.code, int) else 1
    finally:
        sys.argv = saved
    return code, out.getvalue()


def analyze(argv):
    """One jsonv2 ``analyze`` run: (report dict, frontier stats, probes).

    Each run starts from a fresh analysis scope, as a one-shot process
    would: the detectors' per-(address, code) caches would otherwise
    suppress findings an earlier run in this process already reported."""
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.observability.metrics import get_registry

    reset_analysis_scope()
    code, out = run_cli(["analyze", *argv, "-o", "jsonv2"])
    check(code == 0, f"analyze {argv} exited {code}")
    report = json.loads(out.strip().splitlines()[-1])[0]
    probes = get_registry().counter("smt.device_probe_dispatches").value
    return report, FrontierStatistics().as_dict(), probes


def issue_set(report):
    """(swc, pc) per finding of a jsonv2 report."""
    return sorted(
        (i["swcID"].replace("SWC-", ""),
         int(i["locations"][0]["sourceMap"].split(":")[0]))
        for i in report["issues"]
    )


def host_routes():
    from mythril_tpu.observability.metrics import get_registry

    return dict(get_registry().labeled_counter(
        "device.host_routes", persistent=True))


def compile_totals():
    from mythril_tpu.observability.metrics import get_registry

    reg = get_registry()
    return (
        float(reg.counter("device.compile_wall_s_total",
                          persistent=True).value),
        int(reg.counter("compilecache.hits", persistent=True).value),
        int(reg.counter("compilecache.misses", persistent=True).value),
    )


class Phase:
    """Times one phase and prints its line: wall, compile, counters."""

    def __init__(self, n, name):
        self.n, self.name, self.counters = n, name, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = compile_totals()[0]
        self.r0 = host_routes()
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        comp = compile_totals()[0] - self.c0
        routes = {k: v - self.r0.get(k, 0) for k, v in host_routes().items()
                  if v - self.r0.get(k, 0)}
        status = "ok" if exc_type is None else f"FAILED: {exc!r}"
        print(f"phase {self.n} {self.name}: {status} wall_s={wall:.3f} "
              f"compile_s={comp:.3f} host_routes={json.dumps(routes)} "
              f"counters={json.dumps(self.counters, sort_keys=True)}",
              flush=True)
        return False


def contract_hex(code: bytes) -> str:
    return "0x" + code.hex()


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_devices(opts):
    import jax

    from mythril_tpu.native.build import build_library
    from mythril_tpu.support.device import local_tpu_chips

    with Phase(1, "devices") as ph:
        devs = jax.devices()
        d0 = devs[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs)}
        # the chip count `myth serve --workers N` is checked against
        ph.counters = dict(device, devices=[str(d) for d in devs],
                           local_tpu_chips=local_tpu_chips())
        # the native library backs the CDCL tier and the host keccak twin:
        # built from the committed sources here, or the smoke fails
        ph.counters["native_lib"] = os.path.basename(build_library())
        if not opts.rehearse:
            check(d0.platform == "tpu",
                  f"JAX found no TPU (platform {d0.platform!r})")
        if opts.chips > 1:
            check(len(devs) == opts.chips,
                  f"{opts.chips} chips asked, {len(devs)} found")
    return device


def phase_bectoken(opts, tmp, on_tpu):
    from bench_contracts import bectoken_like

    from mythril_tpu.support import calibration

    path = os.path.join(tmp, "bectoken.bin-runtime")
    with open(path, "w") as f:
        f.write(bectoken_like().hex())
    # The reference's 86400 s execution timeout is cut to 180 s so all five
    # phases fit the driver's 1200 s (run to its end, this analysis takes
    # ~5 min of host solving on the CPU); batchTransfer's SWC-101 confirmed
    # inside that budget on the chip (PR 21).  The probe is pinned to the
    # device: under "auto" this contract sends almost no query past the
    # break-even (2 of 3 fell under it in the first chip run, PR 21), and
    # this phase checks that the device path runs.  Pinned, it pays one XLA
    # compile per conjunction the tape VM cannot express: three DAGs of
    # 723-898 nodes, ~390 s together on the chip (PR 21).
    argv = ["-f", path, "--bin-runtime", "-t", "2", "-m", "IntegerArithmetics",
            "--execution-timeout", "180", "--probe-backend", "jax",
            "--frontier", "--frontier-width", str(opts.width)]
    with Phase(2, "analyze-bectoken") as ph:
        report, fstats, probes = analyze(argv)
        if fstats["segments"] == 0:
            # the narrow gate kept this contract on the host at these flags;
            # this phase checks the device path runs a real contract
            ph.counters["narrow_gate_kept_on_host"] = True
            report, fstats, probes = analyze(argv + ["--frontier-force"])
        swcs = {s for s, _pc in issue_set(report)}
        ph.counters.update(
            issues=issue_set(report),
            device_instructions=fstats["device_instructions"],
            segments=fstats["segments"], probe_dispatches=probes,
            backend=report["meta"]["device"]["backend"],
            compile_s_by_bucket=report["meta"]["device"][
                "compile_wall_s_by_bucket"],
            calibration=calibration.telemetry(),
        )
        check("101" in swcs, f"SWC-101 not reported: {sorted(swcs)}")
        check(fstats["device_instructions"] > 0, "no device instructions")
        check(fstats["segments"] > 0, "no device segments")
        if on_tpu:
            check(probes > 0, "the probe never dispatched to the device")
            check(report["meta"]["device"]["backend"] == "tpu",
                  "report meta.device.backend is not tpu")
            check(calibration.telemetry().get("dispatch_rtt_ms") is not None,
                  "calibration did not measure dispatch_rtt_ms")


def wide_device_run(opts, extra=()):
    """Phase 3's analysis: the 1024-path wide contract, device-resident."""
    from bench_contracts import wide_contract

    from mythril_tpu.absdomain import device as kb_device
    from mythril_tpu.devsolver import device as ds_device
    from mythril_tpu.observability.metrics import get_registry

    # compile the solver's device tiers first, as a warm `myth serve`
    # process has them: their warming is a host-routing policy, which must
    # not fire inside this analysis
    kb_device.warmup()
    ds_device.warmup()
    code = contract_hex(wide_contract(opts.wide_branches))
    report, fstats, _ = analyze([
        "-c", code, "--bin-runtime", "-t", "1", "-m", "AccidentallyKillable",
        "--frontier", "--frontier-force", "--frontier-width", str(opts.width),
        "--probe-backend", "jax", *extra,
    ])
    fstats["mesh_fallbacks"] = get_registry().counter(
        "frontier.mesh_fallbacks").value
    return code, report, fstats


def phase_wide(opts):
    with Phase(3, "wide-frontier") as ph:
        r0 = host_routes()
        code, report, fstats = wide_device_run(opts)
        fired = {k: v - r0.get(k, 0) for k, v in host_routes().items()
                 if v - r0.get(k, 0)}
        host_report, _, _ = analyze([
            "-c", code, "--bin-runtime", "-t", "1", "-m",
            "AccidentallyKillable", "--probe-backend", "host",
        ])
        ph.counters.update(
            issues=issue_set(report), host_issues=issue_set(host_report),
            width=opts.width, **fstats,
        )
        check(not fired, f"host-routing policies fired: {fired}")
        check(fstats["segments"] > 0 and fstats["device_instructions"] > 0,
              "wide contract did not run on the device")
        check("106" in {s for s, _pc in issue_set(report)},
              "selfdestruct not reported")
        check(issue_set(report) == issue_set(host_report),
              "device issue set differs from the host engine's")


def phase_kernels(opts):
    import jax
    import numpy as np

    with Phase(4, "kernels-vs-host") as ph:
        ph.counters["keccak"] = _kernel_keccak(opts, jax, np)
        ph.counters["tape_vm"] = _kernel_tape_vm(np)
        ph.counters["absdomain"] = _kernel_absdomain(np)
        ph.counters["devsolver"] = _kernel_devsolver(np)
        from mythril_tpu.absdomain import device as kb_device
        from mythril_tpu.devsolver import device as ds_device
        from mythril_tpu.ops import tape_vm

        states = {"tape_vm": tape_vm._WARMUP.state,
                  "absdomain": kb_device._WARMUP.state,
                  "devsolver": ds_device._WARMUP.state}
        ph.counters["warmup_states"] = states
        check(all(s == "ready" for s in states.values()),
              f"a device warmup did not end ready: {states}")


def _kernel_keccak(opts, jax, np):
    """Pallas keccak (compiled on the chip) vs the native host keccak."""
    from functools import partial

    from mythril_tpu.native import keccak as native_keccak
    from mythril_tpu.ops import bitvec as bv
    from mythril_tpu.ops import keccak_pallas
    from mythril_tpu.ops.keccak_jax import keccak256

    permute = partial(keccak_pallas.keccak_f1600, interpret=opts.rehearse)
    rng = random.Random(0x6B656363)
    n = opts.batch
    out = {}
    for width in (256, 512):
        values = [rng.getrandbits(width) for _ in range(n)]
        digest = jax.jit(lambda d, w=width: keccak256(d, w, permute=permute))
        got = np.asarray(digest(bv.from_ints(values, width)))
        want = native_keccak.keccak256_batch(
            [v.to_bytes(width // 8, "big") for v in values])
        check(want is not None, "native keccak unavailable")
        got_ints = [sum(int(limb) << (16 * i) for i, limb in enumerate(row))
                    for row in got]
        check(got_ints == [int.from_bytes(w, "big") for w in want],
              f"Pallas keccak-{width} differs from the native keccak")
        out[f"preimage_{width}"] = n
    return out


def _kernel_tape_vm(np):
    """The tape-VM probe at the B=4096 bucket vs host concrete evaluation."""
    from mythril_tpu.ops import tape_vm
    from mythril_tpu.smt import terms
    from mythril_tpu.smt.concrete_eval import Assignment, evaluate
    from mythril_tpu.support.support_args import args

    x, y = terms.var("smoke_x", 256), terms.var("smoke_y", 256)
    conj = [
        terms.ult(x, terms.const(1 << 200, 256)),
        terms.eq(terms.band(terms.mul(x, y), terms.const(0xF, 256)),
                 terms.const(0x4, 256)),
        terms.ule(terms.add(x, y), terms.const((1 << 256) - 7, 256)),
        terms.ne(terms.keccak(x), terms.const(0, 256)),
    ]
    rng = random.Random(0x7A9E)
    cands = []
    for _ in range(4096):
        asg = Assignment()
        asg.scalars[x] = rng.getrandbits(rng.choice((8, 64, 201, 256)))
        asg.scalars[y] = rng.getrandbits(rng.choice((16, 256)))
        cands.append(asg)
    saved = args.probe_backend
    args.probe_backend = "jax"
    try:
        tape_vm.warmup()
        compiled = tape_vm.compile_tape(conj)
        truth = compiled.evaluate_batch(cands)
    finally:
        args.probe_backend = saved
    want = np.array([[bool(evaluate(conj, a)[c]) for c in conj] for a in cands])
    check(np.array_equal(np.asarray(truth, bool), want),
          "tape VM differs from host evaluation")
    return {"batch": len(cands), "shape": list(compiled.tensors["shape"]),
            "sat_rows": int(want.all(axis=1).sum())}


def _kernel_absdomain(np):
    """Device known-bits pass vs the host numpy pass, 64 rows."""
    from mythril_tpu.absdomain import device, domains, tape
    from mythril_tpu.smt import terms

    rng = random.Random(0xAB5D)
    rows = []
    for i in range(64):
        x, y = terms.var(f"smoke_kb_x{i}", 256), terms.var(f"smoke_kb_y{i}", 256)
        prod = terms.mul(terms.zext(x, 256), terms.zext(y, 256))
        rows.append([
            terms.ult(x, terms.const(rng.getrandbits(64), 256)),
            terms.eq(terms.band(x, terms.const(0xFF, 256)),
                     terms.const(rng.getrandbits(8), 256)),
            terms.lnot(terms.ult(prod, terms.const(1 << 256, 512))),
            terms.ult(terms.add(x, y), terms.const(1 << 128, 256)),
        ])
    pack = tape.pack(rows)
    device.warmup()
    want = domains.eval_kb_host(pack)
    got = device.run_kb(pack)
    for w, g in zip(want, got):
        check(np.array_equal(w, np.asarray(g)),
              "absdomain known-bits device pass differs from host")
    return {"rows": pack.n_rows, "nodes": pack.n_nodes,
            "refuted": int(np.asarray(got[2]).sum())}


def _kernel_devsolver(np):
    """Device DPLL kernel vs its host twin on 16 blasted queries."""
    from mythril_tpu import devsolver
    from mythril_tpu.devsolver import blaster, device, kernel
    from mythril_tpu.smt import terms

    rng = random.Random(0xD5)
    blasted = []
    while len(blasted) < kernel.Q_BUCKETS[-1]:
        i = len(blasted)
        x, y = terms.var(f"smoke_ds_x{i}", 32), terms.var(f"smoke_ds_y{i}", 32)
        row = [
            terms.eq(terms.add(x, y), terms.const(rng.getrandbits(32), 32)),
            terms.ult(terms.bxor(x, y), terms.const(rng.getrandbits(32), 32)),
            terms.ne(terms.sub(x, terms.const(rng.getrandbits(32), 32)), y),
        ]
        b = blaster.blast(row)
        if b.verdict is None:
            blasted.append(b)
    plane = kernel.pack_plane([(b.clauses, b.dec_vars) for b in blasted],
                              max(b.n_vars for b in blasted))
    iters = devsolver._config["iters"]
    device.warmup()
    s_host, a_host = kernel.run_host(plane, iters)
    s_dev, a_dev = device.run_device(plane, iters)
    check(np.array_equal(s_host, s_dev) and np.array_equal(a_host, a_dev),
          "devsolver device kernel differs from its host twin")
    return {"queries": len(blasted), "clause_bucket": int(plane.lits.shape[1]),
            "var_bucket": int(plane.n_vars),
            "statuses": sorted(int(s) for s in s_dev)}


def phase_serve(opts, tmp):
    from bench_contracts import etherstore_like, rubixi_like

    from mythril_tpu.service.client import ServiceClient
    from mythril_tpu.service.server import wait_for_server

    with open(os.path.join(REPO, "tests", "testdata", "inputs",
                           "kill_simple.bin-runtime")) as f:
        kill = f.read().strip()
    contracts = [("kill_simple", kill),
                 ("etherstore", contract_hex(etherstore_like())),
                 ("rubixi", contract_hex(rubixi_like()))]
    with Phase(5, "serve") as ph:
        solo = {}
        for name, code in contracts:
            report, _, _ = analyze(["-c", code, "--bin-runtime", "-t", "2",
                                    "--execution-timeout", "60", "--frontier"])
            solo[name] = issue_set(report)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        served, errors = {}, []

        def client():
            try:
                check(wait_for_server("127.0.0.1", port, timeout=900),
                      "service never came up")
                cl = ServiceClient("127.0.0.1", port, timeout=900)
                for name, code in contracts:
                    done = cl.submit(code, name=name)
                    served[name] = sorted(
                        (str(i["swc_id"]), int(i["address"]))
                        for i in done["issues"])
            except Exception as e:  # reported by the main thread
                errors.append(e)
            finally:
                os.kill(os.getpid(), signal.SIGTERM)  # the drain under test

        t = threading.Thread(target=client, name="smoke-client")
        t.start()
        rc, _out = run_cli(["serve", "--port", str(port), "--workers", "1",
                            "--cache-root", os.path.join(tmp, "svc")])
        t.join(timeout=60)
        ph.counters.update(solo=solo, served=served, drain_rc=rc)
        check(not t.is_alive(), "client thread did not finish")
        check(not errors, f"client failed: {errors[:1]}")
        check(rc == 0, f"SIGTERM drain returned {rc}")
        check(served == solo, "served issue sets differ from solo analyze")


def phase_mesh(opts):
    """--chips 4: the path-sharded wide analysis and the cooperative corpus
    against the same runs on one chip."""
    from bench_contracts import etherstore_like, rubixi_like

    from mythril_tpu.analysis.cooperative import analyze_cooperative
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.support.support_args import args

    with Phase(3, "wide-frontier-mesh") as ph:
        _, sharded, s_stats = wide_device_run(opts)
        _, single, o_stats = wide_device_run(opts, extra=["--no-mesh"])
        ph.counters.update(
            issues=issue_set(sharded), sharded=s_stats, one_chip=o_stats)
        check(s_stats["mesh_fallbacks"] == 0, "mesh fell back")
        check(s_stats["mesh_devices"] == opts.chips,
              f"state not path-sharded over {opts.chips} chips")
        check(o_stats["mesh_devices"] == 0, "--no-mesh run was sharded")
        check(issue_set(sharded) == issue_set(single),
              "sharded issue set differs from one chip")
        check(s_stats["device_instructions"] == o_stats["device_instructions"],
              "sharded device instruction count differs from one chip")

    with open(os.path.join(REPO, "tests", "testdata", "inputs",
                           "kill_simple.bin-runtime")) as f:
        kill = bytes.fromhex(f.read().strip().replace("0x", ""))
    jobs = [("kill_simple", kill), ("etherstore", etherstore_like()),
            ("rubixi", rubixi_like())]

    def run_coop(mesh_on):
        overrides = {"frontier": True, "frontier_force": True,
                     "frontier_mesh": mesh_on,
                     "frontier_width": opts.width // 4,
                     "probe_backend": "host"}
        saved = {k: getattr(args, k) for k in overrides}
        vars(args).update(overrides)
        reset_analysis_scope()
        try:
            by_name, _states = analyze_cooperative(
                jobs, transaction_count=2, execution_timeout=120)
        finally:
            vars(args).update(saved)
        found = {n: sorted((i.swc_id, i.address) for i in issues)
                 for n, issues in by_name.items()}
        return found, FrontierStatistics().as_dict()

    with Phase(4, "cooperative-corpus-mesh") as ph:
        sharded, s_stats = run_coop(True)
        single, o_stats = run_coop(False)
        ph.counters.update(issues=sharded, one_chip_issues=single,
                           sharded=s_stats, one_chip=o_stats)
        check(s_stats["segments"] > 0, "corpus never hit the device")
        check(s_stats["mesh_devices"] == opts.chips,
              f"corpus not path-sharded over {opts.chips} chips")
        check(sharded == single, "sharded corpus issue sets differ")
        check(s_stats["device_instructions"] == o_stats["device_instructions"],
              "sharded corpus device instruction count differs")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the path-sharded frontier comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: width 64, Pallas in interpret mode "
                    "(still ends non-zero off the TPU)")
    opts = ap.parse_args()
    opts.width = 64 if opts.rehearse else 1024
    opts.wide_branches = 6 if opts.rehearse else 10  # 2^n paths
    opts.batch = 512 if opts.rehearse else 4096
    if not os.path.isdir(os.path.join(REPO, "mythril_tpu")):
        print("chip_smoke: no mythril_tpu package next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    import mythril_tpu
    from mythril_tpu.observability import deviceplane

    mythril_tpu.arm_compile_cache()
    deviceplane.install()  # count every compile, not only the frontier's
    t0 = time.perf_counter()
    try:
        device = phase_devices(opts)
        on_tpu = device["platform"] == "tpu"
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            if opts.chips == 4:
                phase_mesh(opts)
            else:
                phase_bectoken(opts, tmp, on_tpu)
                phase_wide(opts)
                phase_kernels(opts)
                phase_serve(opts, tmp)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    comp, hits, misses = compile_totals()
    print(f"compile total: {comp:.3f} s over the run, persistent cache "
          f"hits={hits} misses={misses} (cold when hits=0); "
          f"wall {time.perf_counter() - t0:.3f} s", flush=True)
    if not on_tpu:
        print("chip_smoke: rehearsal complete; no TPU, so no ok line",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
