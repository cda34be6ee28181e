"""``myth serve --workers 1`` in this process, driven by ``service/client.py``
clients on threads (``chip_smoke.py`` phase 5).

The daemon runs on the main thread, as ``myth serve`` does, so its
SIGTERM drain is the real one.  A driver thread warms the daemon, runs
the window's load and then sends the SIGTERM.  The daemon's caches live
in a directory of this run, so every run starts with an empty result
store and query cache.
"""

from __future__ import annotations

import os
import shutil
import signal
import socket
import tempfile
import threading
import time
from typing import List, Optional

from benchmark.loadgen import Request

CLIENT_TIMEOUT_S = 600.0


class ServeEntry:
    def __init__(self, config: dict, traffic: dict, modules: Optional[List[str]] = None):
        self.options = list(config["options"])
        self.traffic = traffic
        self.tier = traffic.get("tier", "batch")
        self.modules = modules
        self.lateness: List[float] = []  # open loop: send time minus due time
        self.error: Optional[BaseException] = None

    # -- one request ----------------------------------------------------

    def submit(self, req: Request) -> None:
        from mythril_tpu.service.client import ServiceClient

        client = ServiceClient("127.0.0.1", self.port, timeout=CLIENT_TIMEOUT_S)
        expected = set(req.variant.expected)
        planted = set(req.variant.planted)
        req.start = time.time()
        try:
            for ev in client.submit_stream(req.variant.hex, name=f"r{req.rid}",
                                           tier=self.tier, modules=self.modules):
                kind = ev.get("event")
                now = time.time()
                if kind == "accepted":
                    req.deduped = bool(ev.get("deduped"))
                elif kind == "issue":
                    hit = (str(ev.get("swc_id")), int(ev.get("address", -1)))
                    if (req.first_planted is None and hit[0] in planted
                            and hit in expected):
                        req.first_planted = now
                elif kind == "done":
                    req.done = now
                    req.issues = [(str(i["swc_id"]), int(i["address"]))
                                  for i in ev.get("issues", [])]
                    # a deduplicated answer streams no issue events
                    if req.first_planted is None and expected & set(req.issues) \
                            and planted & {s for s, _ in req.issues}:
                        req.first_planted = now
                elif kind == "error":
                    req.error = str(ev.get("error"))
        except Exception as e:  # a request that never comes back
            req.error = f"{type(e).__name__}: {e}"

    # -- load -----------------------------------------------------------

    def _open_loop(self, gen, seconds: float, window) -> List[Request]:
        offsets = gen.arrivals(seconds)
        stream = gen.stream(len(offsets))
        reqs, threads = [], []
        window.open()
        for rid, off in enumerate(offsets):
            req = Request(rid, next(stream), due=window.t0 + off)
            delay = req.due - time.time()
            if delay > 0:
                time.sleep(delay)
            self.lateness.append(time.time() - req.due)
            t = threading.Thread(target=self.submit, args=(req,), daemon=True)
            t.start()
            reqs.append(req)
            threads.append(t)
        for t in threads:
            t.join()
        window.close()
        return reqs

    def _closed_loop(self, gen, seconds: float, window) -> List[Request]:
        stream = gen.stream()
        lock = threading.Lock()
        reqs: List[Request] = []
        window.open()
        end = window.t0 + seconds

        def client():
            while time.time() < end:
                with lock:
                    req = Request(len(reqs), next(stream))
                    reqs.append(req)
                self.submit(req)

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(int(self.traffic.get("clients", 1)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        window.close()
        return reqs

    # -- the run --------------------------------------------------------

    def _drive(self, warm, gen, seconds, window, out) -> None:
        from mythril_tpu.service.server import wait_for_server

        try:
            if not wait_for_server("127.0.0.1", self.port, timeout=900):
                raise RuntimeError("the service never came up")
            # warm-up: the mix's families and sizes side by side, as the
            # window batches them
            reqs = [Request(-1 - i, v) for i, v in enumerate(warm)]
            threads = [threading.Thread(target=self.submit, args=(r,)) for r in reqs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            failed = [r for r in reqs if r.error or r.done is None]
            if failed:
                raise RuntimeError(f"warm-up request failed: {failed[0].error}")
            if self.traffic["loop"] == "open":
                out.extend(self._open_loop(gen, seconds, window))
            else:
                out.extend(self._closed_loop(gen, seconds, window))
        except BaseException as e:
            self.error = e
        finally:
            os.kill(os.getpid(), signal.SIGTERM)  # the daemon's drain

    def execute(self, warm, gen, seconds: float, window) -> List[Request]:
        from benchmark.entries.analyze import run_cli

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        root = tempfile.mkdtemp(prefix="bench-serve-")
        out: List[Request] = []
        driver = threading.Thread(target=self._drive,
                                  args=(warm, gen, seconds, window, out),
                                  name="bench-driver")
        driver.start()
        try:
            rc, _ = run_cli(["serve", "--port", str(self.port),
                             "--cache-root", root, *self.options])
        finally:
            driver.join()
            shutil.rmtree(root, ignore_errors=True)
        if self.error is not None:
            raise RuntimeError(f"serve run failed: {self.error!r}") from self.error
        if rc != 0:
            raise RuntimeError(f"myth serve drained with exit code {rc}")
        return out
