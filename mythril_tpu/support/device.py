"""What the device is, and the compile-warmup contract of the device tiers.

Every device decision asks the initialised JAX backend (``on_tpu``), never
an environment variable.  ``local_tpu_chips`` counts chips WITHOUT
initialising a backend, for a parent process (the service daemon) whose
workers own the chips.

``DeviceWarmup`` is the one warm-up contract the tape VM, the absdomain
known-bits interpreter and the devsolver kernel share: the first compile
is claimed under a lock (by a background thread, or synchronously), the
host twin answers until it lands, and a failed compile is KEPT — the next
call that would have used the device raises it, instead of the tier
silently staying on the host.
"""

from __future__ import annotations

import glob
import logging
import os
import threading
from typing import Callable

log = logging.getLogger(__name__)


class DeviceFailure(RuntimeError):
    """A device tier failed to compile or run.  Never degraded to a host
    answer: the analysis that needed the device fails with it."""


def on_tpu() -> bool:
    """Whether the initialised default backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"


def local_tpu_chips() -> int:
    """TPU chips this process may use, counted without initialising a JAX
    backend: the chips ``TPU_VISIBLE_CHIPS`` names, else the chip device
    nodes this machine exposes (``/dev/vfio/<n>`` on v5e, ``/dev/accel<n>``
    on older TPUs; the PCI bus lists every chip of the host, also those a
    one-chip machine cannot open); 0 when ``JAX_PLATFORMS`` keeps JAX off
    the TPU (the test suite, CPU rehearsals)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len(visible.split(","))
    vfio = [p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()]
    return len(vfio or glob.glob("/dev/accel[0-9]*"))


def note_host_route(policy: str) -> None:
    """Count one firing of a deliberate policy that keeps work on the host
    (``narrow_gate``, ``slow-bail``, ``narrow-bail``, ``probe_breakeven``,
    ``probe_warming``, ``prefilter_warming``, ``devsolver_warming``) under
    ``device.host_routes``; ``chip_smoke.py`` prints them per phase."""
    from mythril_tpu.observability.metrics import get_registry

    get_registry().labeled_counter(
        "device.host_routes", persistent=True
    ).inc(policy)


class DeviceWarmup:
    """cold -> warming -> ready | failed, for one device program family."""

    def __init__(self, name: str, compile_fn: Callable[[], None]):
        self.name = name
        self._compile = compile_fn
        self._lock = threading.Lock()
        self._done = threading.Event()
        self.state = "cold"
        self.error: BaseException | None = None

    def _claim(self) -> bool:
        with self._lock:
            if self.state != "cold":
                return False
            self.state = "warming"
            self._done.clear()
            return True

    def _run(self) -> None:
        try:
            self._compile()
        except BaseException as e:
            with self._lock:
                self.state, self.error = "failed", e
            raise
        else:
            with self._lock:
                self.state = "ready"
        finally:
            self._done.set()

    def _run_in_background(self) -> None:
        try:
            self._run()
        except Exception:
            log.warning("%s warmup failed; raised at the next device call",
                        self.name, exc_info=True)

    def raise_if_failed(self) -> None:
        if self.state == "failed":
            raise DeviceFailure(
                f"{self.name} device compile failed: {self.error!r}"
            ) from self.error

    def warmup(self) -> None:
        """Compile synchronously, or wait for the in-flight compile."""
        if self._claim():
            self._run()
            return
        self._done.wait()
        self.raise_if_failed()

    def ensure_warming(self) -> None:
        """Kick the compile on a background thread (idempotent).

        Not a daemon thread: interpreter shutdown while an XLA compile is
        in flight aborts the process, so exit waits for the compile."""
        if not self._claim():
            self.raise_if_failed()
            return
        threading.Thread(target=self._run_in_background, daemon=False,
                         name=f"{self.name}-warmup").start()

    def ready(self) -> bool:
        """True once compiled; raises the kept failure of a failed compile."""
        self.raise_if_failed()
        return self.state == "ready"
