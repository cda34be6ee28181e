"""Detection-module import must stay jax-free.

Detectors import frontier.taint (bit registry) at load time; the frontier
package's engine->step->jax chain must only load when a FrontierEngine is
actually constructed (svm.py's deliberately-lazy import depends on this).
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

PROBE = (
    "import sys; "
    "assert 'jax' not in sys.modules, 'jax preloaded at startup'; "
    "import mythril_tpu.analysis.module.loader as L; "
    "mods = L.ModuleLoader().get_detection_modules(); "
    "assert len(mods) == 14, len(mods); "
    "sys.exit(1 if 'jax' in sys.modules else 0)"
)


def test_detector_import_stays_jax_free():
    # a clean PYTHONPATH: a site directory that preloads jax at interpreter
    # startup would mask what the detector imports actually pull
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "PYTHONSTARTUP")
    }
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [sys.executable, "-c", PROBE],
        cwd=str(REPO),
        env=env,
        capture_output=True,
    )
    assert proc.returncode == 0, proc.stderr.decode()
