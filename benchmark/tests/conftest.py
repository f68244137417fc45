"""The benchmark's own tests: ``python3 -m pytest benchmark/tests``.

They run on the CPU and cost no chip time. Nothing here is a device number:
the end-to-end rehearsals run the harness at a tiny, test-only size through
``run_cell(..., require_tpu=False)``, each in a process of its own because
the device count is fixed once per process (one device for a one-chip cell,
four virtual devices for the ``data=4`` cell).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
