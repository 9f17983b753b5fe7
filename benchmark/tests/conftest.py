"""The benchmark's own tests: CPU, four virtual devices, tiny sizes.

Run them with ``python -m pytest benchmark/tests -q`` (they are not part of
the repo's tier-1 run, which collects ``tests/`` only)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
