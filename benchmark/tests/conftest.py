"""The benchmark's own tests run on the CPU by hand
(``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``); they are not
part of tier 1."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
