"""These tests run beside the benchmark (``pytest benchmarks/tests``), on the
CPU unless a test says otherwise. They are not part of the repo's tier-1
suite, which collects ``tests/`` alone."""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
