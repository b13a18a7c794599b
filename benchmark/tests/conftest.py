"""Tests of the benchmark's own yardstick. Run by hand and in the CPU
rehearsal (``python -m pytest benchmark/tests -q``); tier-1 collects only
``tests/``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
