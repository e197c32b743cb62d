"""Benchmark tests: CPU only, small sizes.  Run from the checkout root:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")  # quiet the CPU cache loader
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
