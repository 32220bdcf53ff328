"""Self-checks of the benchmark: ``pytest benchmarks/tests -q``. They run
on the CPU (correctness, counts, arithmetic — never a speed) and are not
part of the repository's tier-1 run."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
