"""The benchmark's own tests. They run on the CPU, at test sizes:

    python -m pytest bench/tests

They are not among the repository's tier-1 tests (``testpaths`` there is
``tests``)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
