"""The benchmark's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They sit outside the repository's tier-1 ``testpaths``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
