"""The benchmark's own tests: run them by name on the CPU, with the
Pallas kernels interpreted,

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(they are not under the repository's tier-1 `testpaths`)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
