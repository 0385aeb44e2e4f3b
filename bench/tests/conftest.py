import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU; the harness's device guard is
# either exercised (and must refuse) or turned off by the test
os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
