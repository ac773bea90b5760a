import os
import sys
from pathlib import Path

# the benchmark's own tests run on the CPU; the package ``bench`` and the
# program under ``src`` are imported from the checkout
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
