"""The benchmark's own self-checks run on the CPU:

    python -m pytest bench/tests

They need no chip: the harness is driven with its look for a TPU
skipped, at sizes a CPU run holds."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
