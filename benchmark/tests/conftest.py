"""The benchmark's own tests: CPU only, run by hand with
`python -m pytest benchmark/tests -q` (not part of tier-1)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("OPENSEARCH_TPU_MESH", "0")

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_BENCH, os.path.dirname(_BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
