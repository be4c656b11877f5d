"""The benchmark's own tests: CPU only, no TPU library, outside ``tests/``
(tier-1 neither gains nor loses a test). Run them with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
