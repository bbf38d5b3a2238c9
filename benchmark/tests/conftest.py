"""`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q` from the root of
the checkout. Not part of `tests/`: these hold the benchmark's own yardstick
(trace reduction, FLOPs, reference, manifest) and its control flow."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
