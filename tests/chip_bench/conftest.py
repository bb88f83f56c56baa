"""The chip benchmark's own tests.  They run on the CPU with no TPU
library loaded; the harness's device check is stubbed where a run is
driven."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
