"""Self-checks of the benchmark's own arithmetic and control flow.  CPU,
seconds, run by hand: ``python -m pytest benchmarks/selfcheck -q``.  Not
part of the repo's tier-1 tests."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
