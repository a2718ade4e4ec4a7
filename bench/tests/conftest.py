"""Run by hand, outside tier-1:  python -m pytest bench/tests -q
(on the CPU; nothing here is a time or a rate)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
