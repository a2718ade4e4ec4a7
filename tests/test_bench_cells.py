"""bench/tests/test_cells.py under tier-1: every test there is a case here."""
from bench.tests.test_cells import *  # noqa: F401,F403
