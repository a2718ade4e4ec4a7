"""bench/tests/test_routed.py under tier-1: every test there is a case here."""
from bench.tests.test_routed import *  # noqa: F401,F403
