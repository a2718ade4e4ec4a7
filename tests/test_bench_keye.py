"""bench/tests/test_keye.py under tier-1: every test there is a case here."""
from bench.tests.test_keye import *  # noqa: F401,F403
