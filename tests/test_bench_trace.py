"""bench/tests/test_trace.py under tier-1: every test there is a case here."""
from bench.tests.test_trace import *  # noqa: F401,F403
