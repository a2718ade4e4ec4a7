"""bench/tests/test_flops.py under tier-1: every test there is a case here."""
from bench.tests.test_flops import *  # noqa: F401,F403
