"""bench/tests/test_lfm2.py under tier-1: every test there is a case here."""
from bench.tests.test_lfm2 import *  # noqa: F401,F403
