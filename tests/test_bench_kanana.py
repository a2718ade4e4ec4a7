"""bench/tests/test_kanana.py under tier-1: every test there is a case here."""
from bench.tests.test_kanana import *  # noqa: F401,F403
