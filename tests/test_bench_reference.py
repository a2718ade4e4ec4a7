"""bench/tests/test_reference.py under tier-1: every test there is a case here."""
from bench.tests.test_reference import *  # noqa: F401,F403
