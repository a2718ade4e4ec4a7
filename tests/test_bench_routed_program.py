"""bench/tests/test_routed_program.py under tier-1: every test there is a case here."""
from bench.tests.test_routed_program import *  # noqa: F401,F403
