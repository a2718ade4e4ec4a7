"""bench/tests/test_granite.py under tier-1: every test there is a case here."""
from bench.tests.test_granite import *  # noqa: F401,F403
