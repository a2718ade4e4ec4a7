"""bench/tests/test_sdar.py under tier-1: every test there is a case here."""
from bench.tests.test_sdar import *  # noqa: F401,F403
