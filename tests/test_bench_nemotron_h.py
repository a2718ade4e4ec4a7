"""bench/tests/test_nemotron_h.py under tier-1: every test there is a case here."""
from bench.tests.test_nemotron_h import *  # noqa: F401,F403
