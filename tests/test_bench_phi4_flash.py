"""bench/tests/test_phi4_flash.py under tier-1: every test there is a case here."""
from bench.tests.test_phi4_flash import *  # noqa: F401,F403
