"""bench/tests/test_host_spans.py under tier-1: every test there is a case here."""
from bench.tests.test_host_spans import *  # noqa: F401,F403
