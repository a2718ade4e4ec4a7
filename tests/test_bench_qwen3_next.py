"""bench/tests/test_qwen3_next.py under tier-1: every test there is a case here."""
from bench.tests.test_qwen3_next import *  # noqa: F401,F403
