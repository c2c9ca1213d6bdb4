"""Fixtures of the benchmark's own tests."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import harness  # noqa: E402


@pytest.fixture
def no_disk_cache(monkeypatch):
    """Keep the tests' compiles out of the checkout's compile cache."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)

