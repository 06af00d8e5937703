"""Tests of the benchmark compile in memory only."""
import pytest

from bench.tests import tiny  # noqa: F401  (puts the program on sys.path)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Tests compile in memory only: the driver's cache would write into
    the checkout."""
    from bench import driver

    monkeypatch.setattr(driver, "enable_compile_cache", lambda: "(off)")
