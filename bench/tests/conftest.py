"""The benchmark's CPU tests run inside the repository's suite: keep JAX's
process-wide settings as the other tests expect them."""

import pytest


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """The harness turns the persistent compilation cache on for its
    process; a test process keeps it off."""
    from repro.core import backend
    monkeypatch.setattr(backend, "enable_compile_cache", lambda: None)
