"""The benchmark's own tests: CPU rehearsals, run by hand
(``python -m pytest benchmark/tests -q``), not collected by tier-1."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture
def on_cpu(tmp_path, monkeypatch):
    """Gateway children on the CPU, their compile cache out of the tree."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path
