"""Shared by the benchmark's tests: the repo root on the path, and the
tiny benchmark tree as a fixture."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench_tiny import tiny_tree  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return tiny_tree(str(tmp_path / "tree"))
