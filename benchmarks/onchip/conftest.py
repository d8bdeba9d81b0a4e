"""Fixtures for the benchmark's CPU tests."""

from __future__ import annotations

import os
import pathlib
import sys
import tempfile

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault(
    "REPRO_TUNING_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="onchip-test-tuning-"),
                 "absent.json"))


@pytest.fixture
def tiny_root(tmp_path):
    from tinykit import make_tiny_root
    here = make_tiny_root(tmp_path)
    return tmp_path, here
