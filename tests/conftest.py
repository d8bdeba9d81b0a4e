"""Shared fixtures.

`retrace_sanitizer`: a `repro.analysis.retrace.RetraceSanitizer` that
asserts every declared compile budget at teardown — a test that watches
a jitted entry point fails if the entry point retraced beyond budget,
even if all its own assertions passed.

The session also pins $REPRO_TUNING_CACHE to a nonexistent temp path:
kernel dispatch consults the autotune cache, and a TUNING_gemm.json left
in the repo root by a local bench run must not leak measured winners
into tests (tests that WANT a cache point the env var somewhere real).

JAX's persistent compilation cache stays off in tests and in the CLI
subprocesses they start (the entry points turn it on for real runs).
"""

import os
import tempfile

import pytest

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault(
    "REPRO_TUNING_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="repro-test-tuning-"),
                 "absent.json"))


@pytest.fixture
def retrace_sanitizer():
    from repro.analysis.retrace import RetraceSanitizer
    s = RetraceSanitizer()
    yield s
    s.assert_ok()
