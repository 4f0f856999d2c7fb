"""Brings the benchmark's own tests (``benchmarks/tests``) within tier-1's
reach: puts their directory on ``sys.path`` and loads their ``conftest.py``
under another name (``conftest`` is already this directory's), so that a
``test_harness_*.py`` module here can import the fixtures and the tests.
No file under ``benchmarks/`` knows of it."""

import importlib.util
import os
import sys

HARNESS_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "tests")
if HARNESS_TESTS not in sys.path:
    sys.path.insert(0, HARNESS_TESTS)

_spec = importlib.util.spec_from_file_location(
    "harness_conftest", os.path.join(HARNESS_TESTS, "conftest.py"))
harness_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(harness_conftest)  # puts benchmarks/ on sys.path

bench = harness_conftest.bench
