"""The two cases of ``benchmarks/tests/test_rehearsal.py`` that start no
sidecar, collected by tier-1: no TPU, no number."""

import harness_path  # noqa: F401 — puts benchmarks/tests on sys.path
from test_rehearsal import (  # noqa: F401
    test_the_command_refuses_a_cpu,
    test_the_command_refuses_a_directory_without_the_program,
)
