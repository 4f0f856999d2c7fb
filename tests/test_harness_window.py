"""``benchmarks/tests/test_window.py``, collected by tier-1 (a change that
breaks the harness is found here on the CPU, not on the chip)."""

import harness_path
from test_window import *  # noqa: F401,F403

bench = harness_path.bench  # the harness's fixture
