"""karpenter_tpu — a TPU-native node-provisioning framework.

Re-implements the capabilities of Karpenter (reference at /root/reference,
see SURVEY.md) with the scheduling core — first-fit-decreasing bin-packing and
the consolidation repack search — expressed as vectorized constraint
satisfaction over a (pod-groups x node-candidates x topology-domains) tensor,
compiled by JAX/XLA for TPU.
"""

__version__ = "0.1.0"

import os as _os

# Lock-discipline sanitizer (docs/ANALYSIS.md): KT_SANITIZE=1 wraps the
# thread-sensitive solver-path classes in lock-assertion proxies that raise
# on cross-thread re-entrancy.  `make battletest` exports it; production
# leaves it off.
if _os.environ.get("KT_SANITIZE") == "1":
    from .analysis import sanitize as _sanitize

    _sanitize.install()
