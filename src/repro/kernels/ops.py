"""Public jit'd entry points for every Pallas kernel.

``interpret`` defaults to None, resolved by :func:`use_interpret`: the
compiled kernel on a TPU backend, the Pallas interpreter elsewhere.
Oracles live in kernels/ref.py with identical signatures.
"""

from __future__ import annotations

from repro.kernels import use_interpret
from repro.kernels.flash_attention import flash_attention
from repro.kernels.leaf_scan import leaf_scan
from repro.kernels.leaf_split import leaf_split
from repro.kernels.leaf_write import leaf_write
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.node_search import node_search
from repro.kernels.paged_attention import paged_attention
from repro.kernels.subtree_walk import subtree_walk

__all__ = [
    "flash_attention",
    "leaf_scan",
    "leaf_split",
    "leaf_write",
    "mamba_scan",
    "node_search",
    "paged_attention",
    "subtree_walk",
    "use_interpret",
]
