"""Work of one weighted label-propagation round over slot-major ELL
adjacency (``kernels/label_prop`` and its neighbour-label gather): N nodes,
K neighbour slots.

The algorithm reads each slot's neighbour id and weight (4 + 4 bytes),
the neighbour's current label (4 bytes), each node's own label, and writes
one label per node: N·K·12 + N·8 bytes.  Its arithmetic is one compare and
one add per slot, 2·N·K operations — an algorithm that groups equal labels
needs no more; the kernel's K² compare-and-add is the implementation's.
"""
from __future__ import annotations

from typing import Tuple

DTYPE = "f32"


def round_work(n: int, k: int) -> Tuple[float, float]:
    """(operations, bytes) of one round over ``n`` nodes, ``k`` slots."""
    return 2.0 * n * k, float(n * k * 12 + n * 8)
