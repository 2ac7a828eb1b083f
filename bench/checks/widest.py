"""Widest path: the maximin bottleneck capacity from the source by
Bellman-Ford iteration over the float32 edge weights (source = 1e9,
unreached = 0).  It takes only mins and maxes of the weights, so the
read-back answer has to equal it: the number compared is the count of
vertices that differ."""
from __future__ import annotations

import numpy as np

NAME = "wrong_vertices"


def reference(n: int, edges: np.ndarray, source: int,
              dtype=np.float64) -> np.ndarray:
    cap = np.zeros(n, np.float64)
    cap[source] = 1e9
    w = (edges[:, 2].astype(np.int32).view(np.float32).astype(dtype)
         .astype(np.float64))
    s, d = edges[:, 0], edges[:, 1]
    while True:
        new = cap.copy()
        np.maximum.at(new, d, np.minimum(cap[s], w))
        if np.array_equal(new, cap):
            return cap.astype(np.float32)
        cap = new


def reference_bfloat16(n: int, edges: np.ndarray, source: int) -> np.ndarray:
    """The same capacities over the weights rounded to bfloat16 (the
    source keeps its 1e9 mark)."""
    import ml_dtypes
    return reference(n, edges, source, dtype=ml_dtypes.bfloat16)


def compare(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.count_nonzero(np.asarray(got) != want))
