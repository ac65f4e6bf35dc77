"""Bytes that the engine's merge-and-reduce program must move.

``_merge_reduce`` (``repro.core.incremental``) joins preserved and delta
edges, keeps the last version of each (key, map key) pair and reduces
the affected keys.  Whatever implements it (a sort and a segment
reduce, on pallas or on xla), it reads each edge once, writes each merged
edge once, reads the affected keys and writes one reduced value and one
count per key.  An edge is a key and a map key (int32 each), its value
columns (float32 each), a valid flag and a sign (one byte each).

The merged edges counted are those the refresh appended to the MRBG
store, which is no more than the edges the merge read: the count is a
floor on the work, never the padded bucket.
"""
from __future__ import annotations


def edge_bytes(value_width: int) -> int:
    return 4 + 4 + 4 * value_width + 1 + 1


def merge_reduce_bytes(edges: int, keys: int, value_width: int) -> int:
    return 2 * edges * edge_bytes(value_width) + keys * (4 + 4 * value_width
                                                         + 4)
