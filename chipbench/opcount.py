"""Bytes and operations the ALGORITHM needs, from shapes alone.

The yardstick's side of every roofline share: least time = these bytes
over the table's peak (chipbench/peaks.json). Count what the algorithm
has to move and never more, so that no share can read over 100%.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str = None) -> dict:
    """The peaks of one device kind. A device that is not in the table
    is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}): add it with its source")
    return table[device_kind]


def stencil_node_bytes(nx: int, itemsize: int = 4) -> int:
    """One heat_part node over nx points: every point read once and
    written once. The two halo points are left out (the count may not
    be too high)."""
    return 2 * itemsize * nx


def stencil_dag_bytes(nx: int, np_: int, nt: int, itemsize: int = 4) -> int:
    return stencil_node_bytes(nx, itemsize) * np_ * nt


def stencil_dag_cells(nx: int, np_: int, nt: int) -> int:
    return nx * np_ * nt


def paged_decode_attention_bytes(positions: Iterable[int], n_layers: int,
                                 n_kv_heads: int, head_dim: int,
                                 itemsize: int = 2) -> int:
    """K and V bytes one decode step has to read: for each live slot
    whose new token sits at position p, rows 0..p of K and of V, in
    every layer. Queries, outputs, tables and dead slots are left out."""
    rows = sum(int(p) + 1 for p in positions)
    return 2 * rows * n_kv_heads * head_dim * itemsize * n_layers
