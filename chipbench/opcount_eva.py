"""Bytes the algorithm of a model with EVA attention layers needs, from
shapes and the traced steps' positions alone: the yardstick's side of
`eva_attn_roofline`. Count what has to move and never more, so that no
share can read over 100%.
"""

from __future__ import annotations

from typing import Iterable


def rows_attended(pos: int, chunk: int, window: int) -> int:
    """Rows the query at position `pos` attends: one summary for every
    `chunk` positions of every complete window behind its own, and its
    own aligned window's exact rows up to itself."""
    return pos // window * (window // chunk) + pos % window + 1


def walk_bytes(positions: Iterable[int], layers: int, heads: int,
               head_dim: int, itemsize: int, chunk: int,
               window: int) -> int:
    """K and V bytes one decode step's walks have to read: for every
    live slot the rows its query attends, a K and a V row of `heads` x
    `head_dim` values each, an EVA layer each. The query, the new row's
    write, the table and the rows of a block beyond the query's own are
    left out."""
    row = 2 * heads * head_dim * itemsize
    return layers * row * sum(rows_attended(int(p), chunk, window)
                              for p in positions)
