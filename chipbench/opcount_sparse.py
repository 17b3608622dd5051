"""Bytes the mechanisms of a sparse + linear-attention model have to
move in one decode step, whatever implements them: the K and V rows of
the blocks a sparse layer's SELECTION names, from the positions alone,
and the linear layers' per-slot state, read once and written once.
"""

from __future__ import annotations

from typing import Sequence


def selected_rows(p: int, block: int, topk: int, dense_len: int) -> int:
    """Rows a query at position p reads in one sparse layer and kv
    group: all p + 1 while p + 1 <= dense_len; else `topk` blocks (the
    forced ones among them), all whole but the last, which holds the
    rows <= p of p's own block."""
    if p + 1 <= dense_len:
        return p + 1
    chosen = min(topk, p // block + 1)
    return (chosen - 1) * block + p % block + 1


def selected_row_bytes(positions: Sequence[int], n_layers: int,
                       n_kv_heads: int, head_dim: int, itemsize: int,
                       block: int, topk: int, dense_len: int) -> int:
    """K and V bytes one decode step's sparse layers read: a slot at
    position p, `selected_rows` rows a layer and kv head."""
    return sum(2 * n_layers * n_kv_heads * head_dim * itemsize
               * selected_rows(p, block, topk, dense_len)
               for p in positions)


def lightning_state_bytes(live_slots: int, n_layers: int, heads: int,
                          head_dim: int) -> int:
    """Float32 state bytes one decode step's linear layers move: every
    live slot's [heads, d, d] state, read once and written once."""
    return 2 * live_slots * n_layers * heads * head_dim * head_dim * 4
