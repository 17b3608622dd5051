"""Bytes and operations that absorbed latent decode attention NEEDS
under many query heads, from shapes and live positions alone: the
yardstick's side of `mla_attn_roofline.docqa`. What the ALGORITHM
needs, whatever implements it: no pad columns of a pooled row, no row
past a slot's live length, no dead slot. Under 128 heads a cached row
of 576 values costs 128 x 2 x (576 + 512) operations against 1,152
bytes: 242 operations a byte, the v5e's ridge, so the least time is
the larger of the two.
"""

from __future__ import annotations

from typing import Iterable


def _rows(positions: Iterable[int]) -> int:
    """Rows 0..p of every live slot whose new token sits at p."""
    return sum(int(p) + 1 for p in positions)


def latent_walk_bytes(positions: Iterable[int], layers: int, rank: int,
                      rope_dim: int, itemsize: int = 2) -> int:
    """Latent bytes one decode step has to read: each live row's rank +
    rope_dim values in every layer, ONCE (keys and values are the same
    bytes)."""
    return _rows(positions) * layers * (rank + rope_dim) * itemsize


def latent_walk_flops(positions: Iterable[int], layers: int, heads: int,
                      rank: int, rope_dim: int) -> int:
    """Operations one decode step has to spend on its latent rows: each
    head's score against a row's rank + rope_dim values and its weight
    on the row's rank values, a multiply and an add each."""
    return _rows(positions) * layers * heads * 2 * (rank + rope_dim + rank)
