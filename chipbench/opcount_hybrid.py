"""Bytes the algorithms of a model with recurrent (KDA) and latent
attention (MLA) layers need, from shapes and live slots alone: the
yardstick's side of `kda_step_roofline` and `mla_attn_roofline`. Count
what has to move and never more, so that no share can read over 100%.
The sparse FFN's bytes are chipbench/opcount_mixed.py's, over the
experts this chip holds.
"""

from __future__ import annotations

from typing import Iterable

from chipbench.opcount_mixed import routed_expert_bytes  # noqa: F401


def kda_state_bytes(live_slots: int, layers: int, heads: int,
                    head_dim: int) -> int:
    """State bytes one decode step has to move: every live slot's
    float32 state matrix of every KDA layer and head, read once and
    written once. The conv tail, q, k, v, the decay and dead slots are
    left out."""
    return live_slots * layers * heads * head_dim * head_dim * 4 * 2


def latent_row_bytes(positions: Iterable[int], layers: int, rank: int,
                     rope_dim: int, itemsize: int = 2) -> int:
    """Latent bytes one decode step has to read: for each live slot
    whose new token sits at position p, rows 0..p of rank + rope_dim
    values in every MLA layer, ONCE (keys and values are the same
    bytes). The pad columns of a pooled row, queries, tables and dead
    slots are left out."""
    rows = sum(int(p) + 1 for p in positions)
    return rows * layers * (rank + rope_dim) * itemsize
