"""Bytes the algorithms of a model with two kinds of attention layer and
sparse experts need, from shapes and the routing alone: the yardstick's
side of `paged_attn_roofline.mixed` and `moe_gmm_roofline`. Count what
has to move and never more, so that no share can read over 100%.
"""

from __future__ import annotations

from typing import Iterable, Sequence


def paged_decode_attention_bytes(positions: Iterable[int],
                                 layer_windows: Sequence[int],
                                 n_kv_heads: int, head_dim: int,
                                 itemsize: int = 2) -> int:
    """K and V bytes one decode step has to read: for each live slot
    whose new token sits at position p, rows 0..p of K and of V in
    every full layer (window 0) and the last min(p + 1, window) rows in
    every window layer. Queries, outputs, tables, dead slots and rows
    behind a window are left out."""
    rows = 0
    for p in positions:
        seen = int(p) + 1
        rows += sum(min(seen, w) if w else seen for w in layer_windows)
    return 2 * rows * n_kv_heads * head_dim * itemsize


def expert_bytes(experts_hit: float, steps: int, sparse_layers: int,
                 d_model: int, expert_width: int, n_experts: int,
                 shared_width: int = 0, itemsize: int = 2) -> int:
    """Weight bytes the sparse FFNs of `steps` decode steps have to
    read. `experts_hit`: summed over the steps, the distinct experts hit
    a step (mean over the sparse layers), as the program's statistics
    vector counts them. Each hit reads the expert's three matrices
    once; every step and layer also reads the router and, where the
    model has one, the shared expert. Activations are left out."""
    per_expert = 3 * d_model * expert_width * itemsize
    every_step = (d_model * n_experts
                  + 3 * d_model * shared_width) * itemsize
    return int(sparse_layers * (experts_hit * per_expert
                                + steps * every_step))


def routed_expert_bytes(experts_hit: float, sparse_layers: int,
                        d_model: int, expert_width: int,
                        itemsize: int = 2) -> int:
    """The part of `expert_bytes` the grouped matrix product itself
    reads: the routed experts' matrices, without router and shared
    expert (those are other ops of the step)."""
    return int(sparse_layers * experts_hit
               * 3 * d_model * expert_width * itemsize)
