"""Bytes the algorithms of a model with selective-scan (Mamba-1) layers
need, from shapes, the traced steps' live slots and the traced chunks'
real rows alone: the yardstick's side of `mamba_step_roofline` and
`mamba_scan_roofline`. Count what has to move and never more, so that
no share can read over 100%.
"""

from __future__ import annotations


def mamba_state_bytes(live_slots: int, layers: int, d_inner: int,
                      d_state: int) -> int:
    """State bytes one decode step has to move: every live slot's
    float32 state of every Mamba layer, read once and written once. u,
    dt, B, C, y, A, the conv tail and dead slots are left out."""
    return live_slots * layers * d_inner * d_state * 4 * 2


def mamba_scan_bytes(real_rows: int, chunks: int, layers: int,
                     d_inner: int, d_state: int) -> int:
    """Bytes the scans of `chunks` prefill chunks holding `real_rows`
    real rows in all have to move, a Mamba layer each: u, dt and y of
    the real rows (float32, d_inner wide), their B and C (d_state
    each), and a chunk's state once in and once out. A, the padding
    rows and the lane-spread copies of B and C are left out."""
    rows = real_rows * (3 * d_inner + 2 * d_state) * 4
    state = chunks * d_inner * d_state * 4 * 2
    return layers * (rows + state)
