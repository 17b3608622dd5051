"""Paged KV-cache subsystem: block pools, page tables, radix prefix
reuse, and the performance counters that observe them.

Host-side bookkeeping lives here (`BlockAllocator`, `PageTable`,
`RadixCache`); the jit-side gather/scatter numerics live in
`hpx_tpu/ops/paged_attention.py`; `models/serving.ContinuousServer`
wires both together: they are its one cache. Knobs come from
the `hpx.cache.*` config keys (`core/config.py`).
"""

from .block_allocator import BlockAllocator, CacheOOM
from .counters import register_fleet, register_server
from .page_table import PageTable, materialize
from .radix import RadixCache, prefix_hashes

__all__ = [
    "BlockAllocator",
    "CacheOOM",
    "PageTable",
    "RadixCache",
    "materialize",
    "prefix_hashes",
    "register_fleet",
    "register_server",
]
