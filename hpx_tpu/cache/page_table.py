"""Per-request logical→physical block maps, materializable for jit.

A `PageTable` is the request-side view of the paged KV cache: an
ordered list of physical block ids covering the request's logical token
positions `[0, tokens)`. Logical block ``i`` holds token rows
``[i*block_size, (i+1)*block_size)``; position ``p`` lives at physical
row ``(table[p // block_size], p % block_size)``.

`as_row` / `materialize` turn host tables into padded int32 arrays the
jitted step/prefill programs index with — the analog of
partitioned_vector's segment map, materialized per step instead of per
container. Padding uses a caller-supplied block id (the server's
reserved trash block) so dead slots and unmapped tail positions always
resolve to a writable-but-never-read physical block: masked lanes can
scatter harmlessly instead of corrupting live data.
"""

from __future__ import annotations

import itertools
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["PageTable", "TwoGrainTable", "WindowTable", "device_table",
           "materialize", "occupancy"]

_UIDS = itertools.count()


class PageTable:
    """Block map for one request: `blocks[i]` backs logical block i.

    `version` counts mutations through the mutator methods
    (`append_block` / `replace_block` / `extend_blocks`); the serving
    step loop keys its materialized-table device cache on it, so a
    steady-state decode step re-uploads nothing. Callers that poke
    `blocks` directly must bump `version` themselves.
    """

    def __init__(self, block_size: int) -> None:
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = block_size
        self.blocks: List[int] = []
        self.tokens = 0            # logical length in token rows
        self.version = 0           # bumped by every mutator
        self.uid = next(_UIDS)     # process-unique (id() can recycle)

    def append_block(self, bid: int) -> None:
        self.blocks.append(bid)
        self.version += 1

    def extend_blocks(self, bids: Sequence[int]) -> None:
        self.blocks.extend(bids)
        self.version += 1

    def replace_block(self, idx: int, bid: int) -> None:
        """Swap the physical block backing logical block `idx`
        (copy-on-write fork installs the private copy here)."""
        self.blocks[idx] = bid
        self.version += 1

    def rollback(self, tokens: int) -> List[int]:
        """Rewind the logical frontier to `tokens` rows and return the
        block ids no longer needed to cover it (caller owns the
        decrefs). This is how speculative rejection stays cheap: draft
        rows past the accepted frontier are simply abandoned — the
        physical rows still hold stale K/V, but the decode mask only
        exposes positions < `tokens`, and any block kept here has its
        stale tail rewritten by the next write at that position before
        it can ever be attended."""
        if tokens < 0:
            raise ValueError(f"cannot rollback to {tokens} tokens")
        keep = self.blocks_for(tokens)
        dropped = self.blocks[keep:]
        if dropped:
            del self.blocks[keep:]
            self.version += 1
        self.tokens = tokens
        return dropped

    @property
    def capacity(self) -> int:
        return len(self.blocks) * self.block_size

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to cover `tokens` rows."""
        return -(-tokens // self.block_size)

    def held(self, rows: int) -> int:
        """Blocks of a request that has consumed `rows` positions."""
        return self.blocks_for(rows)

    def block_of(self, pos: int) -> int:
        """Physical block id backing logical position `pos`."""
        return self.blocks[pos // self.block_size]

    def as_row(self, max_blocks: int, pad: int) -> np.ndarray:
        """Padded int32 row `[max_blocks]` for the jitted programs."""
        if len(self.blocks) > max_blocks:
            raise ValueError(
                f"page table has {len(self.blocks)} blocks, row width "
                f"is {max_blocks}")
        row = np.full((max_blocks,), pad, np.int32)
        row[:len(self.blocks)] = self.blocks
        return row


class WindowTable(PageTable):
    """Block map of one request in a WINDOW group of layers: only the
    blocks that hold a row some later query can still see stay mapped.
    `blocks[i]` backs logical block `base + i`; `free_behind(pos)`
    drops the blocks wholly behind `pos`'s window (a query at `pos`
    sees rows j with pos - window < j) and the caller returns them to
    the group's allocator, where another request takes them.

    The jitted programs read the map as a RING of `ring` columns,
    logical block b in column b % ring (`as_row`): with ring >=
    ceil(window / block_size) + 1 no two live blocks share a column,
    so the fused kernel walks `ring` entries whatever the context's
    length."""

    def __init__(self, block_size: int, window: int, base: int = 0,
                 blocks: Sequence[int] = ()) -> None:
        super().__init__(block_size)
        self.window, self.base = int(window), int(base)
        self.blocks = list(blocks)

    @property
    def capacity(self) -> int:
        return (self.base + len(self.blocks)) * self.block_size

    def first_needed(self, pos: int) -> int:
        """The first logical block a query at `pos` still sees."""
        return max(0, pos - self.window + 1) // self.block_size

    def free_behind(self, pos: int) -> List[int]:
        n = min(len(self.blocks), self.first_needed(pos) - self.base)
        if n <= 0:
            return []
        freed, self.blocks = self.blocks[:n], self.blocks[n:]
        self.base += n
        self.version += 1
        return freed

    def as_row(self, ring: int, pad: int) -> np.ndarray:
        if len(self.blocks) > ring:
            raise ValueError(f"window table holds {len(self.blocks)} "
                             f"live blocks, its ring has {ring} columns")
        row = np.full((ring,), pad, np.int32)
        for i, bid in enumerate(self.blocks):
            row[(self.base + i) % ring] = bid
        return row

    def as_linear_row(self, max_blocks: int, pad: int) -> np.ndarray:
        """Column b = logical block b (pad where it is not mapped): the
        write row of the prefill splice, whose scratch holds every row
        of the sequence."""
        row = np.full((max_blocks,), pad, np.int32)
        row[self.base:self.base + len(self.blocks)] = self.blocks
        return row


class TwoGrainTable(PageTable):
    """Block map of one request on layers that keep rows at TWO grains
    (ops/eva.py): the exact rows of the aligned window of `window`
    positions the request is in, and one SUMMARY row every `chunk`
    positions of every window behind it. `blocks` is ONE run: the
    first `summary` blocks hold the summaries of the complete windows,
    `per` blocks a window, the rest the window's exact rows in order.
    A window's window / chunk summaries fill whole blocks (`per` is a
    whole number), so logical row `row_of(pos)` of the run is gap-free
    and the programs read this table like any other (`as_row`).

    Two clocks: the exact run grows a block every `block_size`
    positions and goes back ALL AT ONCE when the window completes
    (`roll`); the summary run grows `per` blocks a window and is freed
    at retirement only. The step that writes a window's last row
    holds the window's `per` summary blocks behind the exact run
    (`blocks_at`), where no live row lies yet; `roll` moves them up."""

    def __init__(self, block_size: int, window: int, chunk: int) -> None:
        super().__init__(block_size)
        if window % chunk or (window // chunk) % block_size:
            raise ValueError(
                f"a window of {window} rows pooled every {chunk} gives "
                f"{window / chunk:g} summaries, no whole number of "
                f"blocks of {block_size}")
        self.window, self.chunk = int(window), int(chunk)
        self.per = window // chunk // block_size
        self.summary = 0           # leading blocks that hold summaries

    def row_of(self, pos: int) -> int:
        """The logical row of position `pos` in the run."""
        return (pos // self.window) * self.per * self.block_size \
            + pos % self.window

    def blocks_at(self, pos: int) -> int:
        """Blocks the run needs for a WRITE at `pos`: the summaries
        behind pos's window, the window's blocks up to pos's and, where
        pos is the window's last row, the blocks its summaries take."""
        n = self.row_of(pos) // self.block_size + 1
        return n + (self.per if (pos + 1) % self.window == 0 else 0)

    def roll(self) -> List[int]:
        """The window is complete and its summaries lie in the last
        `per` blocks: they join the summary run, and the exact run's
        blocks come back for the caller to free."""
        freed = self.blocks[self.summary:-self.per]
        self.blocks = self.blocks[:self.summary] + self.blocks[-self.per:]
        self.summary += self.per
        self.version += 1
        return freed

    def held(self, rows: int) -> int:
        """Blocks of a request that has consumed `rows` positions and
        rolled every window it completed: `per` a complete window and
        the exact rows of the window under way."""
        return rows // self.window * self.per \
            + self.blocks_for(rows % self.window)

    def adopt(self, rows: int) -> None:
        """Lay the run out for a request that has consumed `rows`
        positions, over the `held(rows)` blocks the table lists."""
        self.summary = rows // self.window * self.per
        self.tokens = rows
        self.version += 1

    def write_rows(self, summary_width: int, pad: int):
        """(summary blocks padded to `summary_width`, the window's
        exact blocks padded to window / block_size): the write rows of
        the prefill splice, whose scratch keeps the two grains apart."""
        srow = np.full((summary_width,), pad, np.int32)
        srow[:self.summary] = self.blocks[:self.summary]
        erow = np.full((self.window // self.block_size,), pad, np.int32)
        exact = self.blocks[self.summary:]
        erow[:len(exact)] = exact
        return srow, erow

    @staticmethod
    def max_blocks(block_size: int, window: int, chunk: int,
                   smax: int) -> int:
        """The most blocks a request of up to `smax` positions holds at
        once: at its last position, or on the step that completes its
        last whole window."""
        t = TwoGrainTable(block_size, window, chunk)
        last_whole = smax // window * window - 1
        return max(t.blocks_at(smax - 1),
                   t.blocks_at(last_whole) if last_whole > 0 else 0)


def occupancy(tables: Sequence[Optional[PageTable]]) -> int:
    """Total MAPPED blocks across live slots (dead/None slots count 0)
    — the table-occupancy input to the decode-attention
    hbm-read-per-token counters: blocks a decode step actually streams
    per slot, as opposed to the padded `max_blocks` row width."""
    return sum(len(pt.blocks) for pt in tables if pt is not None)


def materialize(tables: Sequence[Optional[PageTable]], max_blocks: int,
                pad: int) -> np.ndarray:
    """Stack per-slot tables into the `[slots, max_blocks]` int32 array
    one decode step consumes; None slots (dead) pad entirely."""
    out = np.full((len(tables), max_blocks), pad, np.int32)
    for i, pt in enumerate(tables):
        if pt is not None:
            out[i] = pt.as_row(max_blocks, pad)
    return out


def device_table(tables: Sequence[Optional[PageTable]],
                 max_blocks: int, pad: int, mesh=None,
                 dp_axis: str = "dp", residency: str = "sharded"):
    """Materialize and PLACE the `[slots, max_blocks]` table for the
    jitted programs. Single-device (``mesh=None``): a plain device
    array. On a mesh the block ids stay GLOBAL (pools replicate their
    block axis over dp, so any id resolves on any shard) and only the
    slot axis placement is a choice, `hpx.serving.mesh.
    table_residency`:

    * ``"sharded"`` — rows shard over `dp_axis`: each dp shard holds
      exactly its slots' rows, matching the shard_map block spec with
      zero resharding on entry (the default).
    * ``"replicated"`` — every device holds the full table; shard_map
      entry slices it. Costs slots/dp × more table bytes per device
      (noise at real sizes) but makes the host upload a single
      broadcast — an escape hatch for debugging placement issues.

    jax is imported lazily: this module stays importable (and its host
    bookkeeping testable) without jax installed."""
    arr = materialize(tables, max_blocks, pad)
    import jax
    import jax.numpy as jnp
    if mesh is None:
        return jnp.asarray(arr)
    from jax.sharding import NamedSharding, PartitionSpec
    if residency not in ("sharded", "replicated"):
        raise ValueError(
            "hpx.serving.mesh.table_residency must be 'sharded' or "
            f"'replicated', got {residency!r}")
    spec = (PartitionSpec(dp_axis, None) if residency == "sharded"
            else PartitionSpec())
    return jax.device_put(arr, NamedSharding(mesh, spec))
