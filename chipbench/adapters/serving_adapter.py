"""Every read of a PRIVATE field of `ContinuousServer` the harness
makes, in one file, so that the `tracing` PR (ROADMAP C12) can replace
each by a public one. The public surface gives `submit()`, `step()`,
`run()`, `ttft`, `failed` and `cache_stats()`: no per-token times, no
poll of finished requests short of `run()`, no positions.

  request_of   `_queue[-1]` right after submit(): the request object,
               whose `.tokens` grows as flushes land tokens on the host
  flush        `_flush()`: the host takes every buffered step's tokens
  done         `_done`: finished requests (run() alone hands them out,
               and only by driving the server empty)
  live         `_slot_req`, `_pos`: live slots and their positions
  drain        empties `_done` after the warm-up
  release      drops `_pools` / `params` so the reference fits
"""

from __future__ import annotations

from typing import Dict, List, Optional


def request_of(server, rid: int):
    req = server._queue[-1]
    if req.rid != rid:
        raise RuntimeError("submit() did not queue its request last")
    return req


def flush(server) -> None:
    server._flush()


def done(server, rid: int) -> Optional[List[int]]:
    return server._done.get(rid)


def live(server) -> Dict[int, int]:
    """slot -> next write position, for every live slot."""
    return {s: server._pos[s] for s in range(server.slots)
            if server._slot_req[s] is not None}


def drain(server) -> None:
    """Forget finished requests (the warm-up's)."""
    server._done.clear()


def release(server) -> None:
    server._pools = None
    server._scales = None
    server._pending.clear()
    server._buf.clear()
    server._cur_dev = server._temp_dev = server._keys_dev = None
    server._tables_arr = None
    server.params = None
