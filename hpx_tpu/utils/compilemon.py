"""Count XLA backend compiles via jax.monitoring.

The serving program-cache work (bucketed prefill) is ultimately about
COMPILES, not dict hits — so tests and benchmarks measure the real
thing: jax emits a ``/jax/core/compile/backend_compile_duration``
event for every compile request that reaches the backend layer, and
`count_compiles` tallies them over a region. Where the persistent
compilation cache is on, a request it answers fires that event too
(seen on the chip, PR 22: a warm second run "compiled" as much as the
cold first), together with ``/jax/compilation_cache/cache_hits`` — so
hits are tallied apart and the count is FRESH compilations.

One process-wide listener is registered on first use and never
removed (jax.monitoring has no unregister API); it fans out to a
stack of active counters, so nested regions each see their own
tally. Note the event fires for EVERY backend compile in the
process — including first-touch eager ops and other threads — so
assertions over a region should either warm unrelated paths first or
allow a small constant slack.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator, List

__all__ = ["count_compiles"]

_lock = threading.Lock()
_installed = False
_active: List["_Tally"] = []


class _Tally:
    """Mutable counter of fresh compilations handed to the caller;
    reads as int. `hits` = requests the persistent cache answered."""

    def __init__(self) -> None:
        self.count = 0
        self.hits = 0

    def __int__(self) -> int:
        return self.count

    def __repr__(self) -> str:
        return f"_Tally(count={self.count}, hits={self.hits})"


def _listener(event: str, duration: float, **kwargs) -> None:
    if "backend_compile" not in event:
        return
    with _lock:
        for t in _active:
            t.count += 1


def _hit_listener(event: str, **kwargs) -> None:
    # fires INSIDE the request whose backend_compile event follows
    if event != "/jax/compilation_cache/cache_hits":
        return
    with _lock:
        for t in _active:
            t.count -= 1
            t.hits += 1


def _install() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_hit_listener)


@contextlib.contextmanager
def count_compiles() -> Iterator[_Tally]:
    """``with count_compiles() as c: ...; int(c)`` — fresh backend
    compiles that happened inside the region (process-wide)."""
    _install()
    tally = _Tally()
    with _lock:
        _active.append(tally)
    try:
        yield tally
    finally:
        with _lock:
            _active.remove(tally)
