"""Device-plane collectives: the same verbs compiled to XLA collectives.

Reference analog: none directly — HPX's collectives are host-value star
fan-ins through a root component (communicator.py replicates that
correctness model). THIS module is the performance model that replaces it
on TPU (SURVEY.md §3.6, §5.8): bulk-array collectives lower to
`lax.psum / all_gather / all_to_all / ppermute` inside `shard_map`, so
XLA schedules ring/tree exchanges over ICI — compiled, not tag-matched,
and never staged through a root.

Two surfaces:
  * whole-array helpers: take a jax.Array sharded over a mesh axis, run
    ONE jitted shard_map program, return the collective's result
    (replicated or resharded as the verb implies);
  * in-body re-exports (psum, pmax, ppermute, ...) for user shard_map
    SPMD code — the `hpx::collectives` verbs usable inside a fork_join-
    style team body.

Programs are cached per (mesh, axis, verb, op) — the first call compiles,
the rest dispatch.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

# In-body verbs (psum, pmax, pmin, pmean, ppermute, axis_index) are
# re-exported lazily via __getattr__ so importing hpx_tpu does not pull
# in jax before the caller has configured platform env vars.
_LAZY_LAX = ("psum", "pmax", "pmin", "pmean", "ppermute", "axis_index")


def __getattr__(name: str):
    if name in _LAZY_LAX:
        from jax import lax
        return getattr(lax, name)
    raise AttributeError(name)


_REDUCERS: Dict[str, Callable] = {}


def _reducers() -> Dict[str, Callable]:
    if not _REDUCERS:
        from jax import lax
        _REDUCERS.update({
            "add": lax.psum, "sum": lax.psum,
            "max": lax.pmax, "min": lax.pmin, "mean": lax.pmean,
        })
    return _REDUCERS


_programs: Dict[Tuple, Any] = {}


def _program(mesh, axis: str, key: Tuple, build: Callable) -> Any:
    # keyed by mesh VALUE (Mesh is hashable): equal-but-distinct Mesh
    # objects (e.g. per-container default layouts) share one compilation
    cache_key = (mesh, axis) + key
    prog = _programs.get(cache_key)
    if prog is None:
        prog = build()
        _programs[cache_key] = prog
    return prog


def _shard_map(body, mesh, in_spec, out_spec):
    import jax
    from jax import shard_map
    # check_vma stays ON (the default): with it off, jax falls back to
    # the legacy psum transpose and silently produces WRONG gradients
    # for differentiated collectives. Each verb below is written so its
    # output's varying-mesh-axes type matches its out_spec (e.g.
    # all_gather is expressed as scatter-place + psum, whose vma rule
    # proves the replication the all_gather rule cannot).
    return jax.jit(shard_map(body, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec))


def _specs(axis: str):
    from jax.sharding import PartitionSpec as P
    return P(axis), P()


def all_reduce(x: Any, mesh, axis: str = "x", op: str = "add") -> Any:
    """Reduce the per-device shards of x with op; replicated result of
    one shard's shape. `op`: add | max | min | mean."""
    sharded, rep = _specs(axis)

    def build():
        reducer = _reducers()[op]
        return _shard_map(lambda s: reducer(s, axis), mesh, sharded, rep)

    return _program(mesh, axis, ("all_reduce", op), build)(x)


def all_gather(x: Any, mesh, axis: str = "x") -> Any:
    """Gather shards along the axis: every device ends with the full
    (concatenated) array, replicated over the WHOLE mesh (`axis` is
    retained for cache keying and API symmetry; the resharding below
    replicates across every mesh axis)."""

    def build():
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        # Whole-array gather IS a resharding: axis-sharded -> fully
        # replicated. GSPMD lowers it to a native all-gather over ICI
        # (no shard_map, so no varying-axes proof is needed), and jax
        # differentiates the resharding exactly.
        return jax.jit(lambda s: s,
                       out_shardings=NamedSharding(mesh, P()))

    return _program(mesh, axis, ("all_gather",), build)(x)


def broadcast(x: Any, mesh, axis: str = "x", root: int = 0) -> Any:
    """Every device gets root's shard (replicated)."""
    import jax.numpy as jnp
    sharded, rep = _specs(axis)

    def build():
        from jax import lax

        def body(s):
            # keep only root's contribution, then sum-reduce: a compiled
            # one-to-all without host staging
            mine = jnp.where(lax.axis_index(axis) == root, s,
                             jnp.zeros_like(s))
            return lax.psum(mine, axis)
        return _shard_map(body, mesh, sharded, rep)

    return _program(mesh, axis, ("broadcast", root), build)(x)


def all_to_all(x: Any, mesh, axis: str = "x") -> Any:
    """Transpose shard ownership: with N devices, shard i's j-th block
    moves to device j's i-th block — the Ulysses/sequence-parallel
    primitive (SURVEY.md §5.7). x stays sharded over the axis."""
    sharded, _ = _specs(axis)
    n_ = mesh.shape[axis]
    shard_len = x.shape[0] // n_
    if x.shape[0] % n_ or shard_len % n_:
        raise ValueError(
            f"all_to_all needs leading dim divisible by n*n (n={n_} devices,"
            f" so a multiple of {n_ * n_}); got shape {tuple(x.shape)}")

    def build():
        from jax import lax
        n = mesh.shape[axis]

        def body(s):
            blocks = s.reshape((n, -1) + s.shape[1:])
            out = lax.all_to_all(blocks, axis, 0, 0, tiled=False)
            return out.reshape((-1,) + s.shape[1:])
        return _shard_map(body, mesh, sharded, sharded)

    return _program(mesh, axis, ("all_to_all",), build)(x)


def reduce_scatter(x: Any, mesh, axis: str = "x", op: str = "add") -> Any:
    """psum_scatter: reduce across devices, leave each device with its
    1/N slice — the bandwidth-optimal half of all_reduce. XLA exposes
    only the additive form (psum_scatter); other ops are rejected rather
    than silently summed."""
    if op not in ("add", "sum"):
        raise ValueError(f"reduce_scatter supports only add, got {op!r}")
    sharded, _ = _specs(axis)

    def build():
        from jax import lax

        def body(s):
            return lax.psum_scatter(s, axis, tiled=True)
        return _shard_map(body, mesh, sharded, sharded)

    return _program(mesh, axis, ("reduce_scatter", op), build)(x)


def ring_shift(x: Any, mesh, axis: str = "x", shift: int = 1) -> Any:
    """Neighbor exchange over the ICI ring (ppermute) — the halo/ring-
    attention substrate. Shard i receives shard (i - shift) mod N."""
    sharded, _ = _specs(axis)

    def build():
        from jax import lax
        n = mesh.shape[axis]
        perm = [(i, (i + shift) % n) for i in range(n)]
        return _shard_map(lambda s: lax.ppermute(s, axis, perm),
                          mesh, sharded, sharded)

    return _program(mesh, axis, ("ring_shift", shift), build)(x)


def barrier(mesh, axis: str = "x") -> None:
    """Device-plane fence: a trivial psum over the axis, blocked on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def build():
        from jax import lax
        sharded, rep = _specs(axis)
        return _shard_map(lambda s: lax.psum(s, axis), mesh, sharded, rep)

    n = mesh.shape[axis]
    token = jax.device_put(
        jnp.zeros((n,), jnp.int32),
        NamedSharding(mesh, P(axis)))
    jax.block_until_ready(_program(mesh, axis, ("barrier",), build)(token))
