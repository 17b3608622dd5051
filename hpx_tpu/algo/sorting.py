"""Sorting and order ops: sort, stable_sort, is_sorted, merge, rotate,
reverse, unique, partition.

Reference analog: libs/core/algorithms include/hpx/parallel/algorithms/
{sort,is_sorted,merge,rotate,reverse,unique,partition}.hpp (parallel
quicksort/merge). Device lowering: XLA's sort (bitonic-style network) via
jnp.sort/argsort — the compiler's sort IS the parallel sort.
"""

from __future__ import annotations

import functools
import operator
from typing import Any, Callable, Optional

from ..exec.policies import ExecutionPolicy
from ._core import (
    device_executor,
    finish,
    is_device_policy,
    to_numpy_view,
)


_SHARDED_SORT_PROGRAMS: dict = {}

# jitted per-element key programs, weakly keyed by the user's key
# function so repeated sorts with the same (named) key reuse one
# executable; inline lambdas are new objects per call and simply miss
import weakref

_KEY_PROGRAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _sharded_axis(a) -> Optional[tuple]:
    """(mesh, axis) when `a` is a jax.Array sharded in contiguous
    chunks over one axis of a 1-D mesh; else None."""
    try:
        from jax.sharding import NamedSharding, PartitionSpec
        sh = getattr(a, "sharding", None)
        if not isinstance(sh, NamedSharding):
            return None
        mesh = sh.mesh
        if len(mesh.axis_names) != 1 or mesh.size <= 1:
            return None
        axis = mesh.axis_names[0]
        if sh.spec != PartitionSpec(axis) or a.ndim != 1:
            return None
        if a.shape[0] % mesh.size:
            return None
        return mesh, axis
    except Exception:  # noqa: BLE001
        return None


def _build_odd_even(mesh, axis: str):
    """Odd-even transposition on blocks: p rounds of pairwise ppermute
    exchange + merge-split (lower-index partner keeps the low half) —
    the classic result that p merge-split phases over p locally sorted
    blocks sort globally. O(p) collective rounds: right shape at small
    p (cheap rounds, no capacity padding), wrong shape at pod scale."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]

    def body(chunk):
        local = jnp.sort(chunk)
        idx = jax.lax.axis_index(axis)
        for r in range(p):
            # round parity picks the pairing: (0,1)(2,3)… then
            # (1,2)(3,4)…; partner = idx±1 by idx parity
            if r % 2 == 0:
                pairs = [(i, i + 1) for i in range(0, p - 1, 2)]
            else:
                pairs = [(i, i + 1) for i in range(1, p - 1, 2)]
            perm = [(a, b) for a, b in pairs] + \
                   [(b, a) for a, b in pairs]
            paired = jnp.zeros((), jnp.bool_)
            lower = jnp.zeros((), jnp.bool_)
            for a, b in pairs:
                paired = paired | (idx == a) | (idx == b)
                lower = lower | (idx == a)
            recv = jax.lax.ppermute(local, axis, perm)
            both = jnp.sort(jnp.concatenate([local, recv]))
            m = local.shape[0]
            keep = jnp.where(lower, both[:m], both[m:])
            local = jnp.where(paired, keep, local)
        return local

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis)))


def _sort_key_fns(dt):
    """(to_key, from_key, key_dtype): a TOTAL-ORDER integer key per
    value dtype, so the sample sort's comparisons/padding never meet
    IEEE partial order. Floats use the classic sign-flip bitcast
    (negatives bit-inverted, positives sign-bit-set → unsigned order
    == numeric order), with every NaN forced to the key-space max so
    NaNs sort last exactly like jnp.sort/np.sort (payloads collapse to
    one canonical NaN on the way back). Ints/bools are their own key."""
    import jax
    import jax.numpy as jnp

    if jnp.issubdtype(dt, jnp.integer):
        return (lambda v: v), (lambda k: k), dt
    if dt == jnp.bool_:
        return (lambda v: v.astype(jnp.uint8)), \
               (lambda k: k.astype(jnp.bool_)), jnp.dtype(jnp.uint8)
    if not jnp.issubdtype(dt, jnp.floating):
        raise TypeError(f"sort_sharded: unsupported dtype {dt}")
    nbits = jnp.dtype(dt).itemsize * 8
    ui = jnp.dtype(f"uint{nbits}")
    sign = ui.type(1 << (nbits - 1))
    allbits = ui.type((1 << nbits) - 1)

    def to_key(v):
        u = jax.lax.bitcast_convert_type(v, ui)
        k = jnp.where((u & sign) != 0, ~u, u | sign)
        return jnp.where(jnp.isnan(v), allbits, k)

    def from_key(k):
        u = jnp.where((k & sign) != 0, k ^ sign, ~k)
        return jax.lax.bitcast_convert_type(u.astype(ui), dt)

    return to_key, from_key, ui


def _transport_fns(dt):
    """(encode, decode, wire_dtype): lossless BIT transport of any
    fixed-width dtype as unsigned ints (the by-key payload path — the
    payload is moved, never compared; integer wire format keeps the
    final zero-identity sum-scatter exact)."""
    import jax
    import jax.numpy as jnp
    if dt == jnp.bool_:
        return (lambda x: x.astype(jnp.uint8)), \
               (lambda u: u.astype(jnp.bool_)), jnp.dtype(jnp.uint8)
    nbits = jnp.dtype(dt).itemsize * 8
    ui = jnp.dtype(f"uint{nbits}")
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return (lambda x: x), (lambda u: u), jnp.dtype(dt)
    return (lambda x: jax.lax.bitcast_convert_type(x, ui)), \
           (lambda u: jax.lax.bitcast_convert_type(u, dt)), ui


def _build_sample_sort(mesh, axis: str, with_payload: bool = False):
    """One-shot sample sort (PSRS — parallel sorting by regular
    sampling): local sort → rank-stripe all_to_all → regular-sample
    splitters via all_gather → ONE bucket all_to_all → local merge →
    exact-rank rebalance all_to_all. O(1) collective steps regardless
    of p (vs odd-even's p rounds) — the pod-scale shape.

    Correctness under duplicates and static shapes, the two things XLA
    makes hard:

    * Every element carries a lexicographic key (value, global_id), so
      keys are DISTINCT and the PSRS bucket bound B_j < 2M (M = padded
      chunk length) is a theorem, not a hope — all-equal inputs
      bucket by id and stay balanced.
    * The rank-stripe pre-exchange (element of local sorted rank r
      moves to device r mod p) makes each device's chunk a union of
      p regular subsamples of sorted chunks. A bucket is a contiguous
      key interval, and a stride-p subsample of a contiguous run of
      length L contains at most L/p + 1 elements, so the per-pair
      send in the bucket exchange is <= B_j/p + p < 2M/p + p — a
      STATIC capacity, so the all_to_all buffer is (p, 2M/p + p + 2)
      instead of the worst-case (p, M) a one-shot exchange would
      otherwise need.
    * Buckets land whole on their device with sizes b_j != m, so a
      final exchange places every element at its exact global rank g
      (device g//m, slot g%m; ranks from an all_gather of bucket
      sizes): output is exactly m per device, same sharding in as out.

    Values travel as total-order integer keys (_sort_key_fns: floats
    sign-flip-bitcast so unsigned order == numeric order with NaN
    forced last like np.sort; ints/bools are their own key), which
    also makes padding trivial: (key-space max, id >= n) sorts after
    every real key, takes ranks >= n, and is dropped by the final
    scatter's mode='drop'. NOT stable (equal values reorder by global
    id, which for distributed duplicates is original-position order —
    but the public contract stays "unstable"; stable_sort keeps the
    XLA path). NaN payloads collapse to one canonical NaN.

    with_payload=True builds the BY-KEY variant: the program takes
    (keys, values) and returns values reordered by ascending key. The
    payload rides every exchange under the same permutations (as its
    own total-order-key transport, so the sum-scatter trick still
    works), and the gid tiebreak makes this one STABLE — equal keys
    keep original global order.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]

    def body(chunk, payload=None):
        m = chunk.shape[0]
        n = m * p
        to_key, from_key, kdt = _sort_key_fns(chunk.dtype)
        kmax = jnp.iinfo(kdt).max
        i = jax.lax.axis_index(axis)

        mp_ = -(-m // p)               # ceil(m/p)
        M = mp_ * p
        pad = M - m
        # ids/ranks span [0, n + p*pad): int32 until ~2^31 elements,
        # int64 beyond (needs x64; wrapped ids would break the
        # distinct-(key,gid) property the capacity bound rests on)
        if n + p * pad < 2 ** 31:
            idt = jnp.int32
        elif jax.config.jax_enable_x64:
            idt = jnp.int64
        else:
            raise ValueError(
                f"sort_sharded(sample): n={n} needs 64-bit ids; "
                "enable jax x64 or use method='odd_even'")
        # widen the device index BEFORE the product: i*m in int32 wraps
        # at the very scale the int64 path exists for
        gid = i.astype(idt) * m + jnp.arange(m, dtype=idt)
        v = to_key(chunk)              # total-order integer keys
        if payload is not None:
            # plain BIT transport (never compared): lossless for any
            # fixed-width dtype incl. NaN payload bits, and integer so
            # the final zero-identity sum-scatter stays exact
            to_pk, from_pk, pdt = _transport_fns(payload.dtype)
            w = to_pk(payload)
        if pad:
            v = jnp.concatenate([v, jnp.full((pad,), kmax, kdt)])
            gid = jnp.concatenate(
                [gid, jnp.asarray(n, idt) + i.astype(idt) * pad
                 + jnp.arange(pad, dtype=idt)])
            if payload is not None:
                w = jnp.concatenate([w, jnp.zeros((pad,), pdt)])

        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0, tiled=True)

        def stripe(arr):
            return a2a(arr.reshape(mp_, p).T.reshape(p, mp_)).reshape(M)

        # ---- phase A: local sort + rank stripe (balances bucket
        # composition across sources; per-pair volume exactly M/p)
        order = jnp.lexsort((gid, v))
        v, gid = v[order], gid[order]
        if payload is not None:
            w = stripe(w[order])
        v, gid = stripe(v), stripe(gid)
        order = jnp.lexsort((gid, v))
        v, gid = v[order], gid[order]
        if payload is not None:
            w = w[order]

        # ---- phase B: p regular samples/device -> p^2 gathered ->
        # splitters at every p-th (p-1 of them)
        sv = jax.lax.all_gather(v[0::mp_][:p], axis).reshape(-1)
        sg = jax.lax.all_gather(gid[0::mp_][:p], axis).reshape(-1)
        sorder = jnp.lexsort((sg, sv))
        sv, sg = sv[sorder], sg[sorder]
        sv, sg = sv[p::p][:p - 1], sg[p::p][:p - 1]

        # ---- phase C: bucket by splitter count (lexicographic), ONE
        # capacity-bounded all_to_all
        less = (sv[None, :] < v[:, None]) | (
            (sv[None, :] == v[:, None]) & (sg[None, :] <= gid[:, None]))
        dest = less.sum(axis=1).astype(jnp.int32)          # (M,) in [0,p)
        counts = jnp.bincount(dest, length=p).astype(jnp.int32)
        cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts)[:-1]])
        off = jnp.arange(M, dtype=jnp.int32) - cum[dest]   # dest is sorted
        cap = 2 * mp_ + p + 2                              # PSRS bound + slack
        bv = jnp.zeros((p, cap), kdt).at[dest, off].set(v, mode="drop")
        bg = jnp.full((p, cap), jnp.iinfo(idt).max,
                      idt).at[dest, off].set(gid, mode="drop")
        rv = a2a(bv).reshape(-1)
        rg = a2a(bg).reshape(-1)
        rc = a2a(counts.reshape(p, 1)).reshape(p)          # per-src counts
        if payload is not None:
            bw = jnp.zeros((p, cap), pdt).at[dest, off].set(
                w, mode="drop")
            rw = a2a(bw).reshape(-1)

        # ---- local merge of my bucket (invalid slots sort last)
        invalid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                   >= rc[:, None]).reshape(-1)
        order = jnp.lexsort((rg, rv, invalid))
        rv, rg = rv[order], rg[order]
        if payload is not None:
            rw = rw[order]
        b_mine = rc.sum()

        # ---- phase D: exact global rank -> (device, slot) scatter.
        # bucket sizes all_gather'd; padding keys rank >= n and invalid
        # slots get dest p — both dropped by mode='drop'.
        sizes = jax.lax.all_gather(b_mine, axis).astype(idt)   # (p,)
        base = jnp.concatenate([jnp.zeros(1, idt),
                                jnp.cumsum(sizes)[:-1]])[i]
        pos = jnp.arange(p * cap, dtype=idt)
        grank = base + pos
        d2 = jnp.where((pos < b_mine) & (grank < n), grank // m, p)
        o2 = grank % m
        # exactly one source owns each global rank, empty slots are 0
        if payload is None:
            out = jnp.zeros((p, m), kdt).at[d2, o2].set(rv, mode="drop")
            return from_key(a2a(out).sum(axis=0))
        pout = jnp.zeros((p, m), pdt).at[d2, o2].set(rw, mode="drop")
        return from_pk(a2a(pout).sum(axis=0))

    if with_payload:
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P(axis), P(axis)),
                                 out_specs=P(axis)))
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis)))


def sort_sharded(v: Any, mesh, axis: str = "x",
                 method: Optional[str] = None) -> Any:
    """Globally sort a 1-D array sharded over `axis` WITHOUT gathering.

    Two compiled strategies (reference analog: the segmented sort over
    partitioned data, SURVEY.md §2.4 segmented_algorithms):

    * ``sample``  — one-shot PSRS sample sort: O(1) all_to_all steps
      independent of mesh size (see _build_sample_sort). Default for
      p > 4: at pod scale, collective-step count is what matters.
    * ``odd_even`` — p rounds of neighbor merge-split. Default for
      p <= 4 where its simplicity and lack of capacity padding win.

    Both are fully compiled (static shapes, XLA collectives over ICI)
    and NOT stable; stable_sort keeps the XLA gather path."""
    p = mesh.shape[axis]
    if method is None:
        method = "odd_even" if p <= 4 else "sample"
    elif method not in ("sample", "odd_even"):
        raise ValueError(f"sort_sharded: unknown method {method!r} "
                         "(expected 'sample' or 'odd_even')")
    from ..core.programs import cached_program
    build = (_build_sample_sort if method == "sample"
             else _build_odd_even)
    prog = cached_program(_SHARDED_SORT_PROGRAMS, (method, mesh, axis),
                          lambda: build(mesh, axis))
    return prog(v)


def sort_sharded_by_key(keys: Any, values: Any, mesh,
                        axis: str = "x") -> Any:
    """Reorder a sharded 1-D `values` by ascending sharded `keys`
    WITHOUT gathering — the PSRS sample sort with the values riding
    every exchange as payload (lossless bit transport — payload NaN
    bit patterns survive). STABLE: the global-id tiebreak preserves
    original order for equal keys."""
    from ..core.programs import cached_program
    prog = cached_program(
        _SHARDED_SORT_PROGRAMS, ("sample_by_key", mesh, axis),
        lambda: _build_sample_sort(mesh, axis, with_payload=True))
    return prog(keys, values)


def sort(policy: ExecutionPolicy, rng: Any,
         key: Optional[Callable] = None) -> Any:
    """Returns the sorted range. `key` maps elements to sort keys
    (HPX's comparator generalized to the key form jax supports).
    A range sharded over a 1-D mesh sorts DISTRIBUTED — with or
    without a key — through the segmented-algorithms sort
    (sort_sharded / sort_sharded_by_key: no gather, O(1) collective
    steps on the sample path)."""
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        sharded = _sharded_axis(rng)
        if sharded:
            mesh, axis = sharded
            if key is None:
                dispatch = lambda a: sort_sharded(a, mesh, axis)  # noqa: E731
            else:
                kp = _KEY_PROGRAMS.get(key)
                if kp is None:
                    kp = jax.jit(jax.vmap(key))
                    try:
                        _KEY_PROGRAMS[key] = kp
                    except TypeError:
                        pass

                def dispatch(a, kp=kp):
                    # keys computed shard-locally (elementwise vmap
                    # keeps the input's sharding), then the by-key
                    # program reorders the values — stable, like the
                    # single-device stable-argsort path below
                    return sort_sharded_by_key(kp(a), a, mesh, axis)
            fut = ex.async_execute_raw(dispatch, rng) \
                if hasattr(ex, "async_execute_raw") else \
                ex.async_execute(dispatch, rng)
            return fut if policy.is_task else fut.get()

        def kernel(a):
            flat = a.reshape(-1)
            if key is None:
                return jnp.sort(flat)
            ks = jax.vmap(key)(flat)
            return flat[jnp.argsort(ks, stable=True)]
        fut = ex.async_execute(kernel, rng)
        return fut if policy.is_task else fut.get()

    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if key is None:
            return np.sort(arr, kind="stable")
        ks = np.array([key(x) for x in arr])
        return arr[np.argsort(ks, kind="stable")]

    return finish(policy, run)


stable_sort = sort  # device sort with stable argsort; numpy kind="stable"


def is_sorted(policy: ExecutionPolicy, rng: Any) -> Any:
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(
            lambda a: (a.reshape(-1)[1:] >= a.reshape(-1)[:-1]).all(), rng)
        if policy.is_task:
            return fut.then(lambda f: bool(f.get()))
        return bool(fut.get())
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        return bool(np.all(arr[1:] >= arr[:-1]))

    return finish(policy, run)


def merge(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Merge two sorted ranges into one sorted range."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(
            lambda a, b: jnp.sort(jnp.concatenate(
                [a.reshape(-1), b.reshape(-1)])), rng, rng2)
        return fut if policy.is_task else fut.get()
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        return np.sort(np.concatenate([a, b]), kind="stable")

    return finish(policy, run)


def reverse(policy: ExecutionPolicy, rng: Any) -> Any:
    if is_device_policy(policy, rng):
        ex = device_executor(policy)
        fut = ex.async_execute(lambda a: a[::-1], rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)
    return finish(policy, lambda: arr[::-1].copy())


def rotate(policy: ExecutionPolicy, rng: Any, middle: int) -> Any:
    """Left-rotate so that rng[middle] becomes the first element."""
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(lambda a: jnp.roll(a, -middle), rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        return np.roll(arr, -middle)

    return finish(policy, run)


def unique(policy: ExecutionPolicy, rng: Any) -> Any:
    """Remove consecutive duplicates (std::unique semantics, shrunk).

    Output size is data-dependent: device path computes the keep-mask on
    device and compacts at the host boundary (static shapes under jit)."""
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        mask_fut = ex.async_execute(
            lambda a: jnp.concatenate(
                [jnp.ones(1, bool),
                 a.reshape(-1)[1:] != a.reshape(-1)[:-1]]), rng)

        def run():
            import numpy as np
            # hpxlint: disable-next=HPX002 — data-dependent compaction:
            # device computed the uniqueness mask; host gather builds
            # the dynamic-shape result
            mask = np.asarray(mask_fut.get())
            # hpxlint: disable-next=HPX002 — host gather (see above)
            return jnp.asarray(np.asarray(rng).reshape(-1)[mask])
        return finish(policy, run)
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if len(arr) == 0:
            return arr.copy()
        mask = np.concatenate([[True], arr[1:] != arr[:-1]])
        return arr[mask]

    return finish(policy, run)


def partition(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    """Stable partition: satisfying elements first; returns (range,
    partition_point)."""
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            flat = a.reshape(-1)
            m = jax.vmap(pred)(flat)
            # stable partition via stable argsort of negated mask
            order = jnp.argsort(~m, stable=True)
            return flat[order], m.sum()
        fut = ex.async_execute(kernel, rng)

        def done(f):
            arr2, point = f.get()
            return arr2, int(point)
        return fut.then(done) if policy.is_task else done(fut)
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        mask = np.array([bool(pred(x)) for x in arr], dtype=bool)
        return np.concatenate([arr[mask], arr[~mask]]), int(mask.sum())

    return finish(policy, run)


def partial_sort(policy: ExecutionPolicy, rng: Any, middle: int) -> Any:
    """Rearrange so the smallest `middle` elements are first and sorted;
    the tail is unspecified (std::partial_sort). Device path lowers to
    the full XLA sort — on TPU the compiler's O(n log n) sort network is
    the parallel sort, and a sorted tail satisfies 'unspecified'; the
    host path does a real introselect + head sort."""
    if is_device_policy(policy, rng):
        return sort(policy, rng)
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if middle <= 0:
            return arr.copy()
        if middle >= len(arr):
            return np.sort(arr, kind="stable")
        out = np.partition(arr, middle - 1)
        out[:middle] = np.sort(out[:middle], kind="stable")
        return out

    return finish(policy, run)


def partial_sort_copy(policy: ExecutionPolicy, rng: Any, k: int) -> Any:
    """The k smallest elements, sorted (std::partial_sort_copy with a
    length-k destination). Device path: lax.top_k on the negated range —
    O(n log k), never materializes a full sort when k << n."""
    k = max(0, min(k, len(rng)))
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            flat = a.reshape(-1)
            if k == 0:                         # static shapes
                return flat[:0]
            if not jnp.issubdtype(flat.dtype, jnp.floating):
                # integer/bool negation wraps (unsigned always, signed
                # at INT_MIN): take the sort-slice path
                return jnp.sort(flat)[:k]
            neg, _ = jax.lax.top_k(-flat, k)   # top_k descending on the
            return -neg                        # negation == ascending k-smallest
        fut = ex.async_execute(kernel, rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if k == 0:
            return arr[:0].copy()
        if k >= len(arr):
            return np.sort(arr, kind="stable")
        return np.sort(np.partition(arr, k - 1)[:k], kind="stable")

    return finish(policy, run)


def nth_element(policy: ExecutionPolicy, rng: Any, n: int) -> Any:
    """Rearrange so position n holds the element that would be there in
    a full sort, with everything before it <= and after it >=
    (std::nth_element). Device path lowers to the full XLA sort (which
    satisfies the postcondition); host path is numpy's introselect."""
    if is_device_policy(policy, rng):
        return sort(policy, rng)
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if not 0 <= n < len(arr):
            return arr.copy()
        return np.partition(arr, n)

    return finish(policy, run)


def shift_left(policy: ExecutionPolicy, rng: Any, n: int) -> Any:
    """Shift elements n positions toward the front; the vacated tail
    keeps its original values ('unspecified' per std::shift_left)."""
    if n <= 0:
        from .elementwise import copy as _copy
        return _copy(policy, rng)
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(
            lambda a: a if n >= a.shape[0] else
            jnp.concatenate([a[n:], a[a.shape[0] - n:]]), rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def run():
        out = arr.copy()
        if n < len(arr):
            out[:len(arr) - n] = arr[n:]
        return out

    return finish(policy, run)


def shift_right(policy: ExecutionPolicy, rng: Any, n: int) -> Any:
    """Shift elements n positions toward the back; the vacated head
    keeps its original values ('unspecified' per std::shift_right)."""
    if n <= 0:
        from .elementwise import copy as _copy
        return _copy(policy, rng)
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(
            lambda a: a if n >= a.shape[0] else
            jnp.concatenate([a[:n], a[:a.shape[0] - n]]), rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def run():
        out = arr.copy()
        if n < len(arr):
            out[n:] = arr[:len(arr) - n]
        return out

    return finish(policy, run)


def swap_ranges(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Exchange the contents of two equal-length ranges; returns the
    (new_rng, new_rng2) pair (std::swap_ranges in the functional data
    model: a swap IS returning the copies crossed over)."""
    from .elementwise import copy as _copy
    if len(rng) != len(rng2):
        raise ValueError("swap_ranges: ranges must have equal length")
    a2 = _copy(policy, rng2)
    b2 = _copy(policy, rng)
    if policy.is_task:
        from ..futures.combinators import when_all
        return when_all(a2, b2).then(
            lambda f: tuple(x.get() for x in f.get()))
    return a2, b2


def partition_copy(policy: ExecutionPolicy, rng: Any,
                   pred: Callable) -> Any:
    """(true_part, false_part) — the pred-satisfying elements and the
    rest, each in stable order (std::partition_copy as a pair return)."""
    res = partition(policy, rng, pred)

    def split(pair):
        arr2, point = pair
        return arr2[:point], arr2[point:]
    if policy.is_task:
        return res.then(lambda f: split(f.get()))
    return split(res)


def is_heap_until(policy: ExecutionPolicy, rng: Any) -> Any:
    """Index of the first element that breaks the max-heap property
    (a[(i-1)//2] >= a[i]), or len(rng) when the whole range is a heap
    (std::is_heap_until as an index). One vectorized parent-compare —
    the heap property is embarrassingly parallel."""
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            f = a.reshape(-1)
            n = f.shape[0]
            if n <= 1:                 # static shape
                return jnp.asarray(n)
            i = jnp.arange(1, n)
            bad = f[(i - 1) // 2] < f[i]
            return jnp.where(bad.any(), jnp.argmax(bad) + 1, n)
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        n = len(arr)
        if n <= 1:
            return n
        i = np.arange(1, n)
        bad = np.flatnonzero(arr[(i - 1) // 2] < arr[i])
        # (via to_numpy_view), no device sync happens here
        return int(bad[0]) + 1 if bad.size else n

    return finish(policy, run)


def is_heap(policy: ExecutionPolicy, rng: Any) -> Any:
    """True when the range is a max-heap (std::is_heap)."""
    res = is_heap_until(policy, rng)
    if policy.is_task:
        return res.then(lambda f: f.get() == len(rng))
    return res == len(rng)
