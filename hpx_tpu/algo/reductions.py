"""Reductions and searches: reduce, transform_reduce, count, any/all/none,
min/max/minmax element values, equal, mismatch, find.

Reference analog: libs/core/algorithms include/hpx/parallel/algorithms/
{reduce,transform_reduce,count,all_any_none,minmax,equal,mismatch,find}.hpp.

Device lowering: reduction with an arbitrary traceable binary op uses
jax.lax.reduce in ONE jitted program; transform_reduce fuses map+reduce —
this is the config #1 (SAXPY+dot) path where XLA fuses the multiply into
the reduction and the MXU/VPU stream the whole range from HBM once.
"""

from __future__ import annotations

import operator
from typing import Any, Callable, Optional

from ..exec.policies import ExecutionPolicy
from ._core import (
    device_executor,
    finish,
    host_bulk,
    is_device_policy,
    to_numpy_view,
)


import operator as _op

# Fast paths with known identities; lax.reduce would use `init` as the
# per-tile identity, which silently corrupts results for non-identity
# inits, so the general path folds neighbours pairwise (identity-free,
# `_pairwise_fold`) and applies init exactly once.
_KNOWN_FOLDS = {}


def _known_folds():
    if not _KNOWN_FOLDS:
        import jax.numpy as jnp
        # (whole-array fold, traceable binary combiner) — the combiner is
        # needed because builtin min/max cannot run on tracers
        _KNOWN_FOLDS.update({
            _op.add: (jnp.sum, jnp.add), _op.mul: (jnp.prod, jnp.multiply),
            min: (jnp.min, jnp.minimum), max: (jnp.max, jnp.maximum),
        })
        # the jnp spellings of the same ops (examples/saxpy_tpu.py
        # passes jnp.add) fold the same way
        _KNOWN_FOLDS.update({combine: (fold, combine) for fold, combine
                             in list(_KNOWN_FOLDS.values())})
    return _KNOWN_FOLDS


def _pairwise_fold(op: Callable, flat: Any) -> Any:
    """Fold by combining NEIGHBOURS, log2(n) times: needs associativity
    only (order is kept, no identity). A prefix scan taken for its last
    element computes the same value, but its program grows with n — at
    2^20 elements the TPU compiler needs 97 s for it, at 2^22 over
    300 s, and at 2^24 it did not finish in 25 minutes on the chip."""
    import jax
    import jax.numpy as jnp
    vop = jax.vmap(op)
    while flat.shape[0] > 1:
        n = flat.shape[0]
        pairs = flat[:n - n % 2].reshape(n // 2, 2)
        head = vop(pairs[:, 0], pairs[:, 1])
        flat = head if n % 2 == 0 else jnp.concatenate([head, flat[n - 1:]])
    return flat[0]


def _device_reduce_kernel(op: Callable, init: Any):
    import jax
    import jax.numpy as jnp

    def kernel(a):
        flat = a.reshape(-1)
        known = _known_folds().get(op)
        if known is not None:
            fold, combine = known
            total = fold(flat)
        else:
            combine = op
            total = _pairwise_fold(op, flat)
        return combine(jnp.asarray(init, flat.dtype), total)

    return kernel


def reduce(policy: ExecutionPolicy, rng: Any, init: Any = 0,
           op: Callable = operator.add) -> Any:
    if is_device_policy(policy, rng):
        ex = device_executor(policy)
        fut = ex.async_execute(_device_reduce_kernel(op, init), rng)
        return fut if policy.is_task else fut.get()

    arr = to_numpy_view(rng)

    def chunk(b: int, e: int) -> Any:
        acc = None
        for i in range(b, e):
            acc = arr[i] if acc is None else op(acc, arr[i])
        return acc

    def run():
        partials = [p for p in host_bulk(policy, len(arr), chunk)
                    if p is not None]
        acc = init
        for p in partials:
            acc = op(acc, p)
        return acc

    return finish(policy, run)


def transform_reduce(policy: ExecutionPolicy, rng: Any, init: Any,
                     reduce_op: Callable, transform_op: Callable,
                     rng2: Optional[Any] = None) -> Any:
    """transform_reduce(policy, a, init, plus, f) or the binary
    (inner-product) form transform_reduce(policy, a, b, init, plus, mul)
    spelled transform_reduce(policy, a, init, plus, mul, rng2=b)."""
    if is_device_policy(policy, rng, rng2):
        import jax
        ex = device_executor(policy)

        if rng2 is None:
            def kernel(a):
                mapped = jax.vmap(transform_op)(a.reshape(-1))
                return _device_reduce_kernel(reduce_op, init)(mapped)
            fut = ex.async_execute(kernel, rng)
        else:
            def kernel2(a, b):
                mapped = jax.vmap(transform_op)(a.reshape(-1), b.reshape(-1))
                return _device_reduce_kernel(reduce_op, init)(mapped)
            fut = ex.async_execute(kernel2, rng, rng2)
        return fut if policy.is_task else fut.get()

    a = to_numpy_view(rng)
    b = to_numpy_view(rng2) if rng2 is not None else None

    def chunk(lo: int, hi: int) -> Any:
        acc = None
        for i in range(lo, hi):
            v = transform_op(a[i]) if b is None else transform_op(a[i], b[i])
            acc = v if acc is None else reduce_op(acc, v)
        return acc

    def run():
        partials = [p for p in host_bulk(policy, len(a), chunk)
                    if p is not None]
        acc = init
        for p in partials:
            acc = reduce_op(acc, p)
        return acc

    return finish(policy, run)


def count(policy: ExecutionPolicy, rng: Any, value: Any) -> Any:
    return count_if(policy, rng, lambda x: x == value)


def count_if(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(
            lambda a: jax.vmap(pred)(a.reshape(-1)).sum(dtype=jnp.int32), rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def chunk(b: int, e: int) -> int:
        return sum(1 for i in range(b, e) if pred(arr[i]))

    return finish(policy,
                  lambda: sum(host_bulk(policy, len(arr), chunk)))


def _bool_query(policy: ExecutionPolicy, rng: Any, pred: Callable,
                combine: str) -> Any:
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            m = jax.vmap(pred)(a.reshape(-1))
            return jnp.all(m) if combine == "all" else jnp.any(m)
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: bool(f.get()))
        return bool(fut.get())
    arr = to_numpy_view(rng)

    def chunk(b: int, e: int) -> bool:
        it = (bool(pred(arr[i])) for i in range(b, e))
        return all(it) if combine == "all" else any(it)

    def run():
        parts = host_bulk(policy, len(arr), chunk)
        return all(parts) if combine == "all" else any(parts)

    return finish(policy, run)


def all_of(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    return _bool_query(policy, rng, pred, "all")


def any_of(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    return _bool_query(policy, rng, pred, "any")


def none_of(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    r = any_of(policy, rng, pred)
    from ..futures.future import Future
    if isinstance(r, Future):
        return r.then(lambda f: not f.get())
    return not r


def min_element(policy: ExecutionPolicy, rng: Any) -> Any:
    return _minmax(policy, rng, "min")


def max_element(policy: ExecutionPolicy, rng: Any) -> Any:
    return _minmax(policy, rng, "max")


def minmax_element(policy: ExecutionPolicy, rng: Any) -> Any:
    return _minmax(policy, rng, "minmax")


def _minmax(policy: ExecutionPolicy, rng: Any, which: str) -> Any:
    """Returns the min/max VALUE (HPX returns iterators; values are the
    range-functional equivalent). minmax returns a (min, max) pair."""
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)
        if which == "min":
            fut = ex.async_execute(lambda a: a.min(), rng)
        elif which == "max":
            fut = ex.async_execute(lambda a: a.max(), rng)
        else:
            fut = ex.async_execute(
                lambda a: jnp.stack([a.min(), a.max()]), rng)
        return fut if policy.is_task else fut.get()
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if which == "min":
            return arr.min()
        if which == "max":
            return arr.max()
        return (arr.min(), arr.max())

    return finish(policy, run)


def equal(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)
        fut = ex.async_execute(lambda a, b: jnp.array_equal(a, b), rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: bool(f.get()))
        return bool(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        return bool(np.array_equal(a, b))

    return finish(policy, run)


def mismatch(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Index of first mismatch, or -1 (iterator-pair analog)."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a, b):
            neq = (a.reshape(-1) != b.reshape(-1))
            any_neq = neq.any()
            idx = jnp.argmax(neq)
            return jnp.where(any_neq, idx, -1)
        fut = ex.async_execute(kernel, rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        neq = np.flatnonzero(a != b)
        # (via to_numpy_view), no device sync happens here
        return int(neq[0]) if neq.size else -1

    return finish(policy, run)


def find(policy: ExecutionPolicy, rng: Any, value: Any) -> Any:
    return find_if(policy, rng, lambda x: x == value)


def find_if(policy: ExecutionPolicy, rng: Any, pred: Callable) -> Any:
    """Index of first match, or -1."""
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            m = jax.vmap(pred)(a.reshape(-1))
            return jnp.where(m.any(), jnp.argmax(m), -1)
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    arr = to_numpy_view(rng)

    def chunk(b: int, e: int) -> int:
        for i in range(b, e):
            if pred(arr[i]):
                return i
        return -1

    def run():
        for idx in host_bulk(policy, len(arr), chunk):
            if idx != -1:
                return idx
        return -1

    return finish(policy, run)


def is_sorted_until(policy: ExecutionPolicy, rng: Any) -> Any:
    """Index of the first element breaking ascending order (the
    std::is_sorted_until iterator as an index), or len(rng) if sorted."""
    if is_device_policy(policy, rng):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            f = a.reshape(-1)
            if f.shape[0] <= 1:        # static shape: nothing to break
                return jnp.asarray(f.shape[0])
            bad = f[1:] < f[:-1]
            return jnp.where(bad.any(), jnp.argmax(bad) + 1, f.shape[0])
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        if len(arr) <= 1:
            return len(arr)
        bad = np.flatnonzero(arr[1:] < arr[:-1])
        return int(bad[0]) + 1 if bad.size else len(arr)

    return finish(policy, run)


def is_partitioned(policy: ExecutionPolicy, rng: Any,
                   pred: Callable) -> Any:
    """True when every pred-satisfying element precedes every
    non-satisfying one (std::is_partitioned)."""
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            m = jax.vmap(pred)(a.reshape(-1))
            # partitioned <=> mask is non-increasing
            return (m[1:].astype(jnp.int8)
                    <= m[:-1].astype(jnp.int8)).all()
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: bool(f.get()))
        return bool(fut.get())
    arr = to_numpy_view(rng)

    def run():
        import numpy as np
        parts = host_bulk(
            policy, len(arr),
            lambda b, e: [bool(pred(arr[i])) for i in range(b, e)])
        mask = np.array([m for part in parts for m in part], dtype=bool)
        if mask.size <= 1:
            return True
        # partitioned <=> mask is non-increasing
        return bool((mask[1:].astype(np.int8)
                     <= mask[:-1].astype(np.int8)).all())

    return finish(policy, run)


def lexicographical_compare(policy: ExecutionPolicy, rng: Any,
                            rng2: Any) -> Any:
    """True when rng compares lexicographically LESS than rng2."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a, b):
            fa, fb = a.reshape(-1), b.reshape(-1)
            n = min(fa.shape[0], fb.shape[0])
            if n == 0:                 # static: empty prefix — length
                return jnp.asarray(fa.shape[0] < fb.shape[0])  # decides
            lt = fa[:n] < fb[:n]
            ne = fa[:n] != fb[:n]
            first = jnp.where(ne.any(), jnp.argmax(ne), n)
            in_prefix = first < n
            # differ inside the common prefix: that position decides;
            # else the shorter range is the lesser
            return jnp.where(in_prefix,
                             lt[jnp.minimum(first, n - 1)],
                             fa.shape[0] < fb.shape[0])
        fut = ex.async_execute(kernel, rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: bool(f.get()))
        return bool(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        n = min(len(a), len(b))
        if n:
            ne = np.flatnonzero(a[:n] != b[:n])
            if ne.size:
                i = int(ne[0])
                return bool(a[i] < b[i])
        return len(a) < len(b)

    return finish(policy, run)


def find_first_of(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Index of the first element of rng that equals ANY element of
    rng2, or -1 (std::find_first_of)."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a, b):
            fa, fb = a.reshape(-1), b.reshape(-1)
            if fa.shape[0] == 0 or fb.shape[0] == 0:   # static shapes
                return jnp.asarray(-1)
            m = (fa[:, None] == fb[None, :]).any(axis=1)
            return jnp.where(m.any(), jnp.argmax(m), -1)
        fut = ex.async_execute(kernel, rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        if len(a) == 0 or len(b) == 0:
            return -1
        hits = np.flatnonzero(np.isin(a, b))
        return int(hits[0]) if hits.size else -1

    return finish(policy, run)


def _window_match(jnp, fa, fb):
    """(n-m+1,) bool: window i of fa equals fb elementwise. Static
    shapes: the (n-m+1, m) window gather is one XLA gather the compiler
    tiles; fine at the m << n shapes subsequence search is for."""
    n, m = fa.shape[0], fb.shape[0]
    idx = jnp.arange(n - m + 1)[:, None] + jnp.arange(m)[None, :]
    return (fa[idx] == fb[None, :]).all(axis=1)


def search(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Index of the FIRST occurrence of subsequence rng2 in rng, or -1
    (std::search). An empty needle matches at 0."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a, b):
            fa, fb = a.reshape(-1), b.reshape(-1)
            if fb.shape[0] == 0:                       # static shapes:
                return jnp.asarray(0)                  # empty needle
            if fb.shape[0] > fa.shape[0]:
                return jnp.asarray(-1)
            m = _window_match(jnp, fa, fb)
            return jnp.where(m.any(), jnp.argmax(m), -1)
        fut = ex.async_execute(kernel, rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        if len(b) == 0:
            return 0
        if len(b) > len(a):
            return -1
        starts = np.flatnonzero(a[:len(a) - len(b) + 1] == b[0])
        for i in starts:
            if np.array_equal(a[i:i + len(b)], b):
                return int(i)
        return -1

    return finish(policy, run)


def find_end(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """Index of the LAST occurrence of subsequence rng2 in rng, or -1
    (std::find_end). An empty needle matches at len(rng)."""
    if is_device_policy(policy, rng, rng2):
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a, b):
            fa, fb = a.reshape(-1), b.reshape(-1)
            if fb.shape[0] == 0:
                return jnp.asarray(fa.shape[0])
            if fb.shape[0] > fa.shape[0]:
                return jnp.asarray(-1)
            m = _window_match(jnp, fa, fb)
            last = m.shape[0] - 1 - jnp.argmax(m[::-1])
            return jnp.where(m.any(), last, -1)
        fut = ex.async_execute(kernel, rng, rng2)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    a, b = to_numpy_view(rng), to_numpy_view(rng2)

    def run():
        import numpy as np
        if len(b) == 0:
            return len(a)
        if len(b) > len(a):
            return -1
        starts = np.flatnonzero(a[:len(a) - len(b) + 1] == b[0])
        for i in starts[::-1]:
            if np.array_equal(a[i:i + len(b)], b):
                return int(i)
        return -1

    return finish(policy, run)


def search_n(policy: ExecutionPolicy, rng: Any, n: int,
             value: Any) -> Any:
    """Index of the first run of n consecutive elements equal to value,
    or -1 (std::search_n). n <= 0 matches at 0 (std semantics)."""
    if n <= 0:
        return finish(policy, lambda: 0)
    if is_device_policy(policy, rng):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(a):
            fa = a.reshape(-1)
            if n > fa.shape[0]:
                return jnp.asarray(-1)
            eq = (fa == value)
            # run length ending at i = (i+1) - (1 + last non-match
            # position <= i), the latter as a cummax of reset markers;
            # the first i with runlen >= n starts the match at i-n+1
            sz = fa.shape[0]
            run = jnp.arange(1, sz + 1) - jax.lax.cummax(
                jnp.where(eq, 0, jnp.arange(1, sz + 1)))
            hit = run >= n
            return jnp.where(hit.any(), jnp.argmax(hit) - (n - 1), -1)
        fut = ex.async_execute(kernel, rng)
        if policy.is_task:
            return fut.then(lambda f: int(f.get()))
        return int(fut.get())
    arr = to_numpy_view(rng)

    def run():
        count = 0
        for i, x in enumerate(arr):
            count = count + 1 if x == value else 0
            if count >= n:
                return i - n + 1
        return -1

    return finish(policy, run)


def contains(policy: ExecutionPolicy, rng: Any, value: Any) -> Any:
    """True when value appears in rng (std::ranges::contains)."""
    res = find(policy, rng, value)
    if policy.is_task:
        return res.then(lambda f: f.get() != -1)
    return res != -1


def contains_subrange(policy: ExecutionPolicy, rng: Any,
                      rng2: Any) -> Any:
    """True when rng2 appears as a contiguous subsequence of rng
    (std::ranges::contains_subrange)."""
    res = search(policy, rng, rng2)
    if policy.is_task:
        return res.then(lambda f: f.get() != -1)
    return res != -1


def starts_with(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """True when rng2 is a prefix of rng (std::ranges::starts_with)."""
    if len(rng2) > len(rng):
        return finish(policy, lambda: False)
    return equal(policy, rng[:len(rng2)], rng2)


def ends_with(policy: ExecutionPolicy, rng: Any, rng2: Any) -> Any:
    """True when rng2 is a suffix of rng (std::ranges::ends_with)."""
    if len(rng2) > len(rng):
        return finish(policy, lambda: False)
    if len(rng2) == 0:
        return finish(policy, lambda: True)
    return equal(policy, rng[len(rng) - len(rng2):], rng2)


def reduce_by_key(policy: ExecutionPolicy, keys: Any, values: Any,
                  op: Callable = _op.add) -> Any:
    """Collapse each run of CONSECUTIVE equal keys to one (key, reduced
    value) pair; returns (unique_run_keys, reduced_values)
    (hpx::experimental::reduce_by_key semantics — sort by key first for
    a global group-by).

    Device lowering: one jitted segmented associative scan — the carry
    is a (value, run_start) pair, so XLA's log-depth scan machinery does
    the segmentation (no data-dependent shapes inside jit); the
    data-dependent OUTPUT length compacts at the host boundary exactly
    like unique/copy_if."""
    if is_device_policy(policy, keys, values):
        import jax
        import jax.numpy as jnp
        ex = device_executor(policy)

        def kernel(ks, vs):
            ks, vs = ks.reshape(-1), vs.reshape(-1)
            n = ks.shape[0]
            if n == 0:                         # static shapes
                return jnp.zeros(0, bool), jnp.zeros(0, bool), vs
            start = jnp.concatenate(
                [jnp.ones(1, bool), ks[1:] != ks[:-1]])
            end = jnp.concatenate([start[1:], jnp.ones(1, bool)])
            known = _known_folds().get(op)
            combine = known[1] if known is not None else jax.vmap(op)

            def seg_combine(a, b):
                av, af = a
                bv, bf = b
                return jnp.where(bf, bv, combine(av, bv)), af | bf

            scanned, _ = jax.lax.associative_scan(
                seg_combine, (vs, start))
            return start, end, scanned
        fut = ex.async_execute(kernel, keys, values)

        def done(f):
            import numpy as np
            # hpxlint: disable-next=HPX002 — data-dependent gather: the
            # scan ran on device; unique-key extraction needs host
            # indexing to build the dynamic-shape result
            start, end, scanned = (np.asarray(x) for x in f.get())
            import jax.numpy as jnp
            # hpxlint: disable-next=HPX002 — host gather for the
            # dynamic-shape unique-keys result
            uk = jnp.asarray(np.asarray(keys).reshape(-1)[start])
            rv = jnp.asarray(scanned[end])
            return uk, rv
        return fut.then(done) if policy.is_task else done(fut)

    ks = to_numpy_view(keys).reshape(-1)
    vs = to_numpy_view(values).reshape(-1)

    def run():
        import numpy as np
        if len(ks) == 0:
            return ks.copy(), vs.copy()
        starts = np.flatnonzero(
            np.concatenate([[True], ks[1:] != ks[:-1]]))
        if op is _op.add:
            return ks[starts], np.add.reduceat(vs, starts)
        out = []
        bounds = np.append(starts, len(ks))
        for b, e in zip(bounds[:-1], bounds[1:]):
            acc = vs[b]
            for i in range(b + 1, e):
                acc = op(acc, vs[i])
            out.append(acc)
        return ks[starts], np.array(out)

    return finish(policy, run)
