"""SPMD blocks — hpx::parallel::spmd_block analog, two planes.

Reference analog: hpx's `define_spmd_block` (quickstart/examples and
`partitioned_vector_view` SPMD access, SURVEY.md §2.6, §5.7): run the
same function as N "images", each knowing its rank, with `sync_all`
barriers between phases.

Two TPU-native planes:

  * HOST plane (`define_spmd_block`): images = host tasks (one per
    image on this locality, or one per locality when distributed=True).
    Good for orchestration logic. Barriers are futures-based
    (local AndGate) or the distributed barrier.

  * DEVICE plane (`device_spmd_block`): images = mesh devices; the
    block body runs inside `shard_map`, `block.sync_all()` is free
    (XLA's SPMD execution is bulk-synchronous per program), and
    `block.image_id()` is the mesh coordinate. This is the idiomatic
    home of SPMD on TPU: the reference's spmd_block pattern collapses
    into a sharded program.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Sequence

from ..futures.combinators import when_all
from ..futures.future import Future
from ..futures.async_ import async_

__all__ = ["SpmdBlock", "define_spmd_block", "device_spmd_block"]


class _LocalBarrier:
    """Reusable generation barrier for N host images."""

    def __init__(self, n: int) -> None:
        self._n = n
        self._count = 0
        self._gen = 0
        self._cv = threading.Condition()

    def arrive_and_wait(self, timeout: float = 60.0) -> None:
        with self._cv:
            gen = self._gen
            self._count += 1
            if self._count == self._n:
                self._count = 0
                self._gen += 1
                self._cv.notify_all()
                return
            if not self._cv.wait_for(lambda: self._gen != gen, timeout):
                from ..core.errors import Error, HpxError
                raise HpxError(Error.deadlock,
                               "spmd_block sync_all timed out")


class SpmdBlock:
    """Handle passed to each image (reference: hpx::spmd_block)."""

    def __init__(self, name: str, image_id: int, num_images: int,
                 barrier: Any) -> None:
        self._name = name
        self._image = image_id
        self._num = num_images
        self._barrier = barrier

    def get_block_name(self) -> str:
        return self._name

    def this_image(self) -> int:
        return self._image

    def get_num_images(self) -> int:
        return self._num

    # HPX spelling
    image_id = this_image

    def sync_all(self) -> None:
        self._barrier()


def define_spmd_block(name: str, num_images: int,
                      fn: Callable[..., Any], *args: Any,
                      distributed: bool = False) -> Future:
    """Run fn(block, *args) as num_images SPMD images.

    distributed=False: images are host tasks on THIS locality (the
    reference's single-locality spmd_block over its thread pool).
    Returns future<list> of the images' return values.

    distributed=True: call this ON EVERY participating locality (SPMD
    style, like the reference's multi-locality blocks); this locality
    runs image `find_here()`, barriers ride the distributed runtime.
    Returns future<value> of the local image.
    """
    if distributed:
        from ..dist.runtime import find_here, get_num_localities, get_runtime
        nloc = get_num_localities()
        if num_images != nloc:
            from ..core.errors import Error, HpxError
            raise HpxError(Error.bad_parameter,
                           f"distributed spmd_block needs one image per "
                           f"locality ({nloc}), got {num_images}")
        rt = get_runtime()
        gen_box = [0]

        def dist_barrier() -> None:
            gen_box[0] += 1
            rt.barrier(f"spmd/{name}/{gen_box[0]}")

        block = SpmdBlock(name, find_here(), num_images, dist_barrier)
        return async_(fn, block, *args)

    # dedicated pool, one thread per image: images block in sync_all, so
    # running them on the shared bounded pool would deadlock whenever
    # num_images exceeds the pool width (no stackful coroutines to
    # suspend, unlike the reference)
    from ..exec.executors import ThreadPoolExecutor
    ex = ThreadPoolExecutor(num_images)
    bar = _LocalBarrier(num_images)
    futs: List[Future] = []
    for i in range(num_images):
        block = SpmdBlock(name, i, num_images, bar.arrive_and_wait)
        futs.append(ex.async_execute(fn, block, *args))

    def collect(f: Future) -> List[Any]:
        try:
            return [x.get() for x in f.get()]
        finally:
            # this continuation runs ON one of ex's own workers: a pool
            # cannot join itself — hand the teardown to the default pool
            from ..runtime.threadpool import default_pool
            default_pool().submit(ex.shutdown)

    return when_all(futs).then(collect)


def device_spmd_block(fn: Callable[..., Any], mesh: Any = None,
                      axis: str = "x",
                      in_specs: Any = None, out_specs: Any = None):
    """Lower an SPMD block onto the device mesh.

    fn(block, *arrays) runs per-shard inside shard_map; block.this_image()
    is a traced mesh coordinate (`lax.axis_index`), block.get_num_images()
    the axis size, and sync_all() a no-op (XLA programs are already
    bulk-synchronous across shards — the reference's sync_all maps to
    "end of fused region").  Returns the jitted callable.

        step = device_spmd_block(body, mesh, "x", in_specs=(P("x"),),
                                 out_specs=P("x"))
        out = step(sharded_array)
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from .mesh import default_mesh
        mesh = default_mesh()
    if in_specs is None:
        in_specs = P(axis)
    if out_specs is None:
        out_specs = P(axis)

    def body(*arrays: Any):
        idx = jax.lax.axis_index(axis)
        n = mesh.shape[axis]
        block = SpmdBlock(f"device/{axis}", idx, n, lambda: None)
        return fn(block, *arrays)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))
