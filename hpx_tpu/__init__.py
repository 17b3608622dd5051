"""hpx_tpu — a TPU-native asynchronous many-task framework.

Capability target: biddisco/hpx (see SURVEY.md). Architecture: TPU-first —
futures/dataflow orchestrate XLA program dispatches; parallel algorithms
lower to jit/Pallas kernels; partitioned data is sharded jax.Arrays;
collectives ride XLA collectives (psum/ppermute/all_gather/all_to_all) over
ICI inside shard_map; localities map onto processes/devices with an
AGAS-style name registry.

Public API façade mirroring HPX's umbrella headers (hpx/hpx.hpp):

    import hpx_tpu as hpx
    f = hpx.async_(fn, *args)            # hpx::async
    hpx.dataflow(fn, f1, f2)             # hpx::dataflow
    hpx.when_all(fs); hpx.wait_all(fs)   # combinators
    hpx.transform_reduce(hpx.par.on(hpx.tpu_executor()), ...)
"""

from .core.version import HPX_TPU_VERSION, full_version_as_string  # noqa: F401
from .core.errors import Error, ErrorCode, HpxError  # noqa: F401
from .core.config import Configuration  # noqa: F401
from .core.timing import (  # noqa: F401
    HighResolutionTimer, TimedExecutor, async_after, async_at,
    high_resolution_clock_now, sleep_for, sleep_until,
)
from .core.topology import Topology, get_topology  # noqa: F401
from .runtime.resource import (  # noqa: F401
    Pool, ResourcePartitioner, get_partitioner,
)
from .runtime import batch_environments  # noqa: F401
from .runtime.dataloader import DeviceLoader, device_loader  # noqa: F401

__version__ = full_version_as_string()

# -- futures / async / dataflow (M1) ----------------------------------------
from .futures import (  # noqa: F401
    Future, Promise, PackagedTask, Launch,
    async_, async_many, post, post_many, sync, dataflow, unwrapping,
    make_ready_future, make_exceptional_future, is_future,
    when_all, when_any, when_each, when_some,
    wait_all, wait_any, wait_each, wait_some, split_future,
)
from .futures.task_group import TaskGroup, task_group  # noqa: F401
from . import lcos  # noqa: F401
from .synchronization import (  # noqa: F401
    Barrier, ConditionVariable, CountingSemaphore, Event, Latch, Mutex,
    SharedMutex, SlidingSemaphore, Spinlock, StopSource, StopToken,
    enable_lock_verification,
)

# -- executors & execution policies (M2) ------------------------------------
from .exec import (  # noqa: F401
    BaseExecutor, SequencedExecutor, ParallelExecutor, ThreadPoolExecutor,
    ForkJoinExecutor, TpuExecutor, Target, get_targets, default_target,
    get_future,
    ExecutionPolicy, seq, par, par_unseq, unseq, simd, par_simd,
    static_chunk_size, auto_chunk_size, dynamic_chunk_size,
    guided_chunk_size, num_cores,
)

# tpu_executor: the north-star spelling (BASELINE.json:
# `hpx::execution::par.on(tpu_executor{})`)
tpu_executor = TpuExecutor

# P2300 senders/receivers (hpx::execution::experimental)
from .exec import p2300  # noqa: F401
# the reference exposes this under hpx::execution::experimental
execution_experimental = p2300

# SPMD blocks (host plane + device/shard_map plane)
from .parallel.spmd import (  # noqa: F401
    SpmdBlock, define_spmd_block, device_spmd_block,
)

# pipeline parallelism (GPipe-style microbatched stages)
from .parallel.pipeline import Pipeline, PipelineStage  # noqa: F401

# plugin system (binary filters, coalescing, open registry)
from .dist import plugins  # noqa: F401

# -- parallel algorithms (M3) ------------------------------------------------
from .algo import (  # noqa: F401
    for_each, for_each_n, for_loop, transform, copy, copy_n, copy_if,
    fill, fill_n, generate, generate_n,
    reduce, transform_reduce, count, count_if,
    all_of, any_of, none_of, min_element, max_element, minmax_element,
    equal, mismatch, find, find_if,
    inclusive_scan, exclusive_scan, transform_inclusive_scan,
    transform_exclusive_scan, adjacent_difference, adjacent_find,
    sort, stable_sort, is_sorted, merge, reverse, rotate, unique, partition,
    induction, reduction,
)

# -- distributed runtime: localities, actions, AGAS (M5) ---------------------
from .dist import (  # noqa: F401
    plain_action, direct_action, async_action, post_action,
    resilient_action,
    init, finalize, get_runtime,
    find_here, find_all_localities, find_remote_localities,
    find_root_locality, get_num_localities,
)
from .dist import agas  # noqa: F401

# -- components: distributed objects (hpx::components) -----------------------
from .dist.components import (  # noqa: F401
    Client, Component, IdType,
    new_, new_sync, migrate, async_colocated,
    register_component_type, register_with_basename, find_from_basename,
)

# -- partitioned data + segmented algorithms (M6) ----------------------------
from .containers import (  # noqa: F401
    PartitionedVector, PartitionedVectorView, Segment, UnorderedMap,
)
from .dist.distribution_policies import (  # noqa: F401
    Binpacked, Colocated, ContainerLayout, PlacementPolicy, binpacked,
    colocated, container_layout, default_layout, target_layout,
)

# the HPX spelling
partitioned_vector = PartitionedVector

# -- collectives + channels (M7) ---------------------------------------------
from . import collectives  # noqa: F401
from .collectives import (  # noqa: F401
    Communicator, create_communicator, create_channel_communicator,
    ChannelCommunicator, DistributedChannel, DistributedLatch,
)

# -- block executor + 2-D halo substrate (M8) --------------------------------
from .exec.block import BlockExecutor, place_blocks  # noqa: F401

# -- services (M9) ------------------------------------------------------------
from .svc import performance_counters  # noqa: F401
from .svc.performance_counters import (  # noqa: F401
    CounterValue, GaugeCounter, CallbackCounter, ElapsedTimeCounter,
    AverageCounter, counter_name, parse_counter_name, register_counter,
    unregister_counter, discover_counters, query_counter, query_counters,
    print_counters, start_counter_printing,
)
from .svc.checkpoint import (  # noqa: F401
    Checkpoint, save_checkpoint, save_checkpoint_sync, restore_checkpoint,
    save_checkpoint_to_file, restore_checkpoint_from_file,
    save_sharded_state, save_sharded_state_to_file,
    restore_sharded_state, restore_sharded_state_from_file,
)
from .svc.resiliency import (  # noqa: F401
    AbortReplayException, AbortReplicateException, ReplayValidationError,
    ReplicateVotingError, async_replay, async_replay_validate,
    async_replicate, async_replicate_validate, async_replicate_vote,
    async_replay_distributed, majority_vote, ReplayExecutor,
    ReplicateExecutor,
)
from .svc.logging import get_logger, set_log_level  # noqa: F401
from .svc.iostreams import cout, cerr  # noqa: F401
from .svc import profiling  # noqa: F401
from .svc import tracing  # noqa: F401
from .svc.tracing import (  # noqa: F401
    Tracer, active_tracer, start_tracing, stop_tracing,
)
