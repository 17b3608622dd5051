"""Where errors land in eager vs watched device futures (the one place
the core future contract diverges from HPX).

The contract, pinned here and documented in exec/tpu.py + README:

  * trace/compile-time failures (bad shapes, dtype errors) surface as an
    EXCEPTIONAL FUTURE in both modes — async_execute never leaks a raise
    to the caller.
  * post-dispatch (device-side) failures:
      - watched mode: the watcher's block_until_ready observes the
        failure, so the future itself completes exceptionally — .get()
        raises. HPX semantics exactly.
      - eager mode: the future is READY the moment dispatch succeeds
        (it holds the in-flight array) — the failure surfaces at the
        first MATERIALIZATION (np.asarray / block_until_ready /
        target.synchronize), not at .get(). This is the documented
        price of zero-sync dispatch (exec/tpu.py module docstring).

On the CPU test backend, jit execution is synchronous, so real
device-side failures raise AT dispatch (async_execute catches them →
exceptional future — also pinned below). The genuinely-asynchronous
watcher path is driven with a duck-typed device value whose
block_until_ready fails, which is exactly the interface the watcher
consumes; `pytest -m tpu` (test_tpu_kernels.py) repeats the real-chip
variant.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.exec.tpu import TpuExecutor, get_future


class _FailingDeviceValue:
    """Duck-typed dispatched value whose completion fails (the watcher
    calls jax.block_until_ready, which defers to this method)."""

    def block_until_ready(self):
        raise RuntimeError("simulated device-side failure")


class TestTraceErrors:
    @pytest.mark.parametrize("eager", [True, False])
    def test_trace_error_becomes_exceptional_future(self, eager):
        ex = TpuExecutor(eager=eager)

        def bad(x):
            return jnp.dot(x, jnp.ones((7, 7)))      # shape mismatch

        fut = ex.async_execute(bad, jnp.ones((3,)))
        assert fut.has_exception()
        with pytest.raises(TypeError):
            fut.get()

    @pytest.mark.parametrize("eager", [True, False])
    def test_host_raise_in_raw_dispatch(self, eager):
        ex = TpuExecutor(eager=eager)

        def boom():
            raise ValueError("host-side")

        fut = ex.async_execute_raw(boom)
        assert fut.has_exception()
        with pytest.raises(ValueError, match="host-side"):
            fut.get()


class TestWatchedMode:
    def test_device_failure_lands_in_future(self):
        fut = get_future(_FailingDeviceValue())
        with pytest.raises(RuntimeError, match="simulated device-side"):
            fut.get()
        assert fut.has_exception()

    def test_success_value_passes_through(self):
        ex = TpuExecutor(eager=False)
        fut = ex.async_execute(lambda x: x * 2, jnp.arange(4.0))
        np.testing.assert_allclose(np.asarray(fut.get()),
                                   [0.0, 2.0, 4.0, 6.0])

    def test_watched_future_not_poisoned_by_later_use(self):
        """A watched future's value is a COMPLETED array: materializing
        it cannot raise afterward."""
        ex = TpuExecutor(eager=False)
        v = ex.async_execute(lambda x: x + 1, jnp.zeros(3)).get()
        np.testing.assert_allclose(np.asarray(v), 1.0)


class TestEagerMode:
    def test_ready_immediately_with_inflight_value(self):
        ex = TpuExecutor(eager=True)
        fut = ex.async_execute(lambda x: x + 1, jnp.zeros(3))
        assert fut.is_ready()          # ready != computed: see docstring
        np.testing.assert_allclose(np.asarray(fut.get()), 1.0)

    def test_downstream_dataflow_correct(self):
        """Eager futures feed further dispatches; XLA orders the chain."""
        ex = TpuExecutor(eager=True)
        a = ex.async_execute(lambda x: x + 1, jnp.zeros(4)).get()
        b = ex.async_execute(lambda x: x * 3, a).get()
        np.testing.assert_allclose(np.asarray(b), 3.0)
