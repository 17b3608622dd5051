"""Multi-host wiring (parallel/multihost.py): batch-env resolution and
global mesh construction. jax.distributed itself is exercised at
num_processes=1 (a real initialize over localhost)."""

import numpy as np
import pytest

import jax

from hpx_tpu.parallel import multihost


def test_resolve_single_host_is_none():
    assert multihost.resolve(environ={}) is None


def test_resolve_from_slurm_env():
    env = {"SLURM_JOB_ID": "1", "SLURM_NTASKS": "4", "SLURM_PROCID": "2",
           "SLURM_JOB_NODELIST": "node[1-4]"}
    coord, n, pid = multihost.resolve(environ=env)
    assert n == 4 and pid == 2
    assert coord.startswith("node1:")


def test_resolve_bare_allocation_is_none():
    # ntasks known but no per-task rank: salloc without srun
    env = {"SLURM_JOB_ID": "1", "SLURM_NTASKS": "4"}
    assert multihost.resolve(environ=env) is None


def test_resolve_explicit_env_wins():
    env = {"JAX_COORDINATOR_ADDRESS": "10.0.0.1:1234",
           "JAX_NUM_PROCESSES": "2", "JAX_PROCESS_ID": "1",
           "SLURM_JOB_ID": "1", "SLURM_NTASKS": "8",
           "SLURM_PROCID": "7"}
    assert multihost.resolve(environ=env) == ("10.0.0.1:1234", 2, 1)


def test_resolve_openmpi():
    env = {"OMPI_COMM_WORLD_SIZE": "2", "OMPI_COMM_WORLD_RANK": "1"}
    coord, n, pid = multihost.resolve(environ=env)
    assert (n, pid) == (2, 1) and coord is None


def test_global_mesh_shapes(devices):
    m = multihost.global_mesh(devices=devices)
    assert m.shape["dp"] == 8
    m2 = multihost.global_mesh((2, None), ("dp", "tp"), devices=devices)
    assert dict(m2.shape) == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError, match="divisible"):
        multihost.global_mesh((3, None), ("a", "b"), devices=devices)
    with pytest.raises(ValueError, match="!="):
        multihost.global_mesh((2, 2), ("a", "b"), devices=devices)


def test_init_single_process_real():
    """A REAL jax.distributed.initialize at num_processes=1 over
    localhost — the same call a pod makes, world size 1. Runs in a
    FRESH interpreter: initialize must precede any backend use, and
    this pytest process already created devices."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import jax\n"
        "from hpx_tpu.parallel import multihost\n"
        "ok = multihost.init(coordinator_address='127.0.0.1:12357',\n"
        "                    num_processes=1, process_id=0)\n"
        "assert ok and multihost.is_initialized()\n"
        "assert jax.process_count() == 1\n"
        "assert len(jax.devices()) >= 1\n"
        "assert multihost.init() is True   # idempotent\n"
        "print('MULTIHOST_OK')\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert p.returncode == 0 and "MULTIHOST_OK" in p.stdout, \
        p.stdout + p.stderr


def test_resolve_tpu_pod_without_hostnames():
    """A pod worker id with no hostname list must still resolve (jax
    self-configures from the metadata server) — returning None here
    would silently train on one host of the pod."""
    env = {"TPU_WORKER_ID": "3"}
    assert multihost.resolve(environ=env) == (None, None, 3)


def test_resolve_partial_jax_env_merges_with_scheduler():
    env = {"JAX_COORDINATOR_ADDRESS": "10.0.0.9:9999",
           "SLURM_JOB_ID": "1", "SLURM_NTASKS": "4",
           "SLURM_PROCID": "2"}
    assert multihost.resolve(environ=env) == ("10.0.0.9:9999", 4, 2)


def test_global_mesh_uses_make_mesh_cache(devices):
    from hpx_tpu.parallel.mesh import make_mesh
    # all-device construction shares the cached Mesh object
    a = multihost.global_mesh((2, 4), ("dp", "pp"))
    b = make_mesh((2, 4), ("dp", "pp"))
    assert a is b
