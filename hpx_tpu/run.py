"""Multi-locality launcher — the hpxrun.py analog.

Reference analog: cmake/templates/hpxrun.py.in (launch N OS processes on
localhost wired via the TCP parcelport — SURVEY.md §4).

    python -m hpx_tpu.run -l 4 [-t 2] script.py [script args...]

Spawns N copies of script.py with HPX_TPU_LOCALITY/LOCALITIES/PARCEL__*
env vars set; locality 0 shares the console port with everyone. Exit
status is the max of the children's (HPX convention: nonzero = failures).
Children run on the CPU jax platform unless `--platform` says
otherwise: a chip belongs to ONE process, so N localities cannot share
it — the real-TPU path is single-process per host, as on actual pods.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
from typing import List


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(script: str, script_args: List[str], localities: int,
           threads: int = 0, jax_platform: str = "cpu",
           timeout: float = 300.0) -> int:
    import secrets as _secrets
    port = _free_port()
    # per-launch shared secret: every locality authenticates its parcel
    # connections (dist/auth.py HMAC handshake) even on loopback, so the
    # pickle deserializer is never reachable unauthenticated and the
    # handshake path is exercised by every multi-process run
    secret = os.environ.get("HPX_TPU_PARCEL__SECRET",
                            _secrets.token_hex(16))
    procs = []
    for loc in range(localities):
        env = dict(os.environ)
        env["HPX_TPU_LOCALITY"] = str(loc)
        env["HPX_TPU_LOCALITIES"] = str(localities)
        env["HPX_TPU_PARCEL__PORT"] = str(port)
        env["HPX_TPU_PARCEL__SECRET"] = secret
        if threads:
            env["HPX_TPU_OS_THREADS"] = str(threads)
        if jax_platform:
            env["JAX_PLATFORMS"] = jax_platform
        procs.append(subprocess.Popen(
            [sys.executable, script, *script_args], env=env))
    rc = 0
    try:
        for p in procs:
            try:
                p.wait(timeout=timeout)
                code = p.returncode or 0
                # signal deaths are negative — report as failure, not 0
                rc = max(rc, code if code > 0 else (1 if code else 0))
            except subprocess.TimeoutExpired:
                rc = max(rc, 1)   # hung locality counts as failure
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                rc = max(rc, 1)
    return rc


def bench_mesh(n_devices: int) -> int:
    """`python -m hpx_tpu.run --bench-mesh N`: BASELINE configs #3/#4/#5
    (partitioned_vector triad, 1M all_reduce, sharded Jacobi) at
    1/2/4/../N devices, in THIS process (a chip belongs to one) on the
    devices jax exposes — too few is an error. A CPU mesh is what the
    caller asks for by name: under JAX_PLATFORMS=cpu the host platform
    is given N virtual devices."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # read at the first device query, which has not happened yet
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if not
                 f.startswith("--xla_force_host_platform_device_count")]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n_devices}"])
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import mesh_scaling
    mesh_scaling.main(n_devices)
    return 0


def _split_argv(argv: List[str]):
    """Launcher flags BEFORE the script path; everything from the
    script on is the script's own (so a script's --timeout is never
    swallowed — hpxrun convention)."""
    takes_value = {"-l", "--localities", "-t", "--threads", "--timeout",
                   "--platform", "--bench-mesh"}
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in ("-h", "--help"):
            return argv[: i + 1], None, []
        if a in takes_value:
            i += 2
        elif a.startswith("-") and "=" in a and \
                a.split("=", 1)[0] in takes_value:
            i += 1
        elif a.startswith("-"):
            # an unknown flag is a launcher usage error, not a script:
            # silently Popen-ing "--localites" would hang N children
            raise SystemExit(
                f"hpx_tpu.run: unknown launcher flag {a!r} "
                "(launcher flags go before the script path; "
                "see --help)")
        else:
            return argv[:i], argv[i], argv[i + 1:]
    # no script: legal only for script-less launcher modes (--bench-mesh)
    return argv, None, []


def main() -> None:
    ap = argparse.ArgumentParser(prog="hpx_tpu.run", allow_abbrev=False)
    ap.add_argument("-l", "--localities", type=int, default=2)
    ap.add_argument("-t", "--threads", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--bench-mesh", type=int, default=0)
    # only PRE-SCRIPT flags are the launcher's: `run.py script.py
    # --bench-mesh 4` passes --bench-mesh through to the script
    launcher_args, script, script_args = _split_argv(sys.argv[1:])
    ns = ap.parse_args(launcher_args)
    if script is None:
        if ns.bench_mesh:           # script-less mode: harness IS the job
            sys.exit(bench_mesh(ns.bench_mesh))
        raise SystemExit("hpx_tpu.run: no script given")
    if ns.bench_mesh:
        raise SystemExit("hpx_tpu.run: --bench-mesh takes no script")
    sys.exit(launch(script, script_args, ns.localities, ns.threads,
                    ns.platform, ns.timeout))


if __name__ == "__main__":
    main()
