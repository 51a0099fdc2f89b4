"""Multi-host trial mesh: ``jax.distributed`` initialization + a local
multi-process launcher (DESIGN.md §10).

The streaming engine's cross-device reduction (``StreamSummary.axis_merge``
— psum counts/histograms, pmax maxima) is already a valid cross-*host*
reduction: sketch merge is integer-exact, associative and commutative.  All
multi-host support needs is (a) every process agreeing on the global device
grid and (b) per-device work keyed by the *global* device index, so a
2-process x 4-device run and a 1-process x 8-device run are the same
program.  This module supplies (a); ``montecarlo/streaming.py`` derives (b)
from ``lax.axis_index`` over a ``trial_mesh()`` built on ``jax.devices()``
(the global device list — ``process_index * local_count + local_index`` in
enumeration order).

Three entry points:

``initialize()``      read coordinator/process-count/process-id from
                      arguments or the ``REPRO_*`` environment (set by
                      ``launch_local`` and by cluster launch scripts) and
                      bring up ``jax.distributed``.  Idempotent; a no-op
                      for single-process runs, so callers can invoke it
                      unconditionally before touching the backend.
``launch_local()``    the CI-exercisable local mode: N processes x
                      ``--xla_force_host_platform_device_count=D`` forced
                      host devices each (the forced-device trick the
                      8-device CI job already uses), coordinated over a
                      free localhost port.  CPU cross-process collectives
                      run on gloo, which jax only honors when configured
                      *in-process before backend init* — ``initialize()``
                      does that, which is why workers must call it first.
``main()``            CLI: ``python -m repro.parallel.distributed launch
                      --processes 2 --devices-per-process 4 -- <cmd...>``
                      re-runs any command as a cooperating process grid;
                      the ``stream`` subcommand is the fixed-workload
                      worker the multihost acceptance test and the
                      ``stream.multihost`` benchmark row compare layouts
                      with.
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

# Error text the CPU backend emits when cross-process collectives are not
# available (no gloo, or a jax too old to route them) — launch/test helpers
# match on it to distinguish "platform can't" from "code broke".
UNSUPPORTED_MARKERS = (
    "Multiprocess computations aren't implemented",
    "cpu_collectives_implementation",
)

_INITIALIZED = False


@dataclass(frozen=True)
class DistInfo:
    """The process-grid coordinates a multi-host run is keyed by."""

    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_multiprocess(self) -> bool:
        return self.process_count > 1


def _backend_already_up() -> bool:
    """True when an XLA backend client exists (best effort: a miss only
    degrades the error message, never correctness)."""
    try:
        from jax._src import xla_bridge
        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:
        return False


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> DistInfo:
    """Bring up ``jax.distributed`` from arguments or the ``REPRO_*`` env.

    Single-process (no coordinator configured, or one process) is a no-op,
    so multihost-capable entry points (``benchmarks.quorum_sweep --shard``,
    the ``stream`` worker below) call this unconditionally as their first
    jax-touching statement.  Re-initialization is a no-op too.

    On the CPU backend, cross-process collectives require the gloo
    implementation, selected via ``jax.config`` **before** the backend
    client exists — calling this after ``jax.devices()``/any computation
    raises instead of silently producing a grid that cannot psum.
    """
    global _INITIALIZED
    import jax

    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None:
        num_processes = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
    if process_id is None:
        process_id = int(os.environ.get(ENV_PROCESS_ID, "0"))

    if coordinator is None or num_processes <= 1:
        return info()
    if _INITIALIZED:
        return info()
    if _backend_already_up():
        raise RuntimeError(
            "repro.parallel.distributed.initialize() must run before the "
            "jax backend is first used (it selects the gloo CPU collectives "
            "implementation, which only takes effect at backend creation); "
            "call it at process start, before any jax computation")
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass          # non-CPU backends / older jax: collectives are native
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True
    return info()


def info() -> DistInfo:
    """The current process-grid coordinates (initializes the backend)."""
    import jax
    return DistInfo(process_index=jax.process_index(),
                    process_count=jax.process_count(),
                    local_device_count=len(jax.local_devices()),
                    global_device_count=len(jax.devices()))


# ---------------------------------------------------------------------------
# Local multi-process launcher (the CI-exercisable mode).
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ``_free_port`` closes the probe socket before the coordinator process
# binds the port, so another process can steal it in between; the
# coordinator then dies with EADDRINUSE.  ``launch_local`` retries the
# whole bring-up on a fresh port when a failing process's output matches
# these markers (gRPC and raw-errno spellings).
EADDRINUSE_MARKERS = ("EADDRINUSE", "address already in use",
                      "Address already in use", "Failed to listen")
LAUNCH_PORT_RETRIES = 3


def _is_addr_in_use(text: str) -> bool:
    return any(m in text for m in EADDRINUSE_MARKERS)


def _src_root() -> str:
    # .../src/repro/parallel/distributed.py -> .../src
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def launch_local(num_processes: int, devices_per_process: int,
                 argv: Sequence[str], *, env: Optional[Dict[str, str]] = None,
                 timeout_s: float = 900.0,
                 port_retries: int = LAUNCH_PORT_RETRIES) -> List[str]:
    """Run ``argv`` as ``num_processes`` cooperating local processes, each
    seeing ``devices_per_process`` forced host devices.

    Every process gets ``REPRO_COORDINATOR`` (a free localhost port),
    ``REPRO_NUM_PROCESSES`` and ``REPRO_PROCESS_ID``, ``JAX_PLATFORMS=cpu``
    (children never take an accelerator), plus ``XLA_FLAGS``
    rewritten to ``--xla_force_host_platform_device_count=D`` — the command
    itself must call ``initialize()`` before using jax.  Returns the
    captured stdout+stderr of each process (index-ordered); raises
    ``RuntimeError`` with the failing process's output on any non-zero
    exit, and ``NotImplementedError`` when the failure is the platform
    lacking multi-process CPU collectives (so callers can skip, not fail).

    The free-port probe closes its socket before the coordinator binds,
    so the port can be stolen in between; a failure whose output matches
    ``EADDRINUSE_MARKERS`` retries the whole bring-up on a fresh port, up
    to ``port_retries`` times, instead of failing the launch.
    """
    if num_processes < 1 or devices_per_process < 1:
        raise ValueError(f"need at least 1 process and 1 device, got "
                         f"{num_processes} x {devices_per_process}")
    for attempt in range(port_retries + 1):
        try:
            return _launch_once(num_processes, devices_per_process, argv,
                                env=env, timeout_s=timeout_s)
        except NotImplementedError:
            raise                           # platform gap, not a port race
        except RuntimeError as e:
            if attempt < port_retries and _is_addr_in_use(str(e)):
                continue                    # lost the race: fresh port
            raise
    raise AssertionError("unreachable")     # loop always returns or raises


def _launch_once(num_processes: int, devices_per_process: int,
                 argv: Sequence[str], *, env: Optional[Dict[str, str]],
                 timeout_s: float) -> List[str]:
    port = _free_port()
    base = dict(os.environ)
    base.update(env or {})
    xla = [f for f in base.get("XLA_FLAGS", "").split()
           if not f.startswith("--xla_force_host_platform_device_count")]
    xla.append(f"--xla_force_host_platform_device_count={devices_per_process}")
    base["XLA_FLAGS"] = " ".join(xla)
    # a forced-host-device CPU layout by design: children never take an
    # accelerator (the parent may hold it, and one chip serves one process)
    base["JAX_PLATFORMS"] = "cpu"
    base["PYTHONPATH"] = os.pathsep.join(
        p for p in (_src_root(), base.get("PYTHONPATH", "")) if p)

    procs = []
    for i in range(num_processes):
        e = dict(base)
        e[ENV_COORDINATOR] = f"localhost:{port}"
        e[ENV_NUM_PROCESSES] = str(num_processes)
        e[ENV_PROCESS_ID] = str(i)
        procs.append(subprocess.Popen(
            list(argv), env=e, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))

    deadline = time.monotonic() + timeout_s
    outs: List[Optional[str]] = [None] * num_processes
    try:
        for i, p in enumerate(procs):
            left = deadline - time.monotonic()
            outs[i], _ = p.communicate(timeout=max(1.0, left))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for i, p in enumerate(procs):
            if outs[i] is None:
                outs[i] = (p.communicate()[0] or "")
        raise RuntimeError(
            f"multi-process launch timed out after {timeout_s:.0f}s; "
            f"process outputs:\n" + "\n---\n".join(o or "" for o in outs))
    failed = [i for i, p in enumerate(procs) if p.returncode != 0]
    if failed:
        blob = "\n---\n".join(f"[proc {i} rc={procs[i].returncode}]\n"
                              f"{outs[i]}" for i in failed)
        if any(m in (outs[i] or "") for i in failed
               for m in UNSUPPORTED_MARKERS):
            raise NotImplementedError(
                f"this platform lacks multi-process CPU collectives "
                f"(gloo): \n{blob}")
        raise RuntimeError(f"multi-process launch failed:\n{blob}")
    return [o or "" for o in outs]


# ---------------------------------------------------------------------------
# Fixed-workload stream worker: the layout-comparison probe.
# ---------------------------------------------------------------------------

def _stream_worker(out_path: str, *, trials: int, chunk: int, seed: int,
                   precision: float) -> None:
    """Run the fixed acceptance workload (paper-headline + Fast Paxos at
    n=11, 2-way race at Δ=0.2 ms) through ``race_stream`` on the global
    trial mesh and — from process 0 — dump the merged ``StreamSummary``
    plus grid metadata to ``out_path`` (npz).

    The workload is pinned so two *layouts* of the same global device count
    (2x4 vs 1x8) are comparable bit-for-bit: same global key, same chunking,
    same per-global-device fold-in keys."""
    dinfo = initialize()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.quorum import QuorumSpec
    from repro.montecarlo import build_mask_table, streaming
    from repro.parallel import sharding as psharding

    table = build_mask_table([QuorumSpec.paper_headline(11),
                              QuorumSpec.fast_paxos(11)])
    offsets = jnp.array([0.0, 0.2], jnp.float32)
    mesh = psharding.trial_mesh()        # global devices, every process
    t0 = time.perf_counter()
    state = streaming.race_stream(jax.random.PRNGKey(seed), table, offsets,
                                  n=11, k_proposers=2, trials=trials,
                                  chunk=chunk, precision=precision,
                                  shard=mesh)
    jax.block_until_ready(state.hist)
    wall = time.perf_counter() - t0
    # hop off the global mesh before querying quantiles: np leaves make the
    # sketch math process-local (identical everywhere — state is replicated)
    host = jax.tree_util.tree_map(lambda x: np.asarray(x), state)
    if dinfo.process_index != 0:
        return
    qs = np.asarray(host.quantile(jnp.array([0.5, 0.999, 0.9999])))
    np.savez(out_path,
             n_trials=np.asarray(host.n_trials),
             n_fast=np.asarray(host.n_fast),
             n_recovery=np.asarray(host.n_recovery),
             n_undecided=np.asarray(host.n_undecided),
             hist=np.asarray(host.hist),
             max_ms=np.asarray(host.max_ms),
             mean_ms=np.asarray(host.mean_ms),
             p50_ms=qs[0], p999_ms=qs[1], p9999_ms=qs[2],
             wall_s=np.float64(wall),
             process_count=np.int64(dinfo.process_count),
             global_devices=np.int64(dinfo.global_device_count))


def run_stream_layout(num_processes: int, devices_per_process: int,
                      out_path: str, *, trials: int = 50_011,
                      chunk: int = 2_048, seed: int = 0,
                      precision: float = 0.01,
                      timeout_s: float = 600.0) -> Dict[str, "object"]:
    """Launch the fixed stream worker on an (N processes x D devices) local
    grid and return process 0's merged summary as an {name: ndarray} dict.
    The acceptance contract (tests/test_multihost.py, the
    ``stream.multihost`` benchmark row): any two layouts of the same
    N*D are bit-identical in counts and histogram."""
    import numpy as np
    launch_local(
        num_processes, devices_per_process,
        [sys.executable, "-m", "repro.parallel.distributed", "stream",
         "--out", out_path, "--trials", str(trials), "--chunk", str(chunk),
         "--seed", str(seed), "--precision", str(precision)],
        timeout_s=timeout_s)
    with np.load(out_path) as z:
        return {k: z[k] for k in z.files}


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.parallel.distributed",
        description="multi-host trial-mesh launcher / worker")
    sub = ap.add_subparsers(dest="cmd", required=True)

    lp = sub.add_parser("launch", help="run a command as N local processes "
                                       "x D forced host devices each")
    lp.add_argument("--processes", type=int, default=2)
    lp.add_argument("--devices-per-process", type=int, default=4)
    lp.add_argument("--timeout", type=float, default=900.0)
    lp.add_argument("argv", nargs=argparse.REMAINDER,
                    help="command to run (prefix with --)")

    sp = sub.add_parser("stream", help="fixed-workload race_stream worker "
                                       "(called by run_stream_layout)")
    sp.add_argument("--out", required=True)
    sp.add_argument("--trials", type=int, default=50_011)
    sp.add_argument("--chunk", type=int, default=2_048)
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--precision", type=float, default=0.01)

    st = sub.add_parser("selftest", help="probe: psum of global device "
                                         "indices across the grid")
    st.add_argument("--quiet", action="store_true")

    args = ap.parse_args(argv)
    if args.cmd == "launch":
        cmd = list(args.argv)
        if cmd and cmd[0] == "--":
            cmd = cmd[1:]
        if not cmd:
            ap.error("launch needs a command after --")
        outs = launch_local(args.processes, args.devices_per_process, cmd,
                            timeout_s=args.timeout)
        for i, o in enumerate(outs):
            sys.stdout.write(f"--- proc {i} ---\n{o}")
        return 0
    if args.cmd == "stream":
        _stream_worker(args.out, trials=args.trials, chunk=args.chunk,
                       seed=args.seed, precision=args.precision)
        return 0
    # selftest
    dinfo = initialize()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as psharding

    mesh = psharding.trial_mesh()
    ndev = mesh.shape[psharding.TRIAL_AXIS]
    f = jax.shard_map(
        lambda x: jax.lax.psum(
            jnp.asarray(jax.lax.axis_index(psharding.TRIAL_AXIS), jnp.int32),
            psharding.TRIAL_AXIS),
        mesh=mesh, in_specs=P(), out_specs=P())
    got = int(jax.jit(f)(jnp.int32(0)))
    want = ndev * (ndev - 1) // 2
    ok = got == want
    if not args.quiet:
        print(f"proc {dinfo.process_index}/{dinfo.process_count}: "
              f"{dinfo.global_device_count} global devices, "
              f"psum(axis_index) = {got} (want {want}) "
              f"{'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
