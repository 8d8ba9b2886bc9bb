"""Multi-host distributed initialization.

Replaces the reference's three ad-hoc coordination mechanisms with one:
- driver rendezvous ServerSocket + allgather of worker host:port
  (ref: src/lightgbm/.../LightGBMUtils.scala:66-105),
- MPI-over-ssh launch with scp'd hostfiles
  (ref: src/cntk-train/.../CommandBuilders.scala:108-267),
- executor discovery via Spark BlockManager
  (ref: LightGBMUtils.scala:139-158).

TPU-native: ``jax.distributed.initialize`` gives every host the same view
of the global device set; collectives ride ICI/DCN via XLA. The
"distributed-without-a-cluster" test mode fakes a pod in one process with
``jax.config.update("jax_num_cpu_devices", n)`` before first backend use
(ref pattern: SURVEY.md §4; see tests/conftest.py).

The rendezvous recipe (docs/multihost_fabric.md): every process calls
``initialize()`` with the same coordinator address — process 0 binds it —
either via arguments or the environment::

    JAX_COORDINATOR_ADDRESS=10.0.0.1:9377 \\
    JAX_NUM_PROCESSES=4 JAX_PROCESS_ID=<rank> python train.py

CPU hosts additionally need a cross-process collectives backend; on
CPU-only groups ``initialize()`` selects gloo before the first backend
use (``jax_cpu_collectives_implementation``), which is what lets the
2-process drills in tests/ run the real allgather/psum wire on one box.
The rendezvous is BOUNDED: a member that never shows up (crashed before
connecting, wrong address) surfaces as a clean ``ProcessGroupError``
after ``timeout_s`` instead of a silent hang — the LightGBM
socket-rendezvous timeout discipline (ref: LightGBMUtils.scala:110-118).
"""

from __future__ import annotations

import os
import socket
import time
from dataclasses import dataclass
from typing import Optional

import jax

_initialized = False

# bounded rendezvous: how long initialize() waits for the full group to
# assemble before raising ProcessGroupError (env override:
# MMLSPARK_TPU_RENDEZVOUS_TIMEOUT_S). jax's own default is 300 s — far
# too long for a fleet health loop to notice a dead member.
DEFAULT_RENDEZVOUS_TIMEOUT_S = 60.0


class ProcessGroupError(RuntimeError):
    """Rendezvous failed: a group member is missing/dead, the
    coordinator is unreachable, or the group timed out assembling."""


@dataclass
class HostInfo:
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0


def _barrier_address(coordinator_address: str) -> tuple:
    """The pre-rendezvous barrier's address: the coordinator host, one
    port above the jax coordinator port (env override:
    MMLSPARK_TPU_BARRIER_PORT)."""
    host, _, port = coordinator_address.rpartition(":")
    bport = int(os.environ.get("MMLSPARK_TPU_BARRIER_PORT",
                               int(port) + 1))
    return host or "127.0.0.1", bport


def _rendezvous_barrier(coordinator_address: str, nproc: int, pid: int,
                        timeout_s: float) -> None:
    """Liveness barrier BEFORE ``jax.distributed.initialize``: the
    coordinator binds a plain ServerSocket and every worker checks in
    with its process id; only when all ``nproc`` members are accounted
    for does anyone enter the jax rendezvous (the LightGBM driver
    ServerSocket + worker-allgather pattern,
    ref: LightGBMUtils.scala:66-105).

    Why: jax's own coordination service turns a rendezvous deadline
    into a FATAL abort (``client.h:80 Terminating process``) — a dead
    group member would kill every survivor instead of surfacing an
    error. This barrier runs in pure Python, so a missing member
    raises a clean, catchable ``ProcessGroupError`` within
    ``timeout_s`` and the survivors keep running (a GBDT fit fails with
    an exception, not a core dump)."""
    host, port = _barrier_address(coordinator_address)
    deadline = time.monotonic() + timeout_s
    if pid == 0:
        try:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind((host, port))
            srv.listen(max(1, nproc - 1))
        except OSError as e:
            raise ProcessGroupError(
                f"coordinator could not bind the rendezvous barrier at "
                f"{host}:{port}: {e}. Set MMLSPARK_TPU_BARRIER_PORT to "
                f"a free port (default: coordinator port + 1).") from e
        conns, seen = [], set()
        try:
            while len(seen) < nproc - 1:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    missing = sorted(set(range(1, nproc)) - seen)
                    raise ProcessGroupError(
                        f"rendezvous barrier timed out after "
                        f"{timeout_s:.0f}s: member(s) {missing} of "
                        f"{nproc} never checked in at {host}:{port} — "
                        f"likely dead or unlaunched.")
                srv.settimeout(remain)
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    continue
                conn.settimeout(max(1.0, deadline - time.monotonic()))
                try:
                    hello = conn.recv(64).decode().strip()
                    seen.add(int(hello))
                    conns.append(conn)
                except (ValueError, OSError):
                    conn.close()
            for conn in conns:
                try:
                    conn.sendall(b"GO\n")
                except OSError:
                    pass
        finally:
            for conn in conns:
                conn.close()
            srv.close()
    else:
        last_err: Optional[Exception] = None
        while True:
            remain = deadline - time.monotonic()
            if remain <= 0:
                raise ProcessGroupError(
                    f"rendezvous barrier timed out after "
                    f"{timeout_s:.0f}s: process {pid} could not reach "
                    f"the coordinator barrier at {host}:{port} "
                    f"({last_err}) — the coordinator is likely dead.")
            try:
                with socket.create_connection(
                        (host, port), timeout=min(remain, 5.0)) as conn:
                    conn.sendall(f"{pid}\n".encode())
                    conn.settimeout(max(1.0,
                                        deadline - time.monotonic()))
                    if conn.recv(8).strip() == b"GO":
                        return
                    raise OSError("barrier closed without GO")
            except OSError as e:
                last_err = e
                time.sleep(0.1)


def _configure_cpu_collectives(impl: str = "gloo") -> None:
    """Select the CPU cross-process collectives backend BEFORE the first
    backend use. Without this, a CPU-only process group rendezvouses
    fine and then every collective (process_allgather, psum over the
    global mesh) fails — the backend default cannot talk across
    processes. No-op on jax builds without the option or once the
    backend is already configured."""
    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except Exception:  # noqa: BLE001 — option absent on this jax build
        pass


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               timeout_s: Optional[float] = None,
               cpu_collectives: Optional[str] = "auto",
               barrier: bool = True) -> HostInfo:
    """Rendezvous this process into a ``jax.distributed`` group.

    Arguments fall back to the environment (JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID), so a launcher can export the
    recipe once and every entry point picks it up. Safe to call in
    single-host mode — becomes a no-op returning the local view.

    ``timeout_s`` bounds the rendezvous (default
    ``DEFAULT_RENDEZVOUS_TIMEOUT_S``, env override
    MMLSPARK_TPU_RENDEZVOUS_TIMEOUT_S): a missing member raises
    ``ProcessGroupError`` instead of hanging the fleet.
    ``cpu_collectives="auto"`` installs gloo on CPU-only groups (any
    explicit string forces that implementation; ``None`` leaves the jax
    default untouched). ``barrier`` runs the Python liveness barrier
    first (see ``_rendezvous_barrier``) so a dead member raises instead
    of tripping jax's fatal-abort deadline."""
    global _initialized
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address and not _initialized:
        nproc = (num_processes if num_processes is not None
                 else int(os.environ.get("JAX_NUM_PROCESSES", "1")))
        pid = (process_id if process_id is not None
               else int(os.environ.get("JAX_PROCESS_ID", "0")))
        if timeout_s is None:
            timeout_s = float(os.environ.get(
                "MMLSPARK_TPU_RENDEZVOUS_TIMEOUT_S",
                DEFAULT_RENDEZVOUS_TIMEOUT_S))
        if cpu_collectives == "auto":
            plats = os.environ.get("JAX_PLATFORMS", "")
            if "cpu" in plats or not plats:
                _configure_cpu_collectives("gloo")
        elif cpu_collectives:
            _configure_cpu_collectives(cpu_collectives)
        if barrier and nproc > 1:
            _rendezvous_barrier(coordinator_address, nproc, pid,
                                timeout_s)
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=nproc,
                process_id=pid,
                initialization_timeout=int(max(1, timeout_s)),
            )
        except Exception as e:  # noqa: BLE001 — surface actionably
            raise ProcessGroupError(
                f"jax.distributed rendezvous failed for process {pid}/"
                f"{nproc} at coordinator {coordinator_address!r} within "
                f"{timeout_s:.0f}s: {type(e).__name__}: {e}. A group "
                f"member is likely dead or unreachable — every process "
                f"must call initialize() with the same coordinator "
                f"address and a distinct process_id.") from e
        _initialized = True
    return host_info()


def host_info() -> HostInfo:
    return HostInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


def in_process_group() -> bool:
    """True when this process rendezvoused into a multi-process group —
    the honest gate for multi-machine floors (``process_count >= 2``),
    the way the fleet-scaling floors gate on usable cores."""
    return jax.process_count() > 1


def require_process_group(min_processes: int = 2) -> HostInfo:
    """Assert this process runs inside a group of at least
    ``min_processes`` — multi-host code paths (fleet-wide floors,
    cross-host GBDT claims) call this instead of silently measuring a
    single-process run and labeling it multi-host."""
    info = host_info()
    if info.process_count < min_processes:
        raise ProcessGroupError(
            f"requires a jax.distributed group of >= {min_processes} "
            f"processes; this process sees process_count="
            f"{info.process_count}. Launch via initialize() with "
            f"JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID "
            f"set (docs/multihost_fabric.md).")
    return info


def shutdown() -> None:
    """Leave the group (test teardown); no-op outside one."""
    global _initialized
    if _initialized:
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — already torn down
            pass
        _initialized = False


def shard_table_for_host(table, info: Optional[HostInfo] = None):
    """Each host keeps only its row range — the host-partitioned feeding
    that replaces HDFS staging + scp (ref: CNTKLearner.scala:123-140)."""
    info = info or host_info()
    if info.process_count <= 1:
        return table
    return table.shards(info.process_count)[info.process_index]
