"""Process orchestrator: run cluster replicas as local subprocesses.

The analogue of the reference's `mz-orchestrator-process`
(src/orchestrator-process): the dev/test stand-in for the kubernetes
orchestrator, satisfying the same ensure_service shape
(src/orchestrator/src/lib.rs:48-68) — named services with replica processes,
ensure/drop semantics, and health checks.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field


def _replica_env(cpu: bool, devices_per_process: int | None = None) -> dict:
    """Environment for spawned replicas. With cpu=True the platform must be
    pinned BEFORE interpreter start: materialize_tpu's import-time gate (the
    persistent compile cache, off under JAX_PLATFORMS=cpu) reads the env
    before clusterd's --cpu flag is ever parsed.

    `devices_per_process` forces that many virtual host devices in each
    replica (XLA_FLAGS, read at backend init — same mechanism as
    tests/conftest.py), so a replica can form an intra-process device mesh
    (parallel/devicemesh/) UNDER the cross-process host mesh — the 2 proc ×
    N devices composition."""
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    if devices_per_process is not None:
        flag = f"--xla_force_host_platform_device_count={int(devices_per_process)}"
        prior = env.get("XLA_FLAGS", "")
        kept = [
            f for f in prior.split()
            if not f.startswith("--xla_force_host_platform_device_count=")
        ]
        env["XLA_FLAGS"] = " ".join(kept + [flag]).strip()
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class Service:
    name: str
    processes: list = field(default_factory=list)  # subprocess.Popen
    ports: list = field(default_factory=list)
    mesh_ports: list = field(default_factory=list)  # [] for plain replicas
    workers_per_process: int = 1


class ProcessOrchestrator:
    def __init__(
        self,
        cpu: bool = True,
        extra_env: dict | None = None,
        devices_per_process: int | None = None,
    ):
        # `extra_env`: additional environment for spawned replicas — the
        # chaos tests ship the seeded fault schedule (MZT_FAULT_SPEC,
        # cluster/faults.py) to clusterd subprocesses this way
        self.services: dict[str, Service] = {}
        self.cpu = cpu
        self.extra_env = dict(extra_env or {})
        self.devices_per_process = devices_per_process

    def _spawn(self, port: int, mesh_port: int | None):
        args = [
            sys.executable,
            "-m",
            "materialize_tpu.cluster.clusterd",
            "--port",
            str(port),
        ]
        if mesh_port is not None:
            args += ["--mesh-port", str(mesh_port)]
        if self.cpu:
            args.append("--cpu")
        env = _replica_env(self.cpu, self.devices_per_process)
        env.update(self.extra_env)
        return subprocess.Popen(args, env=env)

    def ensure_service(self, name: str, scale: int = 1) -> list[tuple]:
        """Start (or resize to) `scale` clusterd replicas; returns addresses."""
        svc = self.services.get(name)
        if svc is None:
            svc = Service(name)
            self.services[name] = svc
        while len(svc.processes) < scale:
            port = _free_port()
            svc.processes.append(self._spawn(port, None))
            svc.ports.append(port)
        while len(svc.processes) > scale:
            proc = svc.processes.pop()
            svc.ports.pop()
            proc.terminate()
        self._await_ready(svc)
        return [("127.0.0.1", port) for port in svc.ports]

    def ensure_sharded_service(
        self, name: str, processes: int, workers_per_process: int = 1
    ) -> tuple[list, list]:
        """Start a SHARD SET: `processes` clusterd processes that together
        host one replica of `processes × workers_per_process` workers
        (cluster/mesh.py). Returns (command addrs, mesh addrs), both indexed
        by process — feed them to ShardedComputeController, which forms the
        mesh and owns the epoch."""
        svc = self.services.get(name)
        if svc is None:
            svc = Service(name, workers_per_process=workers_per_process)
            self.services[name] = svc
        elif (
            svc.workers_per_process != workers_per_process
            or len(svc.mesh_ports) != len(svc.processes)
            or len(svc.processes) > processes
        ):
            # an existing service of a DIFFERENT shape (plain replicas
            # without mesh listeners, another worker split, or more
            # processes) cannot be quietly reused as this shard set
            raise ValueError(
                f"service {name!r} exists with an incompatible shape: "
                f"{len(svc.processes)} processes × {svc.workers_per_process} "
                f"workers, {len(svc.mesh_ports)} mesh listeners; wanted "
                f"{processes} × {workers_per_process}"
            )
        while len(svc.processes) < processes:
            port = _free_port()
            mesh_port = _free_port()
            svc.processes.append(self._spawn(port, mesh_port))
            svc.ports.append(port)
            svc.mesh_ports.append(mesh_port)
        self._await_ready(svc)
        return (
            [("127.0.0.1", port) for port in svc.ports],
            [("127.0.0.1", port) for port in svc.mesh_ports],
        )

    def _await_ready(self, svc: Service, timeout: float = 30.0) -> None:
        deadline = time.time() + timeout
        for port in svc.ports:
            while True:
                try:
                    with socket.create_connection(("127.0.0.1", port), timeout=1.0):
                        break
                except OSError:
                    if time.time() > deadline:
                        raise TimeoutError(f"replica on :{port} never came up")
                    time.sleep(0.1)

    def replica_alive(self, name: str, idx: int) -> bool:
        """Health probe: is the replica process still running?"""
        return self.services[name].processes[idx].poll() is None

    def restarter(self, name: str):
        """A restart hook for ShardedComputeController(restart_shard=...):
        respawns shard `idx` at its original ports if its process died —
        the self-healing half the controller itself cannot do."""

        def restart(idx: int) -> None:
            if not self.replica_alive(name, idx):
                self.restart_replica(name, idx)

        return restart

    def kill_replica(self, name: str, idx: int) -> None:
        """Fault injection: kill one replica process (it stays in the service
        at the same port slot — restart_replica brings it back)."""
        svc = self.services[name]
        svc.processes[idx].kill()
        svc.processes[idx].wait()

    def restart_replica(self, name: str, idx: int) -> None:
        svc = self.services[name]
        port = svc.ports[idx]
        mesh_port = svc.mesh_ports[idx] if svc.mesh_ports else None
        svc.processes[idx] = self._spawn(port, mesh_port)
        self._await_ready(svc)

    def drop_service(self, name: str) -> None:
        svc = self.services.pop(name, None)
        if svc is None:
            return
        for proc in svc.processes:
            proc.terminate()
        for proc in svc.processes:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()

    def shutdown(self) -> None:
        for name in list(self.services):
            self.drop_service(name)
