"""Multi-host bootstrap: topology discovery, ``jax.distributed`` init, and a
CPU process spawner so the whole runtime is exercisable in CI without TPUs.

Three pieces (docs/DESIGN.md §11.1):

- ``Topology`` — the process-topology descriptor: how many processes
  (hosts), which one this is, where the coordinator lives, and how many XLA
  host devices each process exposes. ``Topology.from_env()`` reads the
  ``REPRO_*`` variables (falling back to single-process) so the same worker
  code runs under ``spawn_local``, a cluster launcher, or bare.
- ``initialize(topo)`` — calls ``jax.distributed.initialize`` exactly once
  for multi-process topologies and returns a ``RuntimeContext`` wrapping the
  coordinator's key-value store. On the CPU backend cross-process XLA
  collectives are unavailable (the backend refuses multiprocess programs),
  so the KV store + barrier IS the cross-pod transport: numpy arrays round-
  trip bit-exactly through ``put_bytes``/``get_bytes``
  (``runtime.comms.CrossPodExchange`` builds on exactly this). On TPU/GPU
  meshes the same context coexists with real device collectives
  (``dist.collectives.psum_scatter_mean`` is the device-side fast path).
- ``spawn_local(worker, n)`` — forks ``n`` fresh CPU processes (spawn
  context: children re-import, so env set here governs their jax), wires
  them to a coordinator on a free localhost port, runs
  ``worker(ctx, *args)`` in each, and returns the per-process results. It is
  a CPU test harness: it refuses to run unless the caller asked for the CPU
  (``JAX_PLATFORMS=cpu``), so on an accelerator host it fails loudly instead
  of running the rounds on the host CPU.

Coordinator discovery order: explicit argument > ``REPRO_COORDINATOR`` >
single-process (no coordinator needed).
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
import socket
import traceback

# env keys the spawner sets and Topology.from_env reads
ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_LOCAL_DEVICES"

_DEFAULT_TIMEOUT_MS = 120_000


@dataclasses.dataclass(frozen=True)
class Topology:
    """Process topology: hosts x (pods live above, in ``PodPlan``) x local
    devices. One instance per process; ``process_id`` names this process."""

    n_processes: int = 1
    process_id: int = 0
    coordinator: str | None = None  # "host:port"; required when n_processes > 1
    local_devices: int = 1          # XLA host devices this process exposes

    def __post_init__(self):
        if self.n_processes < 1:
            raise ValueError(f"n_processes must be >= 1, got {self.n_processes}")
        if not 0 <= self.process_id < self.n_processes:
            raise ValueError(
                f"process_id {self.process_id} out of range "
                f"[0, {self.n_processes})"
            )
        if self.n_processes > 1 and not self.coordinator:
            raise ValueError("multi-process topology needs a coordinator "
                             "address (host:port)")

    @classmethod
    def from_env(cls, coordinator: str | None = None,
                 n_processes: int | None = None,
                 process_id: int | None = None) -> "Topology":
        """Env-discovered topology; explicit arguments win over env vars."""
        return cls(
            n_processes=int(n_processes if n_processes is not None
                            else os.environ.get(ENV_NUM_PROCESSES, "1")),
            process_id=int(process_id if process_id is not None
                           else os.environ.get(ENV_PROCESS_ID, "0")),
            coordinator=(coordinator if coordinator is not None
                         else os.environ.get(ENV_COORDINATOR) or None),
            local_devices=int(os.environ.get(ENV_LOCAL_DEVICES, "1")),
        )


class RuntimeContext:
    """One process's handle on the multi-host runtime.

    Wraps the topology plus (multi-process only) the ``jax.distributed``
    coordinator's key-value store — the exact-byte transport the CPU
    hierarchical decode exchanges pod records through. Single-process
    contexts have no store; ``barrier``/``put_bytes`` are no-ops/errors so
    callers can treat "1 process" uniformly via ``is_distributed``.
    """

    def __init__(self, topo: Topology, kv_client=None):
        self.topo = topo
        self._kv = kv_client

    # ------------------------------------------------------------ topology

    @property
    def n_processes(self) -> int:
        return self.topo.n_processes

    @property
    def process_id(self) -> int:
        return self.topo.process_id

    @property
    def is_distributed(self) -> bool:
        return self.topo.n_processes > 1

    def pods_owned(self, n_pods: int) -> range:
        """Contiguous ceil-block pod ownership (the ``ChunkOwnership``
        idiom): process i owns pods [i*cpp, min((i+1)*cpp, P))."""
        cpp = -(-n_pods // self.n_processes)  # ceil
        lo = min(self.process_id * cpp, n_pods)
        return range(lo, min(lo + cpp, n_pods))

    def owner_of_pod(self, pod: int, n_pods: int) -> int:
        if not 0 <= pod < n_pods:
            raise ValueError(f"pod {pod} out of range [0, {n_pods})")
        cpp = -(-n_pods // self.n_processes)
        return pod // cpp

    # ------------------------------------------------------- KV transport

    def put_bytes(self, key: str, value: bytes) -> None:
        if self._kv is None:
            raise RuntimeError("single-process context has no KV store")
        self._kv.key_value_set_bytes(key, value)

    def get_bytes(self, key: str,
                  timeout_ms: int = _DEFAULT_TIMEOUT_MS) -> bytes:
        if self._kv is None:
            raise RuntimeError("single-process context has no KV store")
        return self._kv.blocking_key_value_get_bytes(key, timeout_ms)

    def delete(self, key: str) -> None:
        if self._kv is not None:
            self._kv.key_value_delete(key)

    def barrier(self, name: str,
                timeout_ms: int = _DEFAULT_TIMEOUT_MS) -> None:
        if self._kv is not None:
            self._kv.wait_at_barrier(name, timeout_ms)


def initialize(topo: Topology | None = None) -> RuntimeContext:
    """Bootstrap this process into the runtime described by ``topo``
    (default: ``Topology.from_env()``).

    Single-process topologies return a storeless context without touching
    ``jax.distributed`` at all. Multi-process topologies call
    ``jax.distributed.initialize`` (idempotent per process: a second call
    returns the existing client) — process 0 hosts the coordinator service
    at ``topo.coordinator``.
    """
    topo = topo or Topology.from_env()
    if topo.n_processes == 1:
        return RuntimeContext(topo)
    import jax

    from jax._src import distributed as _jdist

    if _jdist.global_state.client is None:
        jax.distributed.initialize(
            coordinator_address=topo.coordinator,
            num_processes=topo.n_processes,
            process_id=topo.process_id,
        )
    client = _jdist.global_state.client
    if client is None:  # pragma: no cover - initialize() raises first
        raise RuntimeError("jax.distributed.initialize produced no client")
    return RuntimeContext(topo, kv_client=client)


def shutdown() -> None:
    """Tear down this process's ``jax.distributed`` membership (no-op when
    never initialized). Spawned workers call this on exit so the coordinator
    sees a clean departure instead of a timeout."""
    from jax._src import distributed as _jdist

    if _jdist.global_state.client is not None:
        import jax

        jax.distributed.shutdown()


def free_port() -> int:
    """A free localhost TCP port (bind-to-0 trick; raceable in principle,
    fine for test/CI spawners)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_main(worker, process_id: int, n_processes: int, coordinator: str,
                local_devices: int, conn, args: tuple) -> None:
    """Spawned-child entry: pin env BEFORE jax creates a backend, join the
    runtime, run the worker, ship the (pickled) result back."""
    os.environ[ENV_COORDINATOR] = coordinator
    os.environ[ENV_NUM_PROCESSES] = str(n_processes)
    os.environ[ENV_PROCESS_ID] = str(process_id)
    os.environ[ENV_LOCAL_DEVICES] = str(local_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if local_devices > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={local_devices}"
        ).strip()
    try:
        ctx = initialize(Topology.from_env())
        try:
            out = worker(ctx, *args)
        finally:
            shutdown()
        conn.send(("ok", out))
    except BaseException:
        conn.send(("err", traceback.format_exc()))
    finally:
        conn.close()


def spawn_local(worker, n_processes: int, *, args: tuple = (),
                local_devices: int = 1, timeout_s: float = 300.0) -> list:
    """Run ``worker(ctx, *args)`` in ``n_processes`` fresh local CPU
    processes wired into one runtime; returns ``[worker result] * n`` in
    process order.

    ``worker`` must be a module-level (picklable) function: the spawn
    context starts clean interpreters, which is exactly what lets each child
    own its jax runtime (the parent's backend state never leaks in).
    Children talk to a coordinator hosted by child 0 on a free localhost
    port. Raises RuntimeError carrying the child tracebacks on any failure,
    and before forking anything when ``JAX_PLATFORMS`` is not ``cpu``.
    """
    if n_processes < 1:
        raise ValueError(f"n_processes must be >= 1, got {n_processes}")
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "spawn_local forks CPU-only worker processes; set "
            "JAX_PLATFORMS=cpu to ask for that, or start one process per "
            "host under a cluster launcher (REPRO_PROCESS_ID et al.)"
        )
    coordinator = f"127.0.0.1:{free_port()}"
    mp = multiprocessing.get_context("spawn")
    procs, conns = [], []
    for i in range(n_processes):
        parent_conn, child_conn = mp.Pipe(duplex=False)
        p = mp.Process(
            target=_child_main,
            args=(worker, i, n_processes, coordinator, local_devices,
                  child_conn, tuple(args)),
            daemon=False,
        )
        p.start()
        child_conn.close()
        procs.append(p)
        conns.append(parent_conn)

    results, errors = [None] * n_processes, []
    try:
        for i, (p, conn) in enumerate(zip(procs, conns)):
            if conn.poll(timeout_s):
                status, payload = conn.recv()
                if status == "ok":
                    results[i] = payload
                else:
                    errors.append(f"[process {i}]\n{payload}")
            else:
                errors.append(f"[process {i}] no result within {timeout_s}s")
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
    if errors:
        raise RuntimeError(
            f"spawn_local: {len(errors)}/{n_processes} workers failed:\n"
            + "\n".join(errors)
        )
    return results
