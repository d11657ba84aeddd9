"""Multi-process cluster bring-up: distributed init, meshes, edge shards.

The paper's § dynamicity scenario is a partitioner running on elastic,
unreliable cloud capacity.  This module stands the capacity up:

* :func:`bootstrap` wraps ``jax.distributed.initialize`` (coordinator +
  N worker processes, each with forced host devices on CPU) and returns
  a :class:`ClusterHandle` exposing the process-local and the
  process-spanning mesh plus the coordination-service primitives (a
  distributed KV store and named barriers) every process can use for
  control-plane traffic.

* :func:`write_edge_shards` / :func:`load_edge_shard` are the per-host
  graph loading path: the directed edge list is split by owning host
  (owner = ``src // v_per_host``, the same range partition
  ``core.distributed.shard_graph`` uses) into one ``.npz`` file per
  host plus a manifest carrying the O(V) vertex state (``deg_w``) and
  the globally agreed raw segment widths.  A worker loads ONLY its
  file and builds its layout row with
  ``shard_graph(view, ndev, local_only=pid, seg_widths=...)`` -- no
  process ever materializes the full O(E) edge set.

* :func:`spawn_local_worker` / :func:`free_port` subprocess-spawn a
  local coordinator + workers for tests and CI (each process pinned to
  its own forced-host-device count via ``XLA_FLAGS``).

Backend note (jax 0.9.0, CPU): after ``jax.distributed.initialize`` the
global device view spans processes, the coordination service (KV
store, barriers) works, and a jitted reduction over a two-process
global mesh runs through the CPU backend's gloo collectives (the
default ``jax_cpu_collectives_implementation``).  The CPU cluster
runtime (``repro.cluster.worker``) still computes on each process's
local mesh and exchanges labels/aggregates through the coordination
service; :meth:`ClusterHandle.global_mesh` is the mesh a worker would
run the engine's own ``shard_map`` program over instead.  Workers are
CPU processes: :func:`worker_env` pins ``JAX_PLATFORMS=cpu`` so a
spawned worker never competes with its parent for an accelerator.
"""
from __future__ import annotations

import base64
import dataclasses
import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

REPO_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Distributed init + handle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClusterConfig:
    """One process's view of the cluster."""
    coordinator_address: str = "127.0.0.1"
    port: int = 0
    num_processes: int = 1
    process_id: int = 0
    # default timeout for blocking KV reads / barriers (seconds); a dead
    # peer surfaces as a timeout here, converted to PeerLost by callers
    rpc_timeout: float = 60.0
    # blocking KV reads wait in slices of this length so the handle's
    # ``on_wait`` hook (the worker's heartbeat) fires while a superstep
    # legitimately blocks on a slow peer -- a live waiter must not look
    # stale to the process supervisor
    poll_slice: float = 5.0

    @property
    def coordinator(self) -> str:
        return f"{self.coordinator_address}:{self.port}"


class PeerLost(RuntimeError):
    """A blocking coordination read timed out -- a peer is presumed dead."""


class ClusterHandle:
    """The live cluster from one process's perspective.

    Wraps the ``jax.distributed`` coordination client: ``kv_put`` /
    ``kv_get`` move small control-plane strings (the CPU worker loop
    encodes label slices and (k,) aggregates through them), ``barrier``
    synchronizes named points, and the mesh accessors build the local
    and the process-spanning device meshes.
    """

    def __init__(self, cfg: ClusterConfig):
        self.cfg = cfg
        import jax
        self._jax = jax
        self.process_id = jax.process_index() if cfg.num_processes > 1 \
            else cfg.process_id
        self.num_processes = cfg.num_processes
        # called between blocking-wait slices in kv_get (the worker
        # binds its heartbeat here): a process still polling the
        # coordination service is alive, however slow its peers are
        self.on_wait: Optional[callable] = None

    # -- meshes ------------------------------------------------------------

    def local_mesh(self, axis: str = "data"):
        """Mesh over THIS process's devices (always executable)."""
        from repro.launch.mesh import make_partition_mesh
        return make_partition_mesh(devices=self._jax.local_devices(),
                                   axis=axis)

    def global_mesh(self, axis: str = "data"):
        """Process-spanning mesh over ``jax.devices()``.

        Constructible on every backend; cross-process execution requires
        an accelerator backend (see the module docstring for the CPU
        limitation).
        """
        from repro.launch.mesh import make_partition_mesh
        return make_partition_mesh(devices=self._jax.devices(), axis=axis)

    # -- coordination service ---------------------------------------------

    @property
    def _client(self):
        from jax._src.distributed import global_state
        client = global_state.client
        if client is None:
            raise RuntimeError("jax.distributed is not initialized; "
                               "call bootstrap() first")
        return client

    def kv_put(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value)

    def kv_get(self, key: str, timeout: Optional[float] = None) -> str:
        """Blocking read with the full ``rpc_timeout`` budget, waited in
        ``poll_slice``-length slices with ``on_wait()`` fired between
        them -- so a worker blocked on a slow peer keeps heartbeating
        and is not misdeclared stale by the process supervisor."""
        total = self.cfg.rpc_timeout if timeout is None else timeout
        deadline = time.monotonic() + total
        err: Optional[Exception] = None
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise PeerLost(f"kv_get({key!r}) timed out after "
                               f"{total}s: {err}") from err
            ms = max(1, int(1000 * min(self.cfg.poll_slice, remaining)))
            t_slice = time.monotonic()
            try:
                return self._client.blocking_key_value_get(key, ms)
            except Exception as e:                  # XlaRuntimeError etc.
                err = e
                # a non-timeout failure (service down) returns instantly:
                # don't spin hot while the deadline runs out
                if time.monotonic() - t_slice < 0.05:
                    time.sleep(0.05)
            if self.on_wait is not None:
                self.on_wait()

    def kv_put_array(self, key: str, arr: np.ndarray) -> None:
        self.kv_put(key, base64.b64encode(
            np.ascontiguousarray(arr).tobytes()).decode("ascii"))

    def kv_get_array(self, key: str, dtype, shape,
                     timeout: Optional[float] = None) -> np.ndarray:
        raw = base64.b64decode(self.kv_get(key, timeout))
        return np.frombuffer(raw, dtype=dtype).reshape(shape).copy()

    def allreduce_sum(self, tag: str, arr: np.ndarray,
                      timeout: Optional[float] = None) -> np.ndarray:
        """Sum ``arr`` across all processes through the KV store.

        Every process publishes its contribution under a unique
        ``tag/pid`` key and reads all peers' -- one logical collective
        per (iteration, call-site) tag.  O(world) small messages; this
        is control-plane math (the (k,) aggregators and halting
        scalars), not the O(V) data plane.
        """
        arr = np.asarray(arr)
        self.kv_put_array(f"{tag}/{self.process_id}", arr)
        total = np.zeros_like(arr)
        for q in range(self.num_processes):
            total = total + self.kv_get_array(
                f"{tag}/{q}", arr.dtype, arr.shape, timeout)
        return total

    def kv_delete(self, key: str) -> None:
        """Best-effort delete of ``key`` (a trailing ``/`` deletes the
        whole prefix).  The worker GCs iteration ``t-1``'s label/reduce
        keys once iteration ``t``'s allreduce proves every peer is past
        them, bounding coordinator memory to O(V) live keys instead of
        O(V x iterations).  A no-op on runtimes without
        ``key_value_delete``; GC must never kill a worker."""
        try:
            delete = getattr(self._client, "key_value_delete", None)
            if delete is not None:
                delete(key)
        except Exception:
            pass

    def barrier(self, name: str, timeout: Optional[float] = None) -> None:
        ms = int(1000 * (self.cfg.rpc_timeout if timeout is None
                         else timeout))
        try:
            self._client.wait_at_barrier(name, ms)
        except Exception as e:
            raise PeerLost(f"barrier({name!r}) timed out: {e}") from e

    def shutdown(self) -> None:
        try:
            self._jax.distributed.shutdown()
        except Exception:
            pass


def bootstrap(cfg: ClusterConfig) -> ClusterHandle:
    """Initialize ``jax.distributed`` for this process and return the
    handle.  Idempotent per process: a second call with the same config
    returns a fresh handle over the existing service.  Single-process
    configs skip distributed init entirely (the handle's coordination
    surface then requires ``num_processes > 1``; the worker loop guards
    on ``world == 1``)."""
    import jax
    if cfg.num_processes > 1:
        from jax._src.distributed import global_state
        if global_state.client is None:
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id)
    return ClusterHandle(cfg)


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Per-host edge shards
# ---------------------------------------------------------------------------

_MANIFEST = "manifest.json"


def write_edge_shards(graph, directory: str, num_hosts: int) -> dict:
    """Split a graph's directed edge list into per-host files.

    Layout on disk (the durable graph the cluster boots from)::

        <dir>/manifest.json   num_vertices, num_hosts, v_per_host,
                              total_weight, seg widths, per-host counts
        <dir>/deg_w.npy       full (V,) weighted degrees (O(V) state)
        <dir>/shard_<h>.npz   src/dst/weight of edges with owner h

    Owner = ``src // v_per_host`` -- the identical range partition
    ``shard_graph`` applies, so host ``h``'s file feeds
    ``shard_graph(view, num_hosts, local_only=h, seg_widths=...)`` and
    reproduces row ``h`` of the full layout byte-for-byte.  The raw
    (max-over-hosts) interior/frontier segment widths are computed here
    once, while the whole edge list is still in one place, and recorded
    in the manifest: that is the only global agreement hosts need to
    build compile-shape-compatible rows independently.
    """
    os.makedirs(directory, exist_ok=True)
    v_per_host = -(-graph.num_vertices // num_hosts)
    real = graph.weight > 0
    src, dst, w = graph.src[real], graph.dst[real], graph.weight[real]
    owner = src // v_per_host
    frontier = (dst // v_per_host) != owner
    n_int = np.bincount(owner[~frontier],
                        minlength=num_hosts).astype(np.int64)
    n_fro = np.bincount(owner[frontier],
                        minlength=num_hosts).astype(np.int64)
    for h in range(num_hosts):
        sel = owner == h
        np.savez(os.path.join(directory, f"shard_{h}.npz"),
                 src=src[sel].astype(np.int32),
                 dst=dst[sel].astype(np.int32),
                 weight=w[sel].astype(np.float32))
    np.save(os.path.join(directory, "deg_w.npy"),
            np.asarray(graph.deg_w, np.float32))
    manifest = {
        "num_vertices": int(graph.num_vertices),
        "num_hosts": int(num_hosts),
        "v_per_host": int(v_per_host),
        "total_weight": float(graph.total_weight),
        "seg_interior": int(n_int.max()) if n_int.size else 0,
        "seg_frontier": int(n_fro.max()) if n_fro.size else 0,
        "interior_counts": [int(x) for x in n_int],
        "frontier_counts": [int(x) for x in n_fro],
    }
    with open(os.path.join(directory, _MANIFEST), "w") as f:
        json.dump(manifest, f)
    return manifest


def read_manifest(directory: str) -> dict:
    with open(os.path.join(directory, _MANIFEST)) as f:
        return json.load(f)


def load_edge_shard(directory: str, host: int):
    """One host's :class:`~repro.core.distributed.EdgeShardView`: its
    edge file plus the shared O(V) degree vector -- never the full edge
    set.  Returns ``(view, manifest)``."""
    from repro.core.distributed import EdgeShardView
    manifest = read_manifest(directory)
    z = np.load(os.path.join(directory, f"shard_{host}.npz"))
    deg_w = np.load(os.path.join(directory, "deg_w.npy"))
    view = EdgeShardView(num_vertices=manifest["num_vertices"],
                         src=z["src"], dst=z["dst"], weight=z["weight"],
                         deg_w=deg_w)
    return view, manifest


def load_local_shard(directory: str, host: int, pad: bool = False):
    """Host ``host``'s single-row ``ShardedGraph`` built from its edge
    file alone (the ``local_only`` path), layout-compatible with every
    other host's row via the manifest's agreed segment widths."""
    from repro.core.distributed import shard_graph
    view, manifest = load_edge_shard(directory, host)
    return shard_graph(view, manifest["num_hosts"], pad=pad,
                       local_only=host,
                       seg_widths=(manifest["seg_interior"],
                                   manifest["seg_frontier"]))


# ---------------------------------------------------------------------------
# Local subprocess spawning (tests / CI)
# ---------------------------------------------------------------------------

def worker_env(*, devices_per_process: int = 1,
               extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a spawned worker: the CPU platform with forced
    host devices + src on the path; ``extra`` entries win."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices_per_process}")
    env["PYTHONPATH"] = REPO_SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if extra:
        env.update(extra)
    return env


def spawn_local_worker(*, workdir: str, gen: int, world: int, pid: int,
                       port: int, devices_per_process: int = 1,
                       extra_env: Optional[Dict[str, str]] = None
                       ) -> subprocess.Popen:
    """Spawn one cluster worker process (``python -m
    repro.cluster.worker``) for the local coordinator/worker topology.
    Process 0 is the coordinator; all read ``<workdir>/job.json``."""
    argv = [sys.executable, "-m", "repro.cluster.worker",
            "--workdir", workdir, "--gen", str(gen),
            "--world", str(world), "--pid", str(pid),
            "--port", str(port)]
    out = open(os.path.join(workdir, f"worker_g{gen}_p{pid}.log"), "wb")
    return subprocess.Popen(argv,
                            env=worker_env(
                                devices_per_process=devices_per_process,
                                extra=extra_env),
                            stdout=out, stderr=subprocess.STDOUT)
