"""World bring-up and environment.

TPU-native re-design of the reference's distributed bring-up
(python/paddle/distributed/parallel.py:978 ``init_parallel_env``: TCPStore handshake +
ProcessGroupNCCL creation).  On TPU the rendezvous/store/comm-init stack collapses into
``jax.distributed.initialize`` (DCN rendezvous) + a global ``jax.sharding.Mesh`` over all
devices (ICI); collectives are XLA ops, not a ProcessGroup runtime.

Rank semantics (single-controller SPMD): the framework follows JAX's model — ONE Python
program drives every device.  ``get_rank()`` is the process index (multi-host) and
``get_world_size()`` is the number of *devices* participating in sharding, which is what
users divide their global batch by.  Under the 8-device CPU test platform this gives
rank 0 / world_size 8, the same per-shard view the reference's fake CustomCPU plugin
tests use (SURVEY.md §4).
"""
from __future__ import annotations

import os
import threading

import jax
import numpy as np

__all__ = [
    "init_parallel_env",
    "is_initialized",
    "get_rank",
    "get_world_size",
    "ParallelEnv",
    "world_mesh",
    "barrier",
]

_WORLD = {"mesh": None, "initialized": False}
_WORLD_AXIS = "world"


def _build_world_mesh():
    devs = np.asarray(jax.devices())
    return jax.sharding.Mesh(devs, (_WORLD_AXIS,))


def init_parallel_env():
    """Reference: python/paddle/distributed/parallel.py:978.

    Multi-host: honours the launcher env contract (``PADDLE_MASTER`` /
    ``PADDLE_TRAINER_ID`` / ``PADDLE_TRAINERS_NUM``) by forwarding it to
    ``jax.distributed.initialize`` — the TCPStore analog.  Single host: just builds the
    world mesh.  Idempotent, like the reference.
    """
    if _WORLD["initialized"]:
        return ParallelEnv()
    # the jax coordinator must NOT share the TCPStore's port (the launcher
    # holds that); prefer the dedicated PADDLE_COORDINATOR, then
    # MASTER_ADDR:MASTER_PORT, then PADDLE_MASTER
    master = (os.environ.get("PADDLE_COORDINATOR")
              or os.environ.get("MASTER_ADDR")
              or os.environ.get("PADDLE_MASTER"))
    nnodes = int(os.environ.get("PADDLE_NNODES", "1"))
    # NOT jax.process_count(): that call initializes the XLA backend,
    # after which jax.distributed.initialize refuses to run
    if master and nnodes > 1 and not jax.distributed.is_initialized():
        port = os.environ.get("MASTER_PORT")
        addr = master if ":" in master or not port else f"{master}:{port}"
        try:
            jax.distributed.initialize(
                coordinator_address=addr,
                num_processes=int(os.environ.get("PADDLE_TRAINERS_NUM",
                                                 nnodes)),
                process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
            )
        except RuntimeError as e:
            msg = str(e)
            if not any(t in msg for t in ("already", "must be called",
                                          "only be called once")):
                raise  # real rendezvous failure
            import warnings

            warnings.warn(
                f"init_parallel_env: jax.distributed not (re)initialized "
                f"({e}); continuing with the current world", stacklevel=2)
    _WORLD["mesh"] = _build_world_mesh()
    _WORLD["initialized"] = True
    return ParallelEnv()


def is_initialized() -> bool:
    return _WORLD["initialized"]


def world_mesh() -> jax.sharding.Mesh:
    if _WORLD["mesh"] is None:
        _WORLD["mesh"] = _build_world_mesh()
    return _WORLD["mesh"]


def get_rank(group=None) -> int:
    if group is not None:
        return group.get_group_rank(jax.process_index())
    return jax.process_index()


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    return jax.device_count()


def barrier(group=None):
    """All participating devices sync; on TPU a tiny psum forces a cross-device fence
    (the reference issues an all-reduce of one element too, collective.py barrier)."""
    mesh = group.mesh if group is not None else world_mesh()
    axes = group.axis_names if group is not None else (_WORLD_AXIS,)
    arr = jax.device_put(
        np.zeros((), np.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )

    def _b(x):
        return jax.lax.psum(x, axes)

    out = jax.jit(
        jax.shard_map(_b, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec())
    )(arr)
    jax.block_until_ready(out)


class ParallelEnv:
    """Reference: python/paddle/distributed/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def dev_id(self):
        return 0

    @property
    def local_rank(self):
        return get_rank()

    @property
    def nranks(self):
        return get_world_size()

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")


_STORE = {"server": None, "client": None}


def create_tcp_store(master_addr=None, master_port=None, is_master=None,
                     world_size=None, timeout=900):
    """Framework-level KV rendezvous on the native C++ TCPStore (reference
    python/paddle/distributed/parallel.py:921 spawning phi TCPStore).  Rank 0
    hosts the server; everyone gets a connected client."""
    from paddle_tpu.core.native import TCPStore, TCPStoreServer

    if _STORE["client"] is not None:
        return _STORE["client"]
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if is_master is None:
        is_master = rank == 0
    master_addr = master_addr or os.environ.get("MASTER_ADDR", "127.0.0.1")
    master_port = int(master_port or os.environ.get("MASTER_PORT", "0") or 0)
    if is_master:
        _STORE["server"] = TCPStoreServer(port=master_port)
        master_port = _STORE["server"].port
        # publish the actually-bound port (setdefault would keep a stale '0')
        os.environ["MASTER_PORT"] = str(master_port)
    _STORE["client"] = TCPStore(host=master_addr, port=master_port,
                                is_master=is_master,
                                world_size=world_size or int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
                                timeout=timeout)
    return _STORE["client"]


def destroy_tcp_store():
    if _STORE["client"] is not None:
        _STORE["client"].close()
        _STORE["client"] = None
    if _STORE["server"] is not None:
        _STORE["server"].stop()
        _STORE["server"] = None


def _watchdog_barrier(orig):
    import functools

    @functools.wraps(orig)
    def wrapper(*a, **kw):
        from paddle_tpu.distributed import collective as _coll

        wd = _coll._WATCHDOG["wd"]
        if wd is None:
            return orig(*a, **kw)
        tid = wd.task_start("barrier", _coll._WATCHDOG["timeout_ms"])
        try:
            return orig(*a, **kw)
        finally:
            wd.task_end(tid)

    return wrapper


barrier = _watchdog_barrier(barrier)
