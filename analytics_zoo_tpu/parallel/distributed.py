"""Multi-host (pod) execution: jax.distributed bootstrap + per-host feeding.

The reference is, before anything else, a *distributed* training system:
synchronous data-parallel SGD where every Spark executor feeds its local
partition and gradients are AllReduced (reference: docs/docs/wp-bigdl.md:
113-160).  Its hard input contract — ``batch_size % total_core_num == 0``
(reference: pyzoo/zoo/pipeline/api/net.py:458-468) — is exactly the
per-host feeding invariant of a TPU pod: each host process feeds its local
shard of the global batch, and ``jax.make_array_from_process_local_data``
assembles the global device array without any cross-host data motion.

TPU-first shape: one JAX process per TPU host (the reference's "single
multi-threaded task per worker", wp-bigdl.md:169-171); the cluster
bootstrap is ``jax.distributed.initialize`` (coordinator + process id from
env), after which ``jax.devices()`` is the *global* device list and every
jit'd step is a pod-wide SPMD program with XLA-inserted collectives over
ICI/DCN — the entire "2 Spark jobs per iteration" structure collapses into
one compiled step.

Env contract (set by the ``zoo-tpu-submit`` launcher, or by the cloud
runtime on real pods where ``jax.distributed.initialize()`` auto-detects):

  ZOO_TPU_COORDINATOR   host:port of process 0  (alias JAX_COORDINATOR_ADDRESS)
  ZOO_TPU_NUM_PROCESSES number of host processes (alias JAX_NUM_PROCESSES)
  ZOO_TPU_PROCESS_ID    this process's rank      (alias JAX_PROCESS_ID)
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from .. import envcontract

log = logging.getLogger("analytics_zoo_tpu")

ENV_COORD = "ZOO_TPU_COORDINATOR"
ENV_NPROC = "ZOO_TPU_NUM_PROCESSES"
ENV_PID = "ZOO_TPU_PROCESS_ID"

_INITIALIZED = False


def cluster_env_present() -> bool:
    """True when multi-process env vars are set (launcher or cloud)."""
    return bool(envcontract.env_str(ENV_COORD)
                or os.environ.get("JAX_COORDINATOR_ADDRESS")
                or envcontract.env_str(ENV_NPROC)
                or os.environ.get("JAX_NUM_PROCESSES"))


def maybe_initialize_distributed() -> bool:
    """Join the pod-wide cluster when cluster env vars are present.

    Must run before any other JAX call initializes the backend (the same
    ordering constraint as the reference's Engine.init-before-use,
    NNContext.scala:132-146).  Returns True when this process is part of a
    multi-process cluster after the call.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True
    if not cluster_env_present():
        return False
    import jax

    coord = (envcontract.env_str(ENV_COORD)
             or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    nproc = (envcontract.env_str(ENV_NPROC)
             or os.environ.get("JAX_NUM_PROCESSES"))
    pid = (envcontract.env_str(ENV_PID)
           or os.environ.get("JAX_PROCESS_ID"))
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        # multi-process CPU (the test/dryrun substrate — SURVEY §4's
        # "local device = cluster" trick at process granularity) needs the
        # gloo collectives implementation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coord:
        kwargs["coordinator_address"] = coord
    if nproc:
        kwargs["num_processes"] = int(nproc)
    if pid is not None:
        kwargs["process_id"] = int(pid)
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        if "already initialized" not in str(e):
            raise
    _INITIALIZED = True
    log.info("jax.distributed: process %d/%d, %d local / %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())
    # first liveness touch at cluster join (local import: this module
    # loads during the train package's own import) — the supervisor's
    # watchdog then covers the first-compile window too, not just
    # steady-state steps (train/faults.py; size --watchdog-sec above
    # the longest compile + step)
    from ..train import faults
    faults.refresh()
    faults.heartbeat()
    return True


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def is_coordinator() -> bool:
    return process_index() == 0


def put_global(a, sharding, batch_sharded: bool = True,
               batch_dim: int = 0):
    """Place a host-local array onto the (possibly multi-host) mesh.

    Single-process: a plain asynchronous ``device_put`` (per-shard: each
    device's slice transfers independently, so uploads overlap compute
    across the mesh).  Multi-process with ``batch_sharded``: ``a`` is
    this host's shard of the global batch along ``batch_dim``, and the
    global array is assembled from every process's local data — the
    TPU-native analog of the reference's partition→core feeding
    (net.py:458-468).  ``batch_dim`` is 0 for plain batches and 1 for
    gradient-accumulation microbatch layouts (accum, micro, ...), where
    the scanned leading axis is common to all processes.  With
    ``batch_sharded=False`` the same ``a`` must be provided by every
    process (replicated placement).
    """
    import jax

    if jax.process_count() == 1:
        return jax.device_put(a, sharding)
    if batch_sharded:
        global_shape = list(a.shape)
        global_shape[batch_dim] *= jax.process_count()
        return jax.make_array_from_process_local_data(
            sharding, a, tuple(global_shape))
    return jax.make_array_from_process_local_data(sharding, a,
                                                  tuple(a.shape))


def local_rows(arr):
    """Host numpy view of the rows of a batch-sharded global array that are
    addressable from this process (i.e. the rows this host fed) in global
    row order.  Handles outputs additionally sharded along trailing axes
    (tensor-parallel logits): trailing dims are assembled to their full
    global extent.  Single-process this is the whole array."""
    import numpy as np
    import jax

    if jax.process_count() == 1:
        return np.asarray(jax.device_get(arr))
    shards = list(arr.addressable_shards)
    if not shards[0].index:  # scalar / fully replicated
        return np.asarray(shards[0].data)
    # distinct leading-axis extents this host holds, in global order
    lead = sorted({((s.index[0].start or 0),
                    (s.index[0].stop if s.index[0].stop is not None
                     else arr.shape[0])) for s in shards})
    offsets = {}
    total = 0
    for start, stop in lead:
        offsets[start] = total
        total += stop - start
    out = np.empty((total,) + tuple(arr.shape[1:]), arr.dtype)
    for s in shards:
        start = s.index[0].start or 0
        stop = (s.index[0].stop if s.index[0].stop is not None
                else arr.shape[0])
        r0 = offsets[start]
        # trailing indices stay in global coordinates (out spans them)
        out[(slice(r0, r0 + (stop - start)),) + tuple(s.index[1:])] = \
            np.asarray(s.data)
    return out


