"""Context initialization: the TPU-native ``init_nncontext`` equivalent.

Parity surface: reference ``NNContext.initNNContext`` / python
``init_nncontext`` (zoo/.../common/NNContext.scala:132-206,
pyzoo/zoo/common/nncontext.py:21-40): conf injection + engine init + version
check.  On TPU the "context" is {platform, mesh, typed config}; there is no
SparkContext and no 5-layer conf sprawl (SURVEY §5 flags this) — one typed
``ZooTpuConfig`` object replaces bundled-conf-file + sys-props + env-var
layering.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import re
from typing import Dict, Optional

import jax

from ..parallel import distributed as dist_lib
from ..parallel import mesh as mesh_lib

log = logging.getLogger("analytics_zoo_tpu")

__version__ = "0.1.0"


@dataclasses.dataclass
class ZooTpuConfig:
    """Typed configuration (replaces spark-analytics-zoo.conf injection)."""

    app_name: str = "analytics-zoo-tpu"
    mesh_axes: Optional[Dict[str, int]] = None  # None -> all devices on data
    compute_dtype: str = "float32"  # "bfloat16" for MXU-native training
    seed: int = 0
    log_level: str = "INFO"
    version_check: bool = False  # parity: spark.analytics.zoo.versionCheck


class NNContext:
    """Holds the device mesh + config for a session."""

    def __init__(self, conf: ZooTpuConfig, mesh):
        self.conf = conf
        self.mesh = mesh
        self.app_name = conf.app_name

    @property
    def devices(self):
        return list(self.mesh.devices.flat)

    @property
    def device_count(self):
        return len(self.devices)

    def __repr__(self):
        return (f"NNContext(app={self.app_name!r}, "
                f"platform={self.devices[0].platform}, "
                f"mesh={dict(self.mesh.shape)})")


_CONTEXT: Optional[NNContext] = None

#: the checkout (or install) root: the directory that holds the package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in use.  Placed from OUTSIDE when it can be: with
    ``JAX_COMPILATION_CACHE_DIR`` set jax reads it itself and nothing
    is set in code; otherwise the fixed ``<checkout>/.jax_cache`` — a
    directory that moves never hits, so never a temporary, pid- or
    time-named one.  Failure to enable it raises: a cold compile
    mistaken for a warm one is a wrong measurement, not a degraded
    one.

    An executable's key holds the program's metadata, its
    ``jax.named_scope``s among it: jax leaves metadata out by default,
    and a program that differs from a cached one in its scopes alone
    would be answered with the other's executable and show the other's
    names in a profile.  File names in that metadata count from the
    checkout (unless a canonicalization is set already), so the same
    code in another directory still hits."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(_CHECKOUT + os.sep))
    return cache_dir


def init_nncontext(conf: Optional[ZooTpuConfig] = None,
                   app_name: Optional[str] = None) -> NNContext:
    """Create (or return) the process-wide context.

    Mirrors the getOrCreate semantics of the reference
    (NNContext.scala:132-146): repeated calls return the same context.
    """
    global _CONTEXT
    if _CONTEXT is not None:
        return _CONTEXT
    if isinstance(conf, str):
        # reference parity: init_nncontext("App Name") treats a bare
        # string conf as the application name (nncontext.py:32-33)
        conf, app_name = None, app_name or conf
    conf = conf or ZooTpuConfig()
    if app_name:
        conf.app_name = app_name
    logging.basicConfig(level=getattr(logging, conf.log_level, logging.INFO))
    if conf.version_check:
        check_version()
    enable_compile_cache()
    # join the pod-wide cluster BEFORE the first backend-initializing jax
    # call, when launcher/cloud env vars are present (the reference's
    # Engine.init-before-use ordering, NNContext.scala:132-146) — after
    # this, jax.devices() below is the GLOBAL device list and the mesh
    # spans every host in the pod
    dist_lib.maybe_initialize_distributed()
    mesh = mesh_lib.create_mesh(conf.mesh_axes)
    mesh_lib.set_default_mesh(mesh)
    log.info("initNNContext: process %d/%d, %d %s device(s), mesh %s",
             jax.process_index(), jax.process_count(),
             len(jax.devices()), jax.devices()[0].platform,
             dict(mesh.shape))
    _CONTEXT = NNContext(conf, mesh)
    return _CONTEXT


# parity alias with the scala camelCase entry point
initNNContext = init_nncontext


def get_nncontext() -> Optional[NNContext]:
    return _CONTEXT


def reset_nncontext():
    global _CONTEXT
    _CONTEXT = None
    mesh_lib.set_default_mesh(None)


def check_version():
    """Compile-time vs runtime version check parity
    (NNContext.scala:78-130 ZooBuildInfo)."""
    import jax as _jax
    log.info("analytics-zoo-tpu %s on jax %s", __version__, _jax.__version__)
    return __version__
