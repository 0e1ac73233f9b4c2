"""TPU cluster context — the replacement for the reference's entire L3 layer
(Spark bootstrap + RayOnSpark + py4j; reference call stack SURVEY.md §3.1:
init_orca_context at pyzoo/zoo/orca/common.py:148 -> init_spark_on_yarn ->
RayContext._start_cluster at pyzoo/zoo/ray/raycontext.py:499).

On TPU the whole barrier/filelock/pid-guard apparatus collapses to: one Python
process per TPU host, `jax.distributed.initialize`, and a device mesh. This
module owns that bootstrap plus the global singleton context.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import Mesh

from .config import OrcaConfig
from ..obs import trace as _trace
from ..parallel.mesh import create_mesh

logger = logging.getLogger("analytics_zoo_tpu")

_lock = threading.Lock()
_current: Optional["ClusterContext"] = None


class ClusterContext:
    """Holds the device mesh, config, and per-host process info.

    Replaces the reference's SparkContext + RayContext pair (returned from
    init_orca_context, pyzoo/zoo/orca/common.py:148-257).
    """

    def __init__(self, config: OrcaConfig, mesh: Mesh):
        self.config = config
        self.mesh = mesh
        self._stopped = False

    # --- cluster topology ---------------------------------------------------
    @property
    def num_processes(self) -> int:
        return jax.process_count()

    @property
    def process_id(self) -> int:
        return jax.process_index()

    @property
    def devices(self):
        return list(self.mesh.devices.flat)

    @property
    def local_devices(self):
        pid = jax.process_index()
        return [d for d in self.mesh.devices.flat if d.process_index == pid]

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def is_coordinator(self) -> bool:
        return self.process_id == 0

    def stop(self):
        self._stopped = True

    def __repr__(self):
        return (f"ClusterContext(mode={self.config.cluster_mode}, "
                f"devices={self.num_devices}, mesh={dict(self.mesh.shape)})")


def _setup_logging(level: str):
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(name)s %(levelname)s: %(message)s"))
        logger.addHandler(h)
    logger.setLevel(level.upper())


def init_orca_context(cluster_mode: str = "local",
                      cores: int | str = "*",
                      memory: str = "2g",
                      num_nodes: int = 1,
                      mesh_axes: Optional[Dict[str, int]] = None,
                      coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None,
                      config: Optional[OrcaConfig] = None,
                      compile_cache_dir: Optional[str] = None,
                      **extra) -> ClusterContext:
    """Bootstrap the cluster context. API-compatible entry point with the
    reference's ``init_orca_context`` (pyzoo/zoo/orca/common.py:148), with
    TPU-native semantics:

    * ``cluster_mode="local"``  — single process, all locally visible chips.
    * ``cluster_mode="tpu"`` / ``"multihost"`` — one process per TPU host;
      calls ``jax.distributed.initialize`` (coordinator/num_processes/
      process_id taken from args or TPU metadata env). ``"tpu"`` raises
      unless every device JAX found is a TPU: with ``JAX_PLATFORMS`` unset
      JAX itself falls back to the CPU when TPU init fails, and a job that
      asked for the chip must not train on the host instead.
    * ``cluster_mode="cpu-sim"`` — force the CPU backend (pairs with
      ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for mesh tests).

    ``cores``/``memory``/``num_nodes`` are accepted for source compatibility
    with Spark-era callers; on TPU they do not allocate anything.

    ``compile_cache_dir`` (or env ``ZOO_COMPILE_CACHE``) points the
    compile plane's executable cache and JAX's own persistent compilation
    cache at one directory, so warm restarts skip XLA compilation. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set that directory wins; on an
    accelerator with nothing set the cache goes to a fixed path in the
    checkout (``compile.configure_compile_cache`` has the rule).
    """
    global _current
    with _lock:
        if _current is not None and not _current._stopped:
            logger.warning("init_orca_context called twice; returning existing "
                           "context (call stop_orca_context first to rebuild)")
            return _current

        if cluster_mode in ("tpu", "multihost"):
            # launch-script contract (scripts/launch_multihost.sh): topology
            # arrives via env when not passed explicitly
            coordinator_address = coordinator_address or os.environ.get(
                "ZOO_COORDINATOR")
            if num_processes is None and os.environ.get("ZOO_NUM_PROCS"):
                num_processes = int(os.environ["ZOO_NUM_PROCS"])
            if process_id is None and os.environ.get("ZOO_PROC_ID"):
                process_id = int(os.environ["ZOO_PROC_ID"])

        cfg = config or OrcaConfig()
        if mesh_axes is None and os.environ.get("ZOO_MESH_AXES"):
            # env default (registered knob); an explicit mesh_axes arg wins
            from ..parallel.mesh import parse_mesh_axes
            mesh_axes = parse_mesh_axes(os.environ["ZOO_MESH_AXES"])
        cfg = cfg.replace(cluster_mode=cluster_mode,
                          coordinator_address=coordinator_address,
                          mesh_axes=dict(mesh_axes or cfg.mesh_axes))
        cfg.extra.update(extra)
        _setup_logging(cfg.log_level)

        # a set-up stage (obs/trace.py): the cluster joined, the backend
        # found, the caches placed, the mesh made
        with _trace.stage("context.init", cluster_mode=cluster_mode):
            if cluster_mode in ("tpu", "multihost") and (
                    (num_processes or 1) > 1 or coordinator_address):
                # multi-host: every host runs this same program (SPMD
                # controller).
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id)
                logger.info("jax.distributed initialized: process %d/%d",
                            jax.process_index(), jax.process_count())
            elif cluster_mode == "cpu-sim":
                # no-op when already cpu: config updates after backend
                # initialization are unreliable (silently ignored on this
                # jax build), so an idempotent guard keeps behavior
                # predictable
                if jax.config.jax_platforms != "cpu":
                    jax.config.update("jax_platforms", "cpu")

            if cluster_mode == "tpu":
                platforms = sorted({d.platform for d in jax.devices()})
                if platforms != ["tpu"]:
                    raise RuntimeError(
                        f'init_orca_context(cluster_mode="tpu") found '
                        f"{jax.device_count()} device(s) on platform "
                        f"{'/'.join(platforms)}, not tpu (JAX_PLATFORMS="
                        f"{os.environ.get('JAX_PLATFORMS', '')!r}); use "
                        'cluster_mode="local" to run on whatever is there')
            # after the backend is known: persistence defaults on only
            # where a compile costs seconds to minutes
            # (configure_compile_cache)
            from ..compile import configure_compile_cache
            configure_compile_cache(compile_cache_dir)
            mesh = create_mesh(cfg.mesh_axes)
        ctx = ClusterContext(cfg, mesh)
        _current = ctx
        atexit.register(stop_orca_context)  # mirrors orca/common.py:179
        logger.info("initialized %r", ctx)
        return ctx


def get_context() -> ClusterContext:
    """Return the active context, creating a local one on demand (the
    reference's lazy `RayContext.get` pattern, pyzoo/zoo/ray/raycontext.py:296)."""
    global _current
    if _current is None or _current._stopped:
        return init_orca_context("local")
    return _current


def stop_orca_context():
    """Tear down the context (reference: pyzoo/zoo/orca/common.py:258)."""
    global _current
    with _lock:
        if _current is not None:
            _current.stop()
            _current = None
