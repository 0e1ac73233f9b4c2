"""Shared two-process ``jax.distributed`` test harness.

The scaffolding ``test_multihost.py`` runs its workers through: worker
script materialization, coordinator port allocation, subprocess fan-out,
timeout kill + output surfacing, the no-CPU-collectives skip.

The coordinator port comes from :func:`free_port` — bind an ephemeral
socket, read the number, close it. That is inherently racy: another
process can claim the port in the window between the close and the
coordinator's own bind, in which case worker 0 dies with a bind error and
every other worker hangs until the timeout. :func:`run_workers` therefore
classifies a failed round: when any worker's output shows a coordinator
bind failure, it retries ONCE with a freshly drawn port before reporting.
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys
from dataclasses import dataclass
from typing import List, Optional

# what a lost port race looks like across jaxlib/grpc versions
_BIND_FAIL_RE = re.compile(
    r"address already in use|failed to bind|bind failed|"
    r"errno\s*=\s*98|EADDRINUSE", re.IGNORECASE)

# this jaxlib build has no cross-process CPU collectives (the gloo/mpi
# backend is compiled out): 2-process init + global-mesh construction
# succeed, but no jitted computation can EXECUTE across processes.
# Environment limitation, not a repo bug — tracked since PR 2.
NO_COLLECTIVES_MARKER = "Multiprocess computations aren't implemented"
NO_COLLECTIVES_SKIP = "jaxlib built without multiprocess CPU collectives"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class WorkerRun:
    """One round of N workers: raw outputs, return codes, verdicts."""

    outs: List[str]
    returncodes: List[Optional[int]]
    timed_out: bool
    port: int
    retried_bind: bool = False

    @property
    def ok(self) -> bool:
        return not self.timed_out and all(rc == 0 for rc in self.returncodes)

    @property
    def no_collectives(self) -> bool:
        return any(NO_COLLECTIVES_MARKER in o for o in self.outs)

    def bind_failed(self) -> bool:
        return (not self.ok
                and any(_BIND_FAIL_RE.search(o) for o in self.outs))

    def tail(self, n: int = 3000) -> str:
        return "\n---\n".join(o[-n:] for o in self.outs)


def _run_once(script_path: str, n_procs: int, port: int,
              timeout: float, devices_per_proc: int) -> WorkerRun:
    # the workers configure their own JAX_PLATFORMS/XLA_FLAGS — ambient
    # values (the suite forces an 8-device mesh) must not leak through
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["ZOO_MH_DEVICES"] = str(devices_per_proc)
    procs = [subprocess.Popen(
        [sys.executable, script_path, str(i), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True) for i in range(n_procs)]
    outs: List[str] = []
    timed_out = False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            out, _ = p.communicate()
        outs.append(out)
    return WorkerRun(outs=outs, returncodes=[p.returncode for p in procs],
                     timed_out=timed_out, port=port)


def run_workers(worker_src: str, tmp_path, n_procs: int = 2,
                timeout: float = 150, devices_per_proc: int = 2
                ) -> WorkerRun:
    """Write ``worker_src`` (``__REPO__`` substituted) to ``tmp_path``,
    launch ``n_procs`` workers against a fresh coordinator port, and
    collect their output. A coordinator bind failure — the
    :func:`free_port` race lost — is retried once with a new port."""
    script = tmp_path / "worker.py"
    script.write_text(worker_src.replace("__REPO__", repo_root()))
    run = _run_once(str(script), n_procs, free_port(), timeout,
                    devices_per_proc)
    if run.bind_failed():
        run = _run_once(str(script), n_procs, free_port(), timeout,
                        devices_per_proc)
        run.retried_bind = True
    return run


# the common worker preamble: pin the virtual CPU device count BEFORE jax
# initializes, join the coordinator, build the global mesh
WORKER_PREAMBLE = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("ZOO_MH_DEVICES", "2"))
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "__REPO__")
import numpy as np
import jax.numpy as jnp
from analytics_zoo_tpu import init_orca_context, stop_orca_context

pid, port = int(sys.argv[1]), sys.argv[2]
ctx = init_orca_context("multihost",
                        coordinator_address="127.0.0.1:" + port,
                        num_processes=2, process_id=pid)
assert jax.process_count() == 2
'''
