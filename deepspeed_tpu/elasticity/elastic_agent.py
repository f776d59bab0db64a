"""Elastic agent — restart-on-failure worker supervision.

Capability parity with reference ``deepspeed/elasticity/elastic_agent.py:28
DSElasticAgent`` (extends torch-elastic's LocalElasticAgent: master addr/port
via store, worker env assembly, monitor loop with max_restarts). TPU-native
equivalence: there is no torch-elastic rendezvous — the agent supervises the
local worker processes directly and restarts the (fixed-size) local group on
failure, exporting ``DS_ELASTIC_RESTART_COUNT`` so workers can detect the
restart generation. *Resizing* to a different world size is the launcher's
job (re-invoke with a new hostfile; ``compute_elastic_config`` gives the
compatible sizes) and training state rides the universal checkpoint.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from ..utils.logging import logger


def require_one_process_per_host(num_local_procs: int,
                                 env: Dict[str, str]) -> None:
    """A chip belongs to one process, and every JAX process claims all
    of its host's chips: N local workers on an accelerator host are N
    claims on the same chips, and all but the first fail or hang. More
    than one local worker is therefore only for the forced-host-device
    CPU mesh, which the workers' environment must select itself
    (``JAX_PLATFORMS=cpu``). The launcher never touches JAX — it would
    hold the chips its workers need — so the environment is all it can
    go by."""
    if num_local_procs > 1 and \
            env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise RuntimeError(
            f"{num_local_procs} worker processes requested on this host, "
            f"but one process drives all local chips: pass one slot per "
            f"host (--num_gpus 1) and let that process see every chip. "
            f"Several local workers are only supported with "
            f"JAX_PLATFORMS=cpu (forced host devices).")


class WorkerSpec:
    """What to run for each local worker (≅ torch-elastic WorkerSpec)."""

    def __init__(self, entrypoint: Sequence[str], local_world_size: int,
                 master_addr: str = "127.0.0.1", master_port: int = 29500,
                 max_restarts: int = 3, monitor_interval: float = 1.0,
                 node_rank: int = 0, nnodes: int = 1,
                 global_rank_offset: Optional[int] = None,
                 world_size: Optional[int] = None,
                 env: Optional[Dict[str, str]] = None):
        self.entrypoint = list(entrypoint)
        self.local_world_size = local_world_size
        self.master_addr = master_addr
        self.master_port = master_port
        self.max_restarts = max_restarts
        self.monitor_interval = monitor_interval
        self.node_rank = node_rank
        self.nnodes = nnodes
        # heterogeneous slots per node: the launcher passes the true offset /
        # world size; the homogeneous defaults only hold when every node has
        # local_world_size slots
        self.global_rank_offset = global_rank_offset \
            if global_rank_offset is not None else node_rank * local_world_size
        self.world_size = world_size \
            if world_size is not None else nnodes * local_world_size
        self.env = dict(env or {})


class DSElasticAgent:
    """Supervises local workers; restarts the whole local group on failure
    up to ``max_restarts`` times (torch-elastic semantics: any worker failure
    fails the group)."""

    def __init__(self, spec: WorkerSpec):
        self.spec = spec
        self.restarts = 0
        self._procs: List[subprocess.Popen] = []

    def _worker_env(self, local_rank: int) -> Dict[str, str]:
        spec = self.spec
        env = dict(os.environ)
        env.update(spec.env)
        global_rank = spec.global_rank_offset + local_rank
        env.update({
            "LOCAL_RANK": str(local_rank),
            "RANK": str(global_rank),
            "LOCAL_SIZE": str(spec.local_world_size),
            "WORLD_SIZE": str(spec.world_size),
            "MASTER_ADDR": spec.master_addr,
            "MASTER_PORT": str(spec.master_port),
            # jax.distributed.initialize contract (same as launch.py)
            "JAX_COORDINATOR_ADDRESS":
                f"{spec.master_addr}:{spec.master_port}",
            "JAX_PROCESS_ID": str(global_rank),
            "JAX_NUM_PROCESSES": str(spec.world_size),
            # restart generation: lets workers detect a re-formed job
            "DS_ELASTIC_RESTART_COUNT": str(self.restarts),
        })
        return env

    def _start_workers(self) -> None:
        self._procs = []
        require_one_process_per_host(self.spec.local_world_size,
                                     self._worker_env(0))
        for local_rank in range(self.spec.local_world_size):
            p = subprocess.Popen(self.spec.entrypoint,
                                 env=self._worker_env(local_rank))
            self._procs.append(p)
        logger.info(f"elastic agent: started {len(self._procs)} workers "
                    f"(restart {self.restarts}/{self.spec.max_restarts})")

    def _kill_workers(self) -> None:
        for p in self._procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for p in self._procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()

    def _monitor(self) -> Optional[int]:
        """Returns the failing exit code, or None if all workers succeeded."""
        while True:
            codes = [p.poll() for p in self._procs]
            failed = [c for c in codes if c is not None and c != 0]
            if failed:
                return failed[0]
            if all(c == 0 for c in codes):
                return None
            time.sleep(self.spec.monitor_interval)

    def run(self) -> int:
        """Supervise until success or restarts exhausted; returns exit code."""
        self._start_workers()
        while True:
            code = self._monitor()
            if code is None:
                logger.info("elastic agent: all workers finished successfully")
                return 0
            self._kill_workers()
            if self.restarts >= self.spec.max_restarts:
                logger.error(
                    f"elastic agent: worker failed (exit {code}) and "
                    f"max_restarts={self.spec.max_restarts} exhausted")
                return code
            self.restarts += 1
            logger.warning(f"elastic agent: worker failed (exit {code}); "
                           f"restarting group "
                           f"({self.restarts}/{self.spec.max_restarts})")
            self._start_workers()

    def shutdown(self) -> None:
        self._kill_workers()
