"""Node-local launcher.

Capability parity with reference ``deepspeed/launcher/launch.py:132 main()``
— decodes the base64 world info, computes this node's global ranks, forks
one training process per local rank with ``RANK/WORLD_SIZE/MASTER_*`` env
set, installs a sigkill handler that tears the whole local group down when
any rank dies (:313), and routes to the elastic agent when
``--enable_elastic_training``.

TPU process model: ONE process per host drives all local chips
(``jax.distributed.initialize`` + every local device visible), so the world
info maps hosts → process slots rather than GPU ids. More than one slot per
host is accepted only with ``JAX_PLATFORMS=cpu`` (CPU-mesh testing); on an
accelerator host it is an error (``require_one_process_per_host``), since
every process would claim every chip. This process initialises no JAX
backend, so it holds no chip itself.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import signal
import subprocess
import sys
import time
from collections import defaultdict
from typing import Dict, List

from ..elasticity.elastic_agent import (DSElasticAgent, WorkerSpec,
                                        require_one_process_per_host)
from ..utils.logging import logger

PID_FILE_BASEPATH = "/tmp"


def parse_args():
    parser = argparse.ArgumentParser(
        description="DeepSpeed-TPU node-local launcher")
    parser.add_argument("--node_rank", type=int, default=0,
                        help="rank of this node in the multi-node job")
    parser.add_argument("--master_addr", default="127.0.0.1", type=str)
    parser.add_argument("--master_port", default=29500, type=int)
    parser.add_argument("--world_info", default="None", type=str,
                        help="base64-encoded json of {host: [slots]}")
    parser.add_argument("--enable_elastic_training", action="store_true")
    parser.add_argument("--max_elastic_restarts", type=int, default=3)
    parser.add_argument("--save_pid", type=int, default=0,
                        help="write a launcher pid file for ds_ssh cleanup")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args()


def decode_world_info(world_info_b64: str) -> Dict[str, List[int]]:
    if world_info_b64 in (None, "None", ""):
        return {}
    decoded = base64.urlsafe_b64decode(world_info_b64)
    return json.loads(decoded)


def main(args=None):
    args = args or parse_args()
    world_info = decode_world_info(args.world_info)
    if not world_info:
        world_info = {"localhost": [0]}
    logger.info(f"launch: world_info={world_info} node_rank={args.node_rank}")

    node_list = list(world_info.keys())
    nnodes = len(node_list)
    if args.node_rank >= nnodes:
        raise ValueError(
            f"node_rank {args.node_rank} >= number of nodes {nnodes}")
    local_slots = world_info[node_list[args.node_rank]]
    num_local_procs = len(local_slots)

    # global rank offset = slots on the preceding nodes
    global_rank_offset = 0
    for i in range(args.node_rank):
        global_rank_offset += len(world_info[node_list[i]])
    world_size = sum(len(s) for s in world_info.values())

    require_one_process_per_host(num_local_procs, os.environ)

    if args.enable_elastic_training:
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-u", args.user_script] +
            args.user_args,
            local_world_size=num_local_procs,
            master_addr=args.master_addr, master_port=args.master_port,
            max_restarts=args.max_elastic_restarts,
            node_rank=args.node_rank, nnodes=nnodes,
            global_rank_offset=global_rank_offset, world_size=world_size)
        agent = DSElasticAgent(spec)
        sys.exit(agent.run())

    processes: List[subprocess.Popen] = []
    for local_rank, slot in enumerate(local_slots):
        env = dict(os.environ)
        env.update({
            "LOCAL_RANK": str(local_rank),
            "RANK": str(global_rank_offset + local_rank),
            "LOCAL_SIZE": str(num_local_procs),
            "WORLD_SIZE": str(world_size),
            "MASTER_ADDR": args.master_addr,
            "MASTER_PORT": str(args.master_port),
            # jax.distributed.initialize contract
            "JAX_COORDINATOR_ADDRESS":
                f"{args.master_addr}:{args.master_port}",
            "JAX_PROCESS_ID": str(global_rank_offset + local_rank),
            "JAX_NUM_PROCESSES": str(world_size),
        })
        cmd = [sys.executable, "-u", args.user_script] + args.user_args
        processes.append(subprocess.Popen(cmd, env=env))

    if args.save_pid:
        pid_path = os.path.join(PID_FILE_BASEPATH,
                                f"ds_tpu_{args.save_pid}.pids")
        with open(pid_path, "w") as f:
            f.write(",".join(str(p.pid) for p in processes))

    def sigkill_handler(signum, frame):
        # any-rank-dies ⇒ whole local group dies (reference launch.py:313)
        for p in processes:
            if p.poll() is None:
                p.terminate()
        logger.error(f"launch: received signal {signum}, killed local group")
        sys.exit(1)

    signal.signal(signal.SIGTERM, sigkill_handler)
    signal.signal(signal.SIGINT, sigkill_handler)

    alive = set(range(len(processes)))
    exit_code = 0
    while alive:
        for i in sorted(alive):
            code = processes[i].poll()
            if code is None:
                continue
            alive.discard(i)
            if code != 0:
                logger.error(
                    f"launch: rank {global_rank_offset + i} exited with "
                    f"code {code}; terminating local group")
                for p in processes:
                    if p.poll() is None:
                        p.terminate()
                sys.exit(code)
        time.sleep(0.5)
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
