"""Per-node process launcher.

Analog of the reference's ``launcher/launch.py:132-251``: spawn one OS
process per local rank with the distributed env set, redirect logs, poll
children, and kill the whole local group if any child dies (the
``sigkill_handler``).  On TPU pods the common shape is ONE process per host
owning all local chips (JAX convention), so ``--nproc`` defaults to 1; the
multi-process-per-host mode exists for CPU simulation, one process per
chip, and the multi-process test harness (SURVEY §4's DistributedTest analog).

Env contract consumed by ``platform.accelerator.init_distributed``:
  DSTPU_COORDINATOR     coordinator address host:port (process 0's host)
  DSTPU_NUM_PROCESSES   global process count
  DSTPU_PROCESS_ID      this process's global id
  DSTPU_LOCAL_RANK      local rank on this node
  DSTPU_NODE_RANK       this node's rank
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def build_child_env(base: dict, *, coordinator: str, num_processes: int,
                    process_id: int, local_rank: int, node_rank: int,
                    slots: "list[int] | None" = None) -> dict:
    env = dict(base)
    env.update({
        "DSTPU_COORDINATOR": coordinator,
        "DSTPU_NUM_PROCESSES": str(num_processes),
        "DSTPU_PROCESS_ID": str(process_id),
        "DSTPU_LOCAL_RANK": str(local_rank),
        "DSTPU_NODE_RANK": str(node_rank),
    })
    if slots is not None:
        # Selected device slots (hostfile :slot filters). launch_local
        # enforces len(slots) == nproc, so each child owns exactly ONE
        # selected chip: pin it via libtpu's env BEFORE the interpreter
        # starts — the TPU analog of the reference exporting
        # CUDA_VISIBLE_DEVICES per rank (launcher/launch.py:221). Explicit
        # user pinning in the parent env wins.
        env["DSTPU_VISIBLE_SLOTS"] = ",".join(str(s) for s in slots)
        env["DSTPU_SLOT_ID"] = str(slots[local_rank])
        if not base.get("TPU_VISIBLE_CHIPS") and not base.get("TPU_VISIBLE_DEVICES"):
            env["TPU_VISIBLE_CHIPS"] = str(slots[local_rank])
            env.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    return env


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="dstpu-launch",
                                description="per-node process launcher")
    p.add_argument("--num_processes", type=int, default=None,
                   help="GLOBAL process count (hosts may have uneven slots); "
                        "default nnodes*nproc")
    p.add_argument("--proc_id_base", type=int, default=None,
                   help="global id of this node's first process; "
                        "default node_rank*nproc")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc", type=int, default=1,
                   help="processes on this node (JAX convention: 1/host)")
    p.add_argument("--coordinator", default="127.0.0.1:12321",
                   help="host:port of process 0's coordination service")
    p.add_argument("--slots", default=None,
                   help="comma list of device-slot ids selected for this "
                        "node (from hostfile include/exclude filters); "
                        "child i gets DSTPU_SLOT_ID=slots[i]")
    p.add_argument("--log_dir", default=None,
                   help="write per-rank logs here instead of inheriting stdio")
    p.add_argument("--module", action="store_true",
                   help="run script as a python module (python -m)")
    p.add_argument("script", help="training script to launch")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch_local(args) -> int:
    """Spawn ``nproc`` children, babysit them, return the first failure code."""
    num_processes = (args.num_processes if args.num_processes is not None
                     else args.nnodes * args.nproc)
    proc_id_base = (args.proc_id_base if args.proc_id_base is not None
                    else args.node_rank * args.nproc)
    cmd = [sys.executable]
    if args.module:
        cmd.append("-m")
    cmd.append(args.script)
    cmd += args.script_args

    children: list[subprocess.Popen] = []
    logs = []
    slots = ([int(s) for s in args.slots.split(",")]
             if getattr(args, "slots", None) else None)
    if slots is not None and len(slots) != args.nproc:
        raise SystemExit(
            f"dstpu-launch: {args.nproc} processes but {len(slots)} selected "
            f"slots ({slots}); refusing to oversubscribe/underuse device "
            "slots — adjust --nproc or the hostfile include/exclude filters")
    for local_rank in range(args.nproc):
        process_id = proc_id_base + local_rank
        env = build_child_env(os.environ, coordinator=args.coordinator,
                              num_processes=num_processes,
                              process_id=process_id, local_rank=local_rank,
                              node_rank=args.node_rank,
                              slots=slots)
        stdout = stderr = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            f = open(os.path.join(args.log_dir, f"rank_{process_id}.log"), "w")
            logs.append(f)
            stdout, stderr = f, subprocess.STDOUT
        children.append(subprocess.Popen(cmd, env=env, stdout=stdout,
                                         stderr=stderr))

    def _kill_all(signum=None, frame=None):
        for c in children:
            if c.poll() is None:
                c.terminate()
        deadline = time.time() + 10
        for c in children:
            try:
                c.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                c.kill()

    signal.signal(signal.SIGTERM, _kill_all)
    signal.signal(signal.SIGINT, _kill_all)

    rc = 0
    try:
        # Poll loop (reference launch.py polls children and sigkills the
        # group on any nonzero exit so no rank hangs on a dead collective).
        live = set(range(len(children)))
        while live:
            time.sleep(0.3)
            for i in sorted(live):
                code = children[i].poll()
                if code is None:
                    continue
                live.discard(i)
                if code != 0:
                    rc = rc or code
                    print(f"[dstpu-launch] rank {i} exited rc={code}; "
                          "terminating local group", file=sys.stderr, flush=True)
                    _kill_all()
                    live.clear()
                    break
    finally:
        for f in logs:
            f.close()
    return rc


def main(argv=None) -> None:
    sys.exit(launch_local(parse_args(argv)))


if __name__ == "__main__":
    main()
