#!/usr/bin/env python
"""Multi-host SPMD launcher (the reference's ``scripts/launch.sh`` analog).

The reference wraps torchrun and exports the NVSHMEM bootstrap env; on TPU
the rendezvous is ``jax.distributed.initialize``, parameterized by three env
vars that ``triton_dist_tpu.runtime.mesh.initialize_distributed`` reads:
``COORDINATOR_ADDRESS``, ``NUM_PROCESSES``, ``PROCESS_ID``.

Two modes:

* **cluster** (one invocation per host — what a pod scheduler runs):

      python scripts/launch.py --coordinator host0:8476 --num-processes 4 \\
          --process-id $HOST_INDEX your_script.py [args...]

* **local** (spawn N processes on this host, CPU backend — the multi-process
  rendezvous smoke test; each process gets its own devices):

      python scripts/launch.py --local 2 your_script.py [args...]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", default=None, help="host:port of process 0")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local", type=int, default=None, metavar="N",
                    help="spawn N local processes (CPU rendezvous smoke mode)")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    ns = ap.parse_args()

    if ns.local:
        port = os.environ.get("TDT_LAUNCH_PORT")
        if port is None:
            # Ephemeral pick: back-to-back/concurrent --local jobs on one
            # host must not collide on a fixed rendezvous port.
            import socket

            with socket.socket() as s_:
                s_.bind(("127.0.0.1", 0))
                port = s_.getsockname()[1]
        port = int(port)
        procs = []
        for pid in range(ns.local):
            env = dict(os.environ)
            env.update(
                COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                NUM_PROCESSES=str(ns.local),
                PROCESS_ID=str(pid),
                JAX_PLATFORMS="cpu",
            )
            procs.append(subprocess.Popen([sys.executable, ns.script, *ns.args], env=env))
        # Wait on EVERY child (short-circuiting would orphan the rest in
        # rendezvous), then report the first failure.
        rcs = [p.wait() for p in procs]
        return next((rc for rc in rcs if rc), 0)

    if not (ns.coordinator and ns.num_processes is not None and ns.process_id is not None):
        ap.error("cluster mode needs --coordinator, --num-processes, --process-id")
    env = dict(os.environ)
    env.update(
        COORDINATOR_ADDRESS=ns.coordinator,
        NUM_PROCESSES=str(ns.num_processes),
        PROCESS_ID=str(ns.process_id),
    )
    return subprocess.call([sys.executable, ns.script, *ns.args], env=env)


if __name__ == "__main__":
    raise SystemExit(main())
