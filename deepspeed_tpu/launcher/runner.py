"""`deepspeed` CLI runner — multi-host TPU job launcher.

Reference: deepspeed/launcher/runner.py:33-378 (hostfile `slots=N` parsing,
--include/--exclude resource filters, base64 world-info, PDSH/MPI multinode
backends). The UX is preserved; the execution model is TPU-native:

* a "slot" is a host-local device (TPU chip); JAX is single-controller
  PER HOST — one Python process per host, not one per device (contrast
  reference launch.py:122-157 spawning one proc per GPU).
* rendezvous is jax.distributed's coordinator (first host:port), exported
  as DSTPU_COORDINATOR / DSTPU_NUM_PROCESSES / DSTPU_PROCESS_ID and
  consumed by comm.dist.init_distributed.
* multinode backends: pdsh (parallel ssh fan-out) or mpirun, selected by
  availability exactly like the reference's PDSH/OpenMPI runners.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import shutil
import subprocess
import sys
from collections import OrderedDict
from typing import Dict, List, Optional

from ..constants import TORCH_DISTRIBUTED_DEFAULT_PORT
from ..utils.logging import logger

DLTS_HOSTFILE = "/job/hostfile"  # reference runner.py:26
EXPORT_ENVS = ["PYTHON", "PATH", "LD_LIBRARY", "XLA_", "JAX_", "TPU_",
               "DSTPU_"]
DEEPSPEED_ENVIRONMENT_NAME = ".deepspeed_env"


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="deepspeed_tpu launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="hostfile: `hostname slots=N` per line")
    parser.add_argument("-i", "--include", type=str, default="",
                        help='e.g. "worker-0@worker-1:0,2"')
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help='e.g. "worker-1:0"')
    parser.add_argument("--num_nodes", type=int, default=-1)
    parser.add_argument("--num_gpus", "--num_chips", type=int, default=-1,
                        dest="num_gpus")
    parser.add_argument("--master_port", type=int,
                        default=TORCH_DISTRIBUTED_DEFAULT_PORT)
    parser.add_argument("--master_addr", type=str, default="")
    parser.add_argument("--launcher", type=str, default="pdsh",
                        choices=["pdsh", "openmpi", "mvapich", "local"])
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def fetch_hostfile(hostfile_path: str) -> Optional[Dict[str, int]]:
    """reference runner.py:84-116: `hostname slots=N` lines -> ordered
    {host: slots}. None when the file doesn't exist (single-node mode)."""
    if not os.path.isfile(hostfile_path):
        return None
    resource_pool: "OrderedDict[str, int]" = OrderedDict()
    with open(hostfile_path, "r") as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                hostname, slots = line.split()
                _, slot_count = slots.split("=")
                slot_count = int(slot_count)
            except ValueError:
                raise ValueError(f"hostfile has bad format: {line!r}")
            if hostname in resource_pool:
                raise ValueError(f"host {hostname} repeated in hostfile")
            resource_pool[hostname] = slot_count
    return resource_pool


def _parse_inclusion_exclusion(resource_pool, inclusion, exclusion):
    active: "OrderedDict[str, List[int]]" = OrderedDict()
    for host, slots in resource_pool.items():
        active[host] = list(range(slots))
    return parse_resource_filter(active, include_str=inclusion,
                                 exclude_str=exclusion)


def parse_resource_filter(host_info, include_str="", exclude_str=""):
    """reference runner.py:119-186: `host1@host2:0,2` selection strings.
    Only one of include/exclude may be set."""
    if include_str and exclude_str:
        raise ValueError("include_str and exclude_str are mutually exclusive")
    filtered: "OrderedDict[str, List[int]]" = OrderedDict()
    if not include_str and not exclude_str:
        return host_info

    spec = include_str or exclude_str
    parsed: Dict[str, Optional[List[int]]] = OrderedDict()
    for term in spec.split("@"):
        term = term.strip()
        if ":" in term:
            host, slots = term.split(":")
            parsed[host] = [int(s) for s in slots.split(",")]
        else:
            parsed[term] = None  # whole host

    for host, slot_filter in parsed.items():
        if host not in host_info:
            raise ValueError(f"host {host!r} not in resource pool")
        if slot_filter is not None:
            for s in slot_filter:
                if s not in host_info[host]:
                    raise ValueError(f"slot {s} not on host {host!r}")

    if include_str:
        for host, slot_filter in parsed.items():
            filtered[host] = (list(slot_filter) if slot_filter is not None
                              else list(host_info[host]))
    else:
        for host, slots in host_info.items():
            if host not in parsed:
                filtered[host] = list(slots)
            else:
                slot_filter = parsed[host]
                if slot_filter is None:
                    continue  # whole host excluded
                keep = [s for s in slots if s not in slot_filter]
                if keep:
                    filtered[host] = keep
    return filtered


def encode_world_info(world_info: Dict[str, List[int]]) -> str:
    """reference runner.py:198-203: json -> base64 (shell-safe)."""
    return base64.urlsafe_b64encode(
        json.dumps(world_info).encode()).decode()


def decode_world_info(encoded: str) -> Dict[str, List[int]]:
    return json.loads(base64.urlsafe_b64decode(encoded.encode()).decode())


def _export_env_lines() -> List[str]:
    """Env vars to propagate to remote hosts (reference EXPORT_ENVS +
    ~/.deepspeed_env, runner.py:27-29,289-309)."""
    exports = []
    for key, val in os.environ.items():
        if any(key.startswith(p) for p in EXPORT_ENVS):
            exports.append(f"export {key}={val}")
    env_file = os.path.join(os.path.expanduser("~"),
                            DEEPSPEED_ENVIRONMENT_NAME)
    if os.path.isfile(env_file):
        with open(env_file) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    exports.append(f"export {line}")
    return exports


def _probe_local_slots() -> int:
    """Local device count WITHOUT initializing jax in this process (TPU
    runtime allows one owner process; the trainer child must be it)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.local_device_count())"],
        capture_output=True, text=True, timeout=120)
    if out.returncode != 0:
        # a child that cannot reach its devices is not "one slot"
        raise RuntimeError(
            f"cannot count local devices: the probe exited "
            f"{out.returncode}: {out.stderr.strip()[-500:]}")
    return int(out.stdout.strip().splitlines()[-1])


def _is_local_host(host: str) -> bool:
    import socket

    if host in ("localhost", "127.0.0.1"):
        return True
    try:
        return host in (socket.gethostname(), socket.getfqdn())
    except Exception:
        return False


def build_local_cmd(args, world_info_b64: str,
                    node_rank: int = 0) -> List[str]:
    cmd = [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
           f"--world_info={world_info_b64}",
           f"--master_addr={args.master_addr or '127.0.0.1'}",
           f"--master_port={args.master_port}",
           f"--node_rank={node_rank}",
           args.user_script] + args.user_args
    return cmd


def _local_node_rank(active_resources) -> int:
    """This host's position in the active host list (for --launcher local
    run per-host against a multinode hostfile); 0 if not found."""
    for i, host in enumerate(active_resources):
        if _is_local_host(host):
            return i
    return 0


def build_pdsh_cmd(args, active_resources, world_info_b64: str):
    """reference multinode_runner.py:35-77 PDSHRunner."""
    os.environ["PDSH_RCMD_TYPE"] = "ssh"
    hosts = ",".join(active_resources.keys())
    exports = "; ".join(_export_env_lines())
    launch = (f"cd {os.path.abspath('.')}; "
              + (exports + "; " if exports else "")
              + f"{sys.executable} -u -m deepspeed_tpu.launcher.launch "
              f"--world_info={world_info_b64} "
              f"--master_addr={args.master_addr} "
              f"--master_port={args.master_port} "
              f"--node_rank=%n "
              + args.user_script + " " + " ".join(args.user_args))
    return ["pdsh", "-S", "-f", "1024", "-w", hosts, launch]


def _write_hostfile(active_resources, line_fmt: str) -> str:
    """Filtered temp hostfile with ONE entry per active host
    (single-controller: one proc per host); the user's hostfile may
    contain excluded hosts and slots=N entries that would let the MPI
    stack ranks on one box.  Removed at interpreter exit (the launcher
    process outlives the mpirun it spawns)."""
    import atexit
    import tempfile

    fh = tempfile.NamedTemporaryFile(
        "w", prefix="dstpu_hostfile_", suffix=".txt", delete=False)
    for host in active_resources:
        fh.write(line_fmt.format(host=host))
    fh.close()
    atexit.register(lambda p=fh.name: os.path.exists(p) and os.remove(p))
    return fh.name


def build_mpi_cmd(args, active_resources, world_info_b64: str):
    """reference multinode_runner.py:80-121 OpenMPIRunner: one proc per
    HOST (TPU single-controller), not per slot."""
    nprocs = len(active_resources)
    hostfile = _write_hostfile(active_resources, "{host} slots=1\n")
    cmd = ["mpirun", "-n", str(nprocs), "-hostfile", hostfile,
           "--mca", "btl", "^openib"]
    for line in _export_env_lines():
        cmd += ["-x", line.split("=", 1)[0].replace("export ", "")]
    cmd += [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
            f"--world_info={world_info_b64}",
            f"--master_addr={args.master_addr}",
            f"--master_port={args.master_port}",
            "--node_rank=-1",  # from OMPI env
            args.user_script] + args.user_args
    return cmd


def build_mvapich_cmd(args, active_resources, world_info_b64: str):
    """reference multinode_runner.py MVAPICHRunner: mpirun_rsh with
    ENV=VAL forwarding and a bare host-per-line hostfile; one proc per
    HOST (TPU single-controller), rank from MV2_COMM_WORLD_RANK."""
    import shlex

    nprocs = len(active_resources)
    hostfile = _write_hostfile(active_resources, "{host}\n")
    cmd = ["mpirun_rsh", "-np", str(nprocs), "-hostfile", hostfile]
    # mpirun_rsh takes ENV=VAL pairs before the executable.  A bare KEY
    # line (export-by-name, valid for the OpenMPI -x path) would be
    # parsed as the remote executable — skip it.  Values with whitespace
    # (multi-flag XLA_FLAGS) would shatter when mpirun_rsh re-joins the
    # command line — those ride a shell-quoted env(1) prefix instead
    # (remote start goes through ssh, so the remote shell re-parses the
    # joined line and the quoting survives).
    spaced = []
    for ln in _export_env_lines():
        pair = ln.replace("export ", "", 1)
        if "=" not in pair:
            logger.warning(
                f"mvapich launcher: skipping bare env line (no '='): "
                f"{pair!r} — export it as KEY=VALUE in ~/.deepspeed_env")
            continue
        if any(c in pair for c in " \t"):
            spaced.append(pair)
        else:
            cmd.append(pair)
    if spaced:
        cmd += ["/usr/bin/env"] + [shlex.quote(p) for p in spaced]
    cmd += [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
            f"--world_info={world_info_b64}",
            f"--master_addr={args.master_addr}",
            f"--master_port={args.master_port}",
            "--node_rank=-1",  # from MV2 env
            args.user_script] + args.user_args
    return cmd


def main(args=None):
    args = parse_args(args)
    resource_pool = fetch_hostfile(args.hostfile)

    if not resource_pool:
        # single node (reference runner.py:312-340). Slot probe runs in a
        # THROWAWAY subprocess: importing jax here would take the
        # per-process TPU lock and starve the spawned trainer.
        slots = args.num_gpus if args.num_gpus > 0 else _probe_local_slots()
        world_info = {"localhost": list(range(slots))}
        cmd = build_local_cmd(args, encode_world_info(world_info))
        logger.info(f"cmd = {' '.join(cmd)}")
        result = subprocess.Popen(cmd, env=os.environ.copy())
        result.wait()
        return result.returncode

    active = _parse_inclusion_exclusion(resource_pool, args.include,
                                        args.exclude)
    if args.num_nodes > 0:
        active = OrderedDict(list(active.items())[:args.num_nodes])
    if args.num_gpus > 0:
        active = OrderedDict(
            (h, s[:args.num_gpus]) for h, s in active.items())
    if not args.master_addr:
        args.master_addr = list(active.keys())[0]

    world_info_b64 = encode_world_info(active)
    # hostfile-backed pools are multinode unless the single active host IS
    # this machine (a lone remote host must still be reached via ssh)
    multi = (args.force_multi or len(active) > 1
             or not _is_local_host(next(iter(active))))
    if not multi or args.launcher == "local":
        # --launcher local against a multinode hostfile is run once per
        # host; each host derives its own node rank from its hostfile slot
        cmd = build_local_cmd(args, world_info_b64,
                              node_rank=_local_node_rank(active))
    elif args.launcher == "pdsh" and shutil.which("pdsh"):
        cmd = build_pdsh_cmd(args, active, world_info_b64)
    elif args.launcher == "openmpi" and shutil.which("mpirun"):
        cmd = build_mpi_cmd(args, active, world_info_b64)
    elif args.launcher == "mvapich" and shutil.which("mpirun_rsh"):
        cmd = build_mvapich_cmd(args, active, world_info_b64)
    elif args.launcher == "pdsh" and shutil.which("mpirun"):
        # pdsh requested but absent; mpirun present — usable fallback
        logger.warning("pdsh not found; falling back to mpirun")
        cmd = build_mpi_cmd(args, active, world_info_b64)
    else:
        missing = {"pdsh": "pdsh (or mpirun)", "openmpi": "mpirun",
                   "mvapich": "mpirun_rsh"}.get(args.launcher,
                                                "pdsh/mpirun")
        raise RuntimeError(
            f"launcher {args.launcher!r} unavailable ({missing} not "
            f"found) — install it or use --launcher local on each host")
    logger.info(f"cmd = {' '.join(cmd)}")
    result = subprocess.Popen(cmd, env=os.environ.copy())
    result.wait()
    return result.returncode


if __name__ == "__main__":
    sys.exit(main())
