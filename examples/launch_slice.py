"""One-command multi-host launcher for a TPU slice.

The runnable replacement for the reference's Ray-autoscaler YAMLs
(reference: benchmarks/cluster.yaml:1-183, examples/horovod/cluster.yaml:
1-105): where the reference provisions AWS nodes and `ray exec`s the
benchmark onto them, a TPU slice's hosts already exist — this script fans
`examples/jax_train_shuffle.py --distributed` out to every host of the
slice, wires up the JAX coordination service and the RSDL_HOSTS shuffle
endpoints, waits for completion, and gathers each host's stats CSV into
one local directory.

Usage (SSH mode — real slice; run from any machine that can SSH to the
hosts, e.g. with `gcloud compute tpus tpu-vm ssh` configured hosts):

    RSDL_HOSTS="10.0.0.2:18515,10.0.0.3:18515" \\
    python examples/launch_slice.py \\
        --ssh user@tpu-host-0,user@tpu-host-1 \\
        --repo /home/user/ray_shuffling_data_loader_tpu \\
        --out ./slice_stats \\
        -- --num-rows 2000000 --num-files 16 --num-epochs 4 \\
           --batch-size 131072

Usage (local mode — smoke the whole control flow with N processes on
this machine, no SSH):

    RSDL_HOSTS="127.0.0.1:18515,127.0.0.1:18516" \\
    python examples/launch_slice.py --local --out /tmp/slice_stats \\
        -- --tiny-model --num-rows 4000 --num-files 2 \\
           --num-epochs 2 --batch-size 500

Local mode pins every trainer to the CPU backend itself: a chip belongs
to one process, so N trainers on one machine cannot share it.

Everything after ``--`` is passed through to jax_train_shuffle.py
verbatim. ``--distributed`` and ``--stats-dir`` are appended
automatically. RSDL_HOSTS (host:port shuffle endpoints, one per host,
ordered by process index) defines the world; host i gets
``JAX_PROCESS_ID=i``, and host 0's address (with ``--coordinator-port``)
is the JAX coordination service.
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
import threading


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--ssh", type=str, default=None,
                   help="Comma-separated SSH targets, one per RSDL_HOSTS "
                        "entry, same order (user@host or a Host alias "
                        "from ~/.ssh/config)")
    p.add_argument("--local", action="store_true",
                   help="Run every 'host' as a local process instead of "
                        "over SSH (smoke mode)")
    p.add_argument("--repo", type=str, default=None,
                   help="Repo checkout path on the remote hosts "
                        "(default: this repo's path, assumed identical)")
    p.add_argument("--out", type=str, default="./slice_stats",
                   help="Local directory to gather per-host stats CSVs")
    p.add_argument("--coordinator-port", type=int, default=8476,
                   help="JAX coordination-service port on host 0")
    p.add_argument("--remote-stats-dir", type=str,
                   default="/tmp/rsdl_slice_stats",
                   help="Where each host writes its CSV before gathering")
    p.add_argument("--python", type=str, default="python3",
                   help="Python interpreter on the hosts")
    if argv is None:
        argv = sys.argv[1:]
    # Everything after a literal "--" goes to jax_train_shuffle.py verbatim
    # (argparse would otherwise reject the dashed passthrough flags).
    train_args: list = []
    if "--" in argv:
        split = argv.index("--")
        argv, train_args = argv[:split], argv[split + 1:]
    args = p.parse_args(argv)
    args.train_args = train_args
    return args


def _stream(proc: subprocess.Popen, tag: str) -> None:
    for line in proc.stdout:
        sys.stdout.write(f"[{tag}] {line}")
        sys.stdout.flush()


def main(argv=None) -> int:
    args = parse_args(argv)
    hosts_env = os.environ.get("RSDL_HOSTS")
    if not hosts_env:
        print("RSDL_HOSTS is required: comma-separated host:port shuffle "
              "endpoints, one per slice host, ordered by process index",
              file=sys.stderr)
        return 2
    endpoints = [h.strip() for h in hosts_env.split(",") if h.strip()]
    world = len(endpoints)
    if args.local and args.ssh:
        print("--local and --ssh are mutually exclusive", file=sys.stderr)
        return 2
    ssh_targets = None
    if not args.local:
        if not args.ssh:
            print("need --ssh targets (or --local for smoke mode)",
                  file=sys.stderr)
            return 2
        ssh_targets = [t.strip() for t in args.ssh.split(",") if t.strip()]
        if len(ssh_targets) != world:
            print(f"--ssh lists {len(ssh_targets)} targets but RSDL_HOSTS "
                  f"has {world} endpoints", file=sys.stderr)
            return 2

    repo_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    remote_repo = args.repo or repo_dir
    coordinator = (f"{endpoints[0].rsplit(':', 1)[0]}:"
                   f"{args.coordinator_port}")
    # Children run with cwd=repo_dir, so a relative --out must be resolved
    # against the LAUNCHER's cwd or local-mode CSVs land inside the repo.
    args.out = os.path.abspath(args.out)
    os.makedirs(args.out, exist_ok=True)

    procs = []
    for i in range(world):
        stats_dir = (os.path.join(args.out, f"host_{i}") if args.local
                     else args.remote_stats_dir)
        env_pairs = {
            "RSDL_HOSTS": hosts_env,
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(world),
            "JAX_PROCESS_ID": str(i),
        }
        csv_name = f"host_{i}_epochs.csv"
        train_cmd = [
            args.python, "examples/jax_train_shuffle.py", "--distributed",
            "--stats-dir", stats_dir, *args.train_args,
        ]
        if args.local:
            env = dict(os.environ, **env_pairs,
                       JAX_PLATFORMS="cpu",
                       PYTHONPATH=repo_dir + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            env.setdefault("PYTHONUNBUFFERED", "1")
            proc = subprocess.Popen(
                [sys.executable] + train_cmd[1:], cwd=repo_dir, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
        else:
            exports = " ".join(f"{k}={shlex.quote(v)}"
                               for k, v in env_pairs.items())
            # Remove this host's CSV from any previous run first, so the
            # gather below can never pick up stale data.
            stale = shlex.quote(os.path.join(stats_dir, csv_name))
            remote = (f"cd {shlex.quote(remote_repo)} && rm -f {stale} && "
                      f"{exports} "
                      + " ".join(shlex.quote(c) for c in train_cmd))
            # -tt forces a pty: killing the local ssh client then HUPs the
            # remote session, so "stop them (peer failed)" actually stops
            # the remote trainer instead of only the local client.
            # stdin=DEVNULL: -tt must not adopt (and raw-mode) the
            # launcher's own tty — killing ssh on the peer-failure path
            # would leave the user's terminal without echo.
            proc = subprocess.Popen(
                ["ssh", "-tt", "-o", "BatchMode=yes", ssh_targets[i],
                 remote],
                stdin=subprocess.DEVNULL,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
        t = threading.Thread(target=_stream, args=(proc, f"host {i}"),
                             daemon=True, name=f"rsdl-slice-stream-{i}")
        t.start()
        procs.append((proc, t))

    # Poll ALL hosts rather than wait in order: one host dying early (bad
    # --repo, import error) strands the rest on collectives, and an
    # in-order wait on host 0 would never observe the failure.
    import time as _time
    rc = 0
    running = dict(enumerate(procs))
    while running:
        for i in list(running):
            proc, t = running[i]
            if proc.poll() is None:
                continue
            del running[i]
            t.join(timeout=10)
            if proc.returncode != 0:
                print(f"[launcher] host {i} exited rc={proc.returncode}",
                      file=sys.stderr)
                rc = rc or proc.returncode
        if rc and running:
            # One dead host strands the others on collectives — stop them.
            for i, (proc, _) in running.items():
                print(f"[launcher] stopping host {i} (peer failed)",
                      file=sys.stderr)
                proc.kill()
        if running:
            _time.sleep(0.2)
    if rc:
        return rc

    if not args.local:
        # Gather every host's CSVs next to each other locally.
        for i, target in enumerate(ssh_targets):
            dest = os.path.join(args.out, f"host_{i}")
            os.makedirs(dest, exist_ok=True)
            # Copy only THIS run's file for THIS rank (never a stale
            # leftover from a previous run with more hosts).
            gather = subprocess.run(
                ["scp", "-o", "BatchMode=yes",
                 f"{target}:{args.remote_stats_dir}/host_{i}_epochs.csv",
                 dest],
                capture_output=True, text=True)
            if gather.returncode != 0:
                print(f"[launcher] gather from host {i} failed: "
                      f"{gather.stderr.strip()}", file=sys.stderr)
                rc = rc or gather.returncode
    print(f"[launcher] done; stats under {args.out}/host_*/")
    return rc


if __name__ == "__main__":
    sys.exit(main())
