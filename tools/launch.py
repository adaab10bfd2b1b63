#!/usr/bin/env python
"""launch.py — multi-process/multi-host job launcher.

Reference: ``tools/launch.py`` over dmlc-tracker (ssh/mpi/sge/yarn/local
launchers spawning scheduler+server+worker processes with ``DMLC_*``
env).  The TPU build has no parameter servers: every process is a
worker, rendezvous runs through ``jax.distributed`` (the TPU runtime's
coordination service), so the launcher spawns N copies of the training
script with the coordinator address and process ids — and, like
dmlc-tracker, PROPAGATES FAILURE: the first worker that dies non-zero
tears the rest of the job down instead of leaving it hung on a
collective.

    # local: N worker processes on this machine (CPU devices, tests)
    python tools/launch.py -n 4 --launcher local python train.py ...

    # ssh: one worker per host listed in a hostfile
    python tools/launch.py -n 2 --launcher ssh -H hosts python train.py

    # tpu-vm: one worker per TPU-VM host (hostfile or
    # TPU_WORKER_HOSTNAMES metadata), jax.distributed env injected
    python tools/launch.py -n 4 --launcher tpu-vm -H hosts python train.py

    # gke: emit a kubectl-ready Indexed Job manifest (no cluster calls)
    python tools/launch.py -n 16 --launcher gke --gke-image IMG \
        --gke-output job.yaml python train.py ...

    # live elasticity: tell a RUNNING job (launched with --elastic-dir)
    # to re-form at a new size/plan without restarting — workers polling
    # the manifest migrate in memory (mxnet_tpu.parallel.elastic)
    python tools/launch.py -n 2 --scale-event --elastic-dir /shared/el \
        --plan data=2

Workers read MXNET_COORDINATOR / MXNET_NUM_WORKERS / MXNET_WORKER_ID and
call ``mxnet_tpu.parallel.init_distributed()`` (or pass them straight to
``jax.distributed.initialize``).  On real TPU pods the runtime provides
these automatically; the tpu-vm/gke modes exist for bring-up on plain
TPU-VM fleets and GKE clusters where nothing injects them for you.
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_propagating(procs, poll_s=0.2):
    """dmlc-tracker semantics: wait for all workers; the FIRST non-zero
    exit kills the remaining workers (a dead rank would otherwise hang
    every peer at its next collective) and becomes the job's rc."""
    rc = 0
    live = list(procs)
    try:
        while live:
            for p in list(live):
                ret = p.poll()
                if ret is None:
                    continue
                live.remove(p)
                if ret != 0 and rc == 0:
                    rc = ret
                    print("launch.py: worker pid %d exited %d; tearing "
                          "down %d remaining worker(s)"
                          % (p.pid, ret, len(live)), file=sys.stderr)
                    for q in live:
                        q.terminate()
            if live:
                time.sleep(poll_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return rc


def _worker_env(env, coordinator, num_workers, rank, elastic_dir=None):
    out = dict(env,
               MXNET_COORDINATOR=coordinator,
               MXNET_NUM_WORKERS=str(num_workers),
               MXNET_WORKER_ID=str(rank))
    if elastic_dir:
        out["MXNET_ELASTIC_DIR"] = elastic_dir
    return out


def launch_local(num_workers, command, env, elastic_dir=None):
    """Start ``num_workers`` copies of ``command`` on THIS host, joined
    through one ``jax.distributed`` coordinator.

    For CPU workers (the multi-process test tier).  It gives no worker
    a chip of its own: every worker inherits the same environment, so
    on a TPU host each one reaches for ALL chips, and a chip belongs to
    one process at a time — the second worker fails or hangs.  One
    process drives all chips of a host (``python chip_smoke.py --chips
    4`` does); do not use this launcher for that."""
    coordinator = "127.0.0.1:%d" % _free_port()
    procs = [subprocess.Popen(
        command, env=_worker_env(env, coordinator, num_workers, rank,
                                 elastic_dir=elastic_dir))
        for rank in range(num_workers)]
    return _wait_propagating(procs)


def emit_scale_event(directory, num_workers, plan=None, reason=""):
    """Publish a live-elasticity scale event for running workers to poll
    (``mxnet_tpu.parallel.elastic``): atomic rename of
    ``<dir>/scale_event.json`` with a monotonically increasing ``seq``.
    Deliberately stdlib-only and schema-identical to
    ``elastic.write_scale_event`` — the JSON file IS the contract, the
    same way the gke manifest is."""
    import json

    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "scale_event.json")
    seq = 0
    try:
        with open(path) as f:
            seq = int(json.load(f).get("seq", 0))
    except (OSError, ValueError):
        pass
    payload = {"seq": seq + 1, "num_workers": int(num_workers),
               "plan": plan or None, "reason": reason}
    tmp = path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    print("launch.py: published scale event seq %d (%d workers%s) to %s"
          % (payload["seq"], num_workers,
             ", plan %s" % plan if plan else "", path))
    return 0


def _read_hosts(hostfile, num_workers):
    if hostfile:
        with open(hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()
                     and not h.startswith("#")]
    elif os.environ.get("TPU_WORKER_HOSTNAMES"):
        # the TPU-VM metadata contract: comma-separated worker hosts
        hosts = os.environ["TPU_WORKER_HOSTNAMES"].split(",")
    else:
        raise SystemExit("need -H hostfile (or TPU_WORKER_HOSTNAMES for "
                         "tpu-vm)")
    if len(hosts) < num_workers:
        raise SystemExit("hostfile has %d hosts, need %d"
                         % (len(hosts), num_workers))
    return hosts


def launch_ssh(num_workers, hostfile, command, env, extra_env=()):
    hosts = _read_hosts(hostfile, num_workers)
    coordinator = "%s:%d" % (hosts[0], 29400)
    passthrough = " ".join(
        shlex.quote("%s=%s" % (k, v)) for k, v in env.items()
        if k.startswith(("MXNET_", "MXTPU_", "JAX_", "XLA_")))
    cmd = " ".join(shlex.quote(c) for c in command)
    procs = []
    for rank in range(num_workers):
        inject = ("MXNET_COORDINATOR=%s MXNET_NUM_WORKERS=%d "
                  "MXNET_WORKER_ID=%d" % (coordinator, num_workers, rank))
        inject += "".join(" %s" % shlex.quote(e) for e in extra_env)
        remote = ("cd %s && env %s %s %s"
                  % (shlex.quote(os.getcwd()), passthrough, inject, cmd))
        procs.append(subprocess.Popen(["ssh", hosts[rank], remote]))
    return _wait_propagating(procs)


def launch_tpu_vm(num_workers, hostfile, command, env):
    """One worker per TPU-VM host: ssh fan-out with the jax.distributed
    bring-up env injected directly (JAX_COORDINATOR_ADDRESS and friends
    are read by ``jax.distributed.initialize()`` with no arguments, so
    unmodified JAX scripts synchronize too, not only mxnet_tpu ones)."""
    hosts = _read_hosts(hostfile, num_workers)
    coordinator = "%s:%d" % (hosts[0], 8476)
    extra = ["JAX_COORDINATOR_ADDRESS=%s" % coordinator,
             "JAX_NUM_PROCESSES=%d" % num_workers]
    # per-rank JAX_PROCESS_ID rides through the generic injection below
    procs = []
    passthrough = " ".join(
        shlex.quote("%s=%s" % (k, v)) for k, v in env.items()
        if k.startswith(("MXNET_", "MXTPU_", "JAX_", "XLA_", "TPU_")))
    cmd = " ".join(shlex.quote(c) for c in command)
    for rank in range(num_workers):
        inject = ("MXNET_COORDINATOR=%s MXNET_NUM_WORKERS=%d "
                  "MXNET_WORKER_ID=%d JAX_PROCESS_ID=%d"
                  % (coordinator, num_workers, rank, rank))
        inject += "".join(" %s" % shlex.quote(e) for e in extra)
        remote = ("cd %s && env %s %s %s"
                  % (shlex.quote(os.getcwd()), passthrough, inject, cmd))
        procs.append(subprocess.Popen(["ssh", hosts[rank], remote]))
    return _wait_propagating(procs)


_GKE_TEMPLATE = """\
# generated by tools/launch.py --launcher gke — kubectl apply -f this.
# Indexed Job: N completions, one worker pod per index; the headless
# Service makes pod 0 resolvable as the jax.distributed coordinator.
apiVersion: v1
kind: Service
metadata:
  name: {name}-coord
spec:
  clusterIP: None
  selector:
    job-name: {name}
  ports:
  - port: {port}
---
apiVersion: batch/v1
kind: Job
metadata:
  name: {name}
spec:
  completions: {n}
  parallelism: {n}
  completionMode: Indexed
  backoffLimit: 0
  template:
    metadata:
      labels:
        job-name: {name}
    spec:
      subdomain: {name}-coord
      restartPolicy: Never
      containers:
      - name: worker
        image: {image}
        command: {command_json}
        env:
        - name: MXNET_WORKER_ID
          valueFrom:
            fieldRef:
              fieldPath: metadata.annotations['batch.kubernetes.io/job-completion-index']
        - name: JAX_PROCESS_ID
          valueFrom:
            fieldRef:
              fieldPath: metadata.annotations['batch.kubernetes.io/job-completion-index']
        - name: MXNET_NUM_WORKERS
          value: "{n}"
        - name: MXNET_COORDINATOR
          value: "{name}-0.{name}-coord:{port}"
        - name: JAX_COORDINATOR_ADDRESS
          value: "{name}-0.{name}-coord:{port}"
        - name: JAX_NUM_PROCESSES
          value: "{n}"
        resources:
          limits:
            google.com/tpu: {tpu_per_pod}
"""


def emit_gke(num_workers, command, image, name="mxtpu-train", port=8476,
             tpu_per_pod=4, output=None):
    """Emit a kubectl-ready Indexed Job manifest (the dmlc-tracker yarn
    role, GKE-shaped).  No cluster API calls: the manifest IS the
    deliverable, applied with kubectl by the operator."""
    import json as _json

    manifest = _GKE_TEMPLATE.format(
        name=name, n=num_workers, image=image, port=port,
        tpu_per_pod=tpu_per_pod, command_json=_json.dumps(command))
    if output:
        with open(output, "w") as f:
            f.write(manifest)
        print("wrote %s (kubectl apply -f %s)" % (output, output))
    else:
        print(manifest)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher",
                    choices=("local", "ssh", "tpu-vm", "gke"),
                    default="local")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference-CLI parity; dist_tpu_sync"
                         " has no parameter servers (ignored with a"
                         " warning)")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--gke-image", default=None,
                    help="container image for --launcher gke")
    ap.add_argument("--gke-name", default="mxtpu-train")
    ap.add_argument("--gke-tpu-per-pod", type=int, default=4)
    ap.add_argument("--gke-output", default=None,
                    help="write the Job manifest here (default: stdout)")
    ap.add_argument("--elastic-dir", default=None,
                    help="shared directory for live-elasticity scale "
                         "events; exported to workers as "
                         "MXNET_ELASTIC_DIR (see docs/fault_tolerance.md "
                         "'Live elasticity')")
    ap.add_argument("--scale-event", action="store_true",
                    help="instead of launching, publish a scale event to "
                         "--elastic-dir telling a RUNNING elastic job to "
                         "re-form at -n workers (optionally --plan)")
    ap.add_argument("--plan", default=None,
                    help="new parallel plan spec for --scale-event, e.g. "
                         "'data=2,model=2'")
    ap.add_argument("--scale-reason", default="launch.py --scale-event",
                    help="reason string recorded in the scale event")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.scale_event:
        if not args.elastic_dir:
            raise SystemExit("--scale-event needs --elastic-dir")
        sys.exit(emit_scale_event(args.elastic_dir, args.num_workers,
                                  plan=args.plan,
                                  reason=args.scale_reason))
    if getattr(args, "num_servers", 0):
        print("WARNING: -s/--num-servers ignored: dist_tpu_sync is SPMD "
              "(no parameter servers); launching workers only",
              file=sys.stderr)
    if not args.command:
        raise SystemExit("no command given")
    env = dict(os.environ)
    if args.elastic_dir:
        # ssh/tpu-vm inject via the MXNET_* passthrough; local via
        # _worker_env
        env["MXNET_ELASTIC_DIR"] = args.elastic_dir
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, args.command, env,
                              elastic_dir=args.elastic_dir))
    if args.launcher == "gke":
        if not args.gke_image:
            raise SystemExit("--launcher gke needs --gke-image")
        sys.exit(emit_gke(args.num_workers, args.command, args.gke_image,
                          name=args.gke_name,
                          tpu_per_pod=args.gke_tpu_per_pod,
                          output=args.gke_output))
    if args.launcher == "tpu-vm":
        sys.exit(launch_tpu_vm(args.num_workers, args.hostfile,
                               args.command, env))
    if args.hostfile is None:
        raise SystemExit("--launcher ssh needs -H hostfile")
    sys.exit(launch_ssh(args.num_workers, args.hostfile, args.command,
                        env))


if __name__ == "__main__":
    main()
