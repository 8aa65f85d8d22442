"""Multi-host SPMD: the sharded block pipeline across OS-process hosts.

The reference scales validators per-process with no cross-node compute;
this framework's scale-out story is the opposite — ONE block pipeline
SPMD over a device mesh (parallel/sharded_eds.py). On a TPU pod the mesh
spans hosts: intra-host shards ride ICI, cross-host collectives ride DCN
(SURVEY §2.4/§5.8; the scaling-book recipe). Real multi-host hardware is
not available here, so this module proves the path the portable way:

  N OS processes x M virtual CPU devices each, joined into ONE global
  jax mesh via jax.distributed (Gloo collectives = the DCN stand-in),
  each process feeding only its LOCAL row shards of the ODS
  (multihost_utils.host_local_array_to_global_array) — the exact
  data-loading discipline a pod deployment uses: no host ever
  materializes another host's shard.

Entry points:
  worker_main(...)  — one host process (used by `multihost-dryrun`)
  spawn_dryrun(...) — driver: spawn N workers, compare every host's data
                      root against the single-host oracle, one JSON line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def worker_main(process_id: int, num_processes: int, coordinator: str,
                k: int, batch: int, devices_per_host: int) -> dict:
    """Run inside a worker process AFTER env setup (JAX_PLATFORMS=cpu,
    xla_force_host_platform_device_count): join the
    global mesh, feed local shards, run the pipeline, return the roots."""
    import jax

    jax.distributed.initialize(coordinator, num_processes=num_processes,
                               process_id=process_id)
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    from celestia_app_tpu.parallel import mesh as mesh_mod
    from celestia_app_tpu.parallel import sharded_eds
    from celestia_app_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

    mesh = mesh_mod.make_mesh(k=k)
    n_data = mesh.shape[DATA_AXIS]
    n_seq = mesh.shape[SEQ_AXIS]
    if batch % n_data != 0:
        raise ValueError(f"batch {batch} must divide over data axis {n_data}")

    # deterministic global workload; each host slices out ONLY the shards
    # it owns (host-local view), then promotes them to a global array —
    # the pod-scale data-loading discipline (no full-array broadcast)
    rng = np.random.default_rng(1234)
    global_ods = rng.integers(
        0, 256, size=(batch, k, k, 512), dtype=np.uint8
    )
    local_rows = mesh.local_mesh.shape[SEQ_AXIS] * (k // n_seq)
    local_data = mesh.local_mesh.shape[DATA_AXIS] * (
        batch // n_data
    )
    # which global (data, seq) block this host owns: derive from the first
    # local device's coordinates in the global mesh grid
    first_local = jax.local_devices()[0]
    grid = np.asarray(mesh.devices)
    pos = np.argwhere(grid == first_local)
    d0, s0 = int(pos[0][0]), int(pos[0][1])
    b_lo = d0 * (batch // n_data)
    r_lo = s0 * (k // n_seq)
    host_local = global_ods[b_lo:b_lo + local_data,
                            r_lo:r_lo + local_rows]
    ods = multihost_utils.host_local_array_to_global_array(
        host_local, mesh,
        P(DATA_AXIS, SEQ_AXIS, None, None),
    )

    run = sharded_eds.jitted_sharded_pipeline(mesh, k)
    t0 = time.monotonic()
    _eds, _rr, _cc, data_roots = run(ods)
    roots_local = multihost_utils.process_allgather(data_roots, tiled=True)
    elapsed = time.monotonic() - t0

    # single-host oracle on block 0 (every host computes + compares)
    from celestia_app_tpu.utils import fast_host

    _, _, _, oracle_root = fast_host.pipeline_fast(global_ods[0])
    roots = np.asarray(roots_local).reshape(-1, 32)[:batch]
    ok = bytes(roots[0]) == bytes(oracle_root)
    return {
        "process_id": process_id,
        "num_processes": num_processes,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "mesh": {"data": n_data, "seq": n_seq},
        "k": k,
        "batch": batch,
        "pipeline_s": round(elapsed, 3),
        "data_root_0": bytes(roots[0]).hex(),
        "matches_host_oracle": bool(ok),
    }


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(devices_per_host: int) -> dict:
    import re

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={devices_per_host}"
    prior = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in prior:
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, prior
        )
    else:  # preserve any other flags the caller composed
        env["XLA_FLAGS"] = (prior + " " + flag).strip()
    return env


def _run_workers(k: int, batch: int, num_processes: int,
                 devices_per_host: int, port: int,
                 timeout_s: float) -> list[dict]:
    import tempfile

    env = _worker_env(devices_per_host)
    procs, err_files = [], []
    for pid in range(num_processes):
        # stderr -> file, NOT a pipe: a later worker blocked on a full
        # stderr pipe would stop participating in collectives and wedge
        # the worker the driver is currently communicate()ing with
        ef = tempfile.NamedTemporaryFile(
            mode="w+", suffix=f".mh{pid}.err", delete=False
        )
        err_files.append(ef)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "celestia_app_tpu", "multihost-worker",
             "--process-id", str(pid),
             "--num-processes", str(num_processes),
             "--coordinator", f"127.0.0.1:{port}",
             "--k", str(k), "--batch", str(batch),
             "--devices-per-host", str(devices_per_host)],
            stdout=subprocess.PIPE, stderr=ef, env=env, text=True,
        ))
    outs = []
    deadline = time.monotonic() + timeout_s
    try:
        for p, ef in zip(procs, err_files):
            left = max(5.0, deadline - time.monotonic())
            out, _ = p.communicate(timeout=left)
            if p.returncode != 0:
                ef.seek(0)
                raise RuntimeError(
                    f"worker failed rc={p.returncode}: {ef.read()[-800:]}"
                )
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for ef in err_files:
            try:
                ef.close()
                os.unlink(ef.name)
            except OSError:
                pass
    return outs


def spawn_dryrun(k: int = 16, batch: int = 2, num_processes: int = 2,
                 devices_per_host: int = 4, port: int = 0,
                 timeout_s: float = 600.0) -> dict:
    """Spawn the workers and aggregate their verdicts (the driver side of
    `python -m celestia_app_tpu multihost-dryrun`).

    The cross-host agreement claim is grounded in the oracle: EVERY host
    independently recomputes block 0's data root with the CPU reference
    pipeline and compares it to the root the global mesh handed it — all
    hosts matching the same deterministic oracle IS agreement, with no
    tautological self-comparison."""
    if num_processes < 1:
        raise ValueError("num_processes must be >= 1")
    last_err: Exception | None = None
    for _attempt in range(2):  # the free-port pick can race other jobs
        chosen = port or _free_port()
        try:
            outs = _run_workers(k, batch, num_processes, devices_per_host,
                                chosen, timeout_s)
            break
        except RuntimeError as e:
            last_err = e
            if port:  # caller pinned the port: don't mask the failure
                raise
    else:
        raise last_err  # both attempts failed
    return {
        "num_processes": num_processes,
        "devices_per_host": devices_per_host,
        "global_devices": outs[0]["global_devices"],
        "mesh": outs[0]["mesh"],
        "k": k,
        "batch": batch,
        "pipeline_s": max(o["pipeline_s"] for o in outs),
        "all_hosts_match_oracle": all(
            o["matches_host_oracle"] for o in outs
        ),
    }
