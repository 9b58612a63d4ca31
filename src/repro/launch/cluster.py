"""Multi-host bootstrap for pod-scale runs.

On real hardware every host runs the SAME program (multi-controller SPMD):

  1. ``init_cluster()`` wires the hosts together (coordinator address from
     the scheduler's env: TPU_WORKER_HOSTNAMES / MEGASCALE_COORDINATOR /
     SLURM, or explicit flags);
  2. ``make_production_mesh(multi_pod=...)`` then sees the global device
     set and builds the (pod, data, model) mesh;
  3. the training loop is identical to launch/train.py — per-host data
     slices come from DataConfig(host_id=jax.process_index(),
     n_hosts=jax.process_count()).

Fault tolerance at this layer:
  * a failed host exits non-zero; the wrapper script (scripts/launch_pod.sh)
    relaunches the job, and launch/train.py auto-resumes from the last
    atomic checkpoint;
  * elastic restarts with a different host count reshard the checkpoint on
    restore (repro.checkpoint supports cross-mesh restore);
  * straggler mitigation is the paper's method: per-pod step times ->
    repro.runtime.RatioTable -> UnevenBatchPlanner microbatch counts; pods
    accumulate locally (no collectives) and join in one weighted
    all-reduce, so a slow pod never blocks lockstep collectives
    mid-accumulation.  The table persists via repro.runtime.RatioStore, so
    an elastic restart warm-starts from the last measured ratios.
"""

from __future__ import annotations

import os
from typing import Optional


def init_cluster(coordinator: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None) -> bool:
    """Initialize jax.distributed if a multi-host environment is detected.

    Returns True when distributed mode is active.  Safe to call on a
    single host (no-op).  A detected cluster whose initialization fails
    raises: carrying on as one host would train or serve on a fraction of
    the devices the job was given.
    """
    import jax

    coordinator = coordinator or os.environ.get("REPRO_COORDINATOR")
    num_processes = num_processes or _env_int("REPRO_NUM_PROCESSES")
    process_id = process_id or _env_int("REPRO_PROCESS_ID")

    # Scheduler-native autodetection (TPU pods, SLURM) works with no args.
    auto = any(v in os.environ for v in
               ("TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS",
                "SLURM_JOB_ID"))
    if coordinator is None and not auto:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def host_data_slice():
    """(host_id, n_hosts) for DataConfig."""
    import jax

    return jax.process_index(), jax.process_count()
