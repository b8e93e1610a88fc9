"""mxnet_tpu.cluster — multi-process launch/supervise/fault-inject harness.

Beyond-reference subsystem (docs/CLUSTER.md) closing ROADMAP's
"multi-process collective harness" item: the reference's dmlc-tracker
launched remote worker/server gangs; here the testable pod is N real
Python processes joined into one `jax.distributed` job on localhost.

Four pieces:

  - **launcher** (launcher.py): `ClusterLauncher` spawns the gang with
    per-rank CPU-device pinning + the Gloo CPU-collectives backend,
    streams rank-prefixed logs, enforces a wall-clock deadline, and
    reaps the whole tree when ranks wedge after a death. Multi-host
    via `MXNET_CLUSTER_HOSTS=host1:4,host2:4` / a hostfile: non-local
    ranks ride ssh carrying the DMLC env contract, rank 0's host is
    the coordinator.
  - **supervisor** (supervisor.py): the self-healing loop — on gang
    death it classifies the failure off the black boxes, decides
    restart-in-place vs shrink-to-(N−1) vs give-up (exit 44,
    `MXNET_SUPERVISE_MAX_RESTARTS`/`_BACKOFF_S` budget), relaunches
    from the last sealed checkpoint commit, and stamps
    restarts_total / mttr_s / shrink_events into telemetry.
  - **inject** (inject.py): `MXNET_CLUSTER_INJECT=<kill|hang|exit>@
    <point>[:rank][@<n>]` — named injection points threaded through
    dist.py and the cooperative checkpoint commit.
  - **selftest** (__main__.py): `python -m mxnet_tpu.cluster --selftest
    --nprocs 2` (the ci.sh quick smoke), `--supervise` for the
    self-healing phases (SIGKILL at N=3 → automatic recovery),
    `--matrix` for the full injection matrix including the
    kill-mid-cooperative-commit sha256-identity proof, `--bench` for
    the time from a SIGKILL to the resumed gang's first step.

The runtime-hardening half lives in `mxnet_tpu.dist`: timeout barriers,
`DistRankFailure` naming missing ranks, coordinated abort
(`MXNET_DIST_TIMEOUT_S` / `MXNET_DIST_RETRIES`).
"""
from __future__ import annotations

from .launcher import (ClusterLauncher, ClusterResult, RankProc,
                       cpu_collectives_available, free_port,
                       parse_host_spec, read_hostfile, LocalTransport,
                       SshTransport)
from .inject import (ACTIONS, ENV_VAR, INJECTION_POINTS, InjectSpec,
                     maybe_inject, parse_spec)
from .supervisor import (Supervisor, SupervisorResult, FailureInfo,
                         Decision, classify_result, decide, GIVEUP_EXIT)
from ..dist import DistRankFailure

__all__ = ["ClusterLauncher", "ClusterResult", "RankProc",
           "cpu_collectives_available", "free_port", "DistRankFailure",
           "parse_host_spec", "read_hostfile", "LocalTransport",
           "SshTransport", "Supervisor", "SupervisorResult",
           "FailureInfo", "Decision", "classify_result", "decide",
           "GIVEUP_EXIT", "ACTIONS", "ENV_VAR", "INJECTION_POINTS",
           "InjectSpec", "maybe_inject", "parse_spec"]
