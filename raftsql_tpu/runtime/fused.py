"""FusedClusterNode — the durable co-located deployment.

The distributed runtime (runtime/node.py) runs one RaftNode per process
and pays one device dispatch and one readback per peer per tick — and a
chip belongs to one process, so P processes cannot share it at all.
When all P peers of every group are co-located on ONE chip — the
reference's Procfile cluster collapsed into a single host process — the
TPU-first shape is the fused cluster step (core/cluster.py): all P peers
× G groups advance in one compiled program, messages delivered by an
on-device transpose, and the host crosses the boundary once per tick
with a packed StepInfo.

Durability keeps the reference's per-batch contract (reference
raft.go:227-235: wal.Save → transport.Send → publish) with the dispatch
itself as the send barrier:

  messages composed at tick t are OBSERVED by their receivers only
  inside step t+1 — and the host does not dispatch step t+1 until every
  peer's tick-t appends and hard states are fsynced.

So a follower's success response (composed at t, seen by the leader at
t+1) never reaches the leader before the entries it acknowledges are
durable on the follower — exactly the raft requirement the reference
gets from saving before sending.  Publish (commit delivery to the apply
layer) happens after the same tick's save, before the next dispatch.

The durable host phase itself — propose queues, WAL + payload-log
writes, the fsync barrier, publish, membership apply-at-commit — lives
in runtime/hostplane.py (ClusterHostPlane), SHARED with the multi-chip
mesh runtime (runtime/mesh.py MeshClusterNode): the two runtimes differ
only in how `_device_step` dispatches the consensus math.

Scope (documented, not hidden): this runtime targets the co-located
steady state.  Followers that fall behind the device ring window are
served by the distributed runtime's host catch-up / InstallSnapshot
machinery, not here — a fused-mode follower outside the window waits
for the window to come back around (bounded lag under steady load).
Crash recovery is full per-peer WAL replay (reference raft.go:122-134).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp

from raftsql_tpu.core.cluster import (cluster_multistep_host,
                                      cluster_step_host)
# Re-exported for existing import sites (tests, tools): the host plane
# moved to runtime/hostplane.py in the mesh-runtime split.
from raftsql_tpu.runtime.hostplane import (_C,  # noqa: F401
                                           _read_committed_epoch,
                                           ClusterHostPlane)

__all__ = ["FusedClusterNode", "FusedPipe", "ClusterHostPlane",
           "PIPELINE_STEPS", "_C", "_read_committed_epoch",
           "MeshClusterNode"]

# The protocol's own depth, in consensus steps, and what the deployment
# that serves passes as `steps` (server/main.py): a proposal popped at
# the head of a dispatch is (1) accepted by its group's leader, (2)
# appended and acknowledged by the followers, (3) committed by the
# leader, (4) learnt by the peer whose stream is applied (FusedPipe:
# peer 0, which leads about a third of the groups).  The least S at
# which such a proposal shows as committed in the column the publish
# reads, `pinfo[-1][0][:, commit]`, whichever peer leads: at 3 only the
# groups peer 0 leads get there, and the rest wait a launch more.
PIPELINE_STEPS = 4


class FusedClusterNode(ClusterHostPlane):
    """The single-device durable runtime: ClusterHostPlane with the
    fused cluster step (core/cluster.py) as its device program —
    including the multi-step dispatch (`steps`, PIPELINE_STEPS where
    it serves) and the device busy bit that drives idle parking."""

    # Steady-state [P] i32 lockstep advance, built once: the None and
    # the skew branch must ship the SAME dtype/shape to the jitted step
    # or a mid-run skew schedule retraces it (and the recompile pause
    # can depose a healthy leader — the jit-stability invariant).
    _ti_ones = None

    def _device_step(self, prop_n: np.ndarray,
                     timer_inc: Optional[np.ndarray] = None):
        """Dispatch one cluster step; returns (packed-info device array,
        device busy bit).  `timer_inc` is the per-peer [P] timer advance
        (None = lockstep 1s, the steady-state fast path)."""
        if self._ti_ones is None:
            self._ti_ones = jnp.ones((self.cfg.num_peers,), jnp.int32)
        ti = self._ti_ones if timer_inc is None \
            else jnp.asarray(np.asarray(timer_inc, np.int32))
        if self._steps > 1:
            self.states, self.inboxes, pinfos_dev, busy = \
                cluster_multistep_host(self.cfg, self.states,
                                       self.inboxes, self._steps,
                                       jnp.asarray(prop_n), ti)
            return pinfos_dev, busy
        self.states, self.inboxes, pinfo_dev, busy = cluster_step_host(
            self.cfg, self.states, self.inboxes, jnp.asarray(prop_n), ti)
        return pinfo_dev, busy


class FusedPipe:
    """The propose/commit/error facade (reference raftpipe.go:3-17) over
    a ClusterHostPlane runtime (fused or mesh), so the whole SQL stack
    above consensus — RaftDB ack routing, HTTP API, CLI — serves from
    the co-located runtime unchanged.  Peer 0's commit stream is the
    apply plane: one process IS the cluster, so one local replica
    applies (the other peers' durability lives in their WALs; a restart
    replays any of them)."""

    def __init__(self, node: ClusterHostPlane):
        self.node = node
        # This facade is the only consumer and it reads peer 0's
        # stream; skip materializing the other peers' publishes.
        node.publish_peers = {0}
        self.commit_q = node.commit_q(0)

    def propose(self, group: int, payload: bytes,
                pid: Optional[int] = None,
                deadline_step: Optional[int] = None) -> None:
        # `pid` (client retry token) is accepted for facade parity and
        # dropped: fused proposals are routed on the host and never
        # forward-retried, so payloads travel PLAIN (no envelope to
        # carry the token — see runtime/db.py RAW_PLAIN contract).
        # `deadline_step` (overload plane, device-step units) rides to
        # the hostplane so expired work is shed before staging.
        self.node.propose_many(group, [payload],
                               deadline_step=deadline_step)

    @property
    def error(self) -> Optional[Exception]:
        return self.node.error

    def close(self) -> Optional[Exception]:
        self.node.stop()
        return self.node.error


def __getattr__(name):
    # Back-compat: MeshClusterNode lived here before the mesh runtime
    # became its own subsystem (runtime/mesh.py).  Lazy to avoid a
    # module cycle (mesh.py imports FusedPipe from this module).
    if name == "MeshClusterNode":
        from raftsql_tpu.runtime.mesh import MeshClusterNode
        return MeshClusterNode
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
