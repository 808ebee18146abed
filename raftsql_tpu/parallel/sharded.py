"""Multi-chip SPMD consensus: the cluster step sharded over a device mesh.

The reference scales by running each raft peer as its own OS process and
wiring them with HTTP streams (reference raft.go:248-266, Procfile:2-4).
The TPU-native design instead lays the whole multi-raft state onto a 2-D
`jax.sharding.Mesh`:

  * ``groups`` axis — the data-parallel analog.  Raft groups are
    embarrassingly parallel: each group's consensus math touches only its
    own rows, so sharding the ``G`` axis needs **zero** collectives.
  * ``peers`` axis — the model-parallel analog.  When one group's peers
    live on different chips, the per-tick message exchange (the reference's
    rafthttp `transport.Send`, raft.go:230) becomes a single
    ``jax.lax.all_to_all`` over ICI: the outbox's src→dst transpose, which
    is a pure data-layout change on one chip (core/cluster.py `deliver`),
    turns into the collective form of the same permutation.

This is BASELINE.json config 5 ("groups sharded over v5e-8, peer-vote
allreduce over ICI") — note the vote/match *reduction* itself stays inside
`peer_step` as dense math over the message-slot axis; what rides ICI is the
message exchange that feeds it.

Everything is built with `shard_map` so the per-device program is exactly
the single-chip `peer_step` vmapped over the local peer rows: one compiled
program, no per-group Python, collectives inserted only where the mesh
demands them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.core.state import I32, Inbox, PeerState, StepInfo
from raftsql_tpu.core.step import peer_step

PEERS_AXIS = "peers"
GROUPS_AXIS = "groups"


def make_mesh(n_peer_shards: int, n_group_shards: int,
              devices=None) -> Mesh:
    """Build the ('peers', 'groups') mesh over the first pp*gg devices."""
    import numpy as np

    devices = jax.devices() if devices is None else devices
    need = n_peer_shards * n_group_shards
    if len(devices) < need:
        raise ValueError(
            f"mesh {n_peer_shards}x{n_group_shards} needs {need} devices, "
            f"have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_peer_shards, n_group_shards)
    return Mesh(grid, (PEERS_AXIS, GROUPS_AXIS))


def _spec2() -> P:
    return P(PEERS_AXIS, GROUPS_AXIS)


def state_specs() -> PeerState:
    """PartitionSpec tree for a stacked PeerState (leaves [P, G, ...]).

    The trailing peer axis of votes/match/next_idx is the *message-slot*
    axis (all P peers of a group, as seen by one peer) — it is replicated,
    only the leading owner-peer axis is sharded.
    """
    s2, s3 = _spec2(), P(PEERS_AXIS, GROUPS_AXIS, None)
    return PeerState(
        term=s2, voted_for=s2, role=s2, leader_hint=s2,
        commit=s2, log_len=s2, log_term=s3,
        tbl_pos=s3, tbl_term=s3,
        elapsed=s2, timeout=s2, hb_elapsed=s2,
        votes=s3, match=s3, next_idx=s3,
        voters=s3, voters_joint=s3,
        resp_tick=s3, xfer_target=s2,
        rng=P(PEERS_AXIS), tick=P(PEERS_AXIS))


def inbox_specs() -> Inbox:
    s3 = P(PEERS_AXIS, GROUPS_AXIS, None)
    s4 = P(PEERS_AXIS, GROUPS_AXIS, None, None)
    return Inbox(
        v_type=s3, v_term=s3, v_last_idx=s3, v_last_term=s3, v_granted=s3,
        a_type=s3, a_term=s3, a_prev_idx=s3, a_prev_term=s3, a_n=s3,
        a_ents=s4, a_commit=s3, a_success=s3, a_match=s3)


def info_specs() -> StepInfo:
    s2 = _spec2()
    return StepInfo(
        commit=s2, role=s2, term=s2, voted_for=s2, leader_hint=s2,
        prop_base=s2, prop_accepted=s2, noop=s2,
        app_from=s2, app_start=s2, app_n=s2, app_conflict=s2,
        new_log_len=s2, lease=s2, xfer=s2,
        next_idx=P(PEERS_AXIS, GROUPS_AXIS, None),
        floor=s2, timer_margin=P(PEERS_AXIS))


def cluster_shardings(mesh: Mesh):
    """(PeerState, Inbox) trees of the NamedSharding each leaf lives
    under on `mesh`: what a jitted initialiser takes as `out_shardings`
    and a host array as its `device_put` target."""
    named = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    return named(state_specs()), named(inbox_specs())


def shard_cluster_arrays(mesh: Mesh, states: PeerState, inboxes: Inbox,
                         prop_n: jax.Array | None = None):
    """Place host-built stacked arrays onto the mesh with the right layout."""
    out = list(jax.device_put((states, inboxes), cluster_shardings(mesh)))
    if prop_n is not None:
        out.append(jax.device_put(prop_n, NamedSharding(mesh, _spec2())))
    return tuple(out)


def _route(outbox_leaf: jax.Array, n_peer_shards: int) -> jax.Array:
    """src→dst message exchange for one outbox leaf, local block view.

    Local shape [p_loc(src), G_loc, P(dst global), ...].  The swapaxes is
    the on-chip half of the permutation; the tiled all_to_all moves each
    destination block to its owner shard over ICI, yielding
    [p_loc(dst local), G_loc, P(src global), ...] — exactly the Inbox
    layout `peer_step` consumes.  With an unsharded peer axis this
    degenerates to core/cluster.py's `deliver` transpose.
    """
    x = jnp.swapaxes(outbox_leaf, 0, 2)
    if n_peer_shards > 1:
        x = jax.lax.all_to_all(x, PEERS_AXIS, split_axis=0, concat_axis=2,
                               tiled=True)
    return x


def make_sharded_step_fn(cfg: RaftConfig, mesh: Mesh):
    """The local-block step body (for composition inside shard_map).

    Validates divisibility, derives the per-shard config, and returns a
    function over LOCAL blocks: states [p_loc, G_loc, ...], inboxes
    [p_loc, G_loc, P, ...], prop_n [p_loc, G_loc], timer_inc [p_loc]
    (this peer block's slice of the global [P] per-peer timer advance —
    the same skew seam core/cluster.py cluster_step exposes, so chaos
    SkewWindow schedules and per-peer pacing express identically on the
    mesh).
    """
    pp = mesh.shape[PEERS_AXIS]
    gg = mesh.shape[GROUPS_AXIS]
    if cfg.num_peers % pp:
        raise ValueError(f"num_peers {cfg.num_peers} not divisible by "
                         f"peer shards {pp}")
    if cfg.num_groups % gg:
        raise ValueError(f"num_groups {cfg.num_groups} not divisible by "
                         f"group shards {gg}")
    local_cfg = dataclasses.replace(cfg, num_groups=cfg.num_groups // gg)
    p_loc = cfg.num_peers // pp

    def _step(states: PeerState, inboxes: Inbox, prop_n: jax.Array,
              timer_inc: jax.Array):
        pidx = jax.lax.axis_index(PEERS_AXIS)
        self_ids = (pidx * p_loc + jnp.arange(p_loc, dtype=I32)).astype(I32)
        goff = jax.lax.axis_index(GROUPS_AXIS) * local_cfg.num_groups
        new_states, outboxes, infos = jax.vmap(
            lambda st, ib, pn, sid, ti: peer_step(
                local_cfg, st, ib, pn, sid, goff, timer_inc=ti))(
                    states, inboxes, prop_n, self_ids, timer_inc)
        # Names as in core/cluster.py, for obs/scopes.py (metadata
        # only); the mesh's own collectives are `raft.mesh_reduce`.
        with jax.named_scope("raft.deliver"):
            delivered = jax.tree.map(lambda x: _route(x, pp), outboxes)
        # timer_margin is a per-(peer, group-shard) min; the host wants
        # the per-peer min over ALL groups, so reduce it over the group
        # axis here — that also makes the P(PEERS_AXIS) out_spec's
        # replication-over-groups claim true by construction.
        with jax.named_scope("raft.mesh_reduce"):
            infos = infos._replace(timer_margin=jax.lax.pmin(
                infos.timer_margin, GROUPS_AXIS))
        return new_states, delivered, infos

    _step.p_loc = p_loc
    return _step


def prop_spec() -> P:
    """PartitionSpec of the [P, G] per-tick proposal counts."""
    return _spec2()


def timer_spec() -> P:
    """PartitionSpec of the [P] per-peer timer advance vector: sharded
    with the owner-peer axis, replicated over groups."""
    return P(PEERS_AXIS)


def make_sharded_cluster_step(cfg: RaftConfig, mesh: Mesh):
    """Compile one whole-cluster LOCKSTEP tick SPMD over `mesh`.

    Returns jitted fn(states, inboxes, prop_n) -> (states, inboxes, infos)
    with every leaf sharded per {state,inbox,info}_specs.  Timers
    advance 1 per peer per tick; the durable mesh runtime uses
    `make_sharded_cluster_step_host`, which takes the per-peer vector.
    """
    step = make_sharded_step_fn(cfg, mesh)

    def _lockstep(states, inboxes, prop_n):
        return step(states, inboxes, prop_n,
                    jnp.ones((step.p_loc,), I32))

    mapped = jax.shard_map(
        _lockstep, mesh=mesh,
        in_specs=(state_specs(), inbox_specs(), _spec2()),
        out_specs=(state_specs(), inbox_specs(), info_specs()))
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_sharded_cluster_step_host(cfg: RaftConfig, mesh: Mesh):
    """The sharded tick with single-array host info, for the durable
    mesh runtime (runtime/mesh.py MeshClusterNode): same SPMD program
    as `make_sharded_cluster_step`, but StepInfo crosses the host
    boundary as ONE packed [P, G, INFO_NCOLS] i32 array (core/step.py
    pack_info) — the host plane (WAL, payload mirroring, publish)
    consumes identical columns whether the cluster runs fused on one
    chip or sharded over the mesh.

    Returns jitted fn(states, inboxes, prop_n, timer_inc[P]) ->
    (states, inboxes, packed_info, busy).  `timer_inc` is the per-peer
    timer advance (pass ones for lockstep); `busy` is the replicated
    scalar device-activity bit the fused runtime's idle parking keys on
    (core/cluster.py cluster_step_host): vote traffic, entry-carrying
    appends, or rejected append responses anywhere on the mesh."""
    from raftsql_tpu.config import MSG_REQ, MSG_RESP
    from raftsql_tpu.core.step import pack_info

    step = make_sharded_step_fn(cfg, mesh)

    def _step(states, inboxes, prop_n, timer_inc):
        states, ib, infos = step(states, inboxes, prop_n, timer_inc)
        busy = (jnp.any(ib.v_type != 0)
                | jnp.any((ib.a_type == MSG_REQ) & (ib.a_n > 0))
                | jnp.any((ib.a_type == MSG_RESP) & ~ib.a_success))
        # OR across every mesh shard: replicated scalar (out_spec P()).
        with jax.named_scope("raft.mesh_reduce"):
            busy = jax.lax.pmax(
                jax.lax.pmax(busy.astype(I32), PEERS_AXIS),
                GROUPS_AXIS) > 0
        with jax.named_scope("raft.pack"):
            packed = jax.vmap(pack_info)(infos)
        return states, ib, packed, busy

    mapped = jax.shard_map(
        _step, mesh=mesh,
        in_specs=(state_specs(), inbox_specs(), _spec2(), timer_spec()),
        out_specs=(state_specs(), inbox_specs(),
                   P(PEERS_AXIS, GROUPS_AXIS, None), P()))
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_sharded_cluster_run(cfg: RaftConfig, mesh: Mesh, num_ticks: int):
    """Compile a `num_ticks`-tick scan of the sharded step (device-resident).

    Returns jitted fn(states, inboxes, prop_n[T, P, G]) ->
    (states, inboxes, committed_total) where committed_total is a replicated
    scalar: the total number of log entries newly committed across ALL
    groups over the run (per-group max commit over peers, summed over
    groups, psum'd over the mesh) — so the benchmark harness moves exactly
    one scalar over the host boundary per run.
    """
    step = make_sharded_step_fn(cfg, mesh)

    def _run(states, inboxes, prop_n):
        ones = jnp.ones((step.p_loc,), I32)

        def group_commit(commit):   # [p_loc, G_loc] -> replicated-[G_loc]
            return jax.lax.pmax(jnp.max(commit, axis=0), PEERS_AXIS)

        commit0 = group_commit(states.commit)

        def body(carry, prop_t):
            st, ib = carry
            st, ib, _ = step(st, ib, prop_t, ones)
            return (st, ib), None

        (states, inboxes), _ = jax.lax.scan(
            body, (states, inboxes), prop_n, length=num_ticks)
        adv = jnp.sum(group_commit(states.commit) - commit0)
        total = jax.lax.psum(adv, GROUPS_AXIS)
        return states, inboxes, total

    return jax.jit(
        jax.shard_map(
            _run, mesh=mesh,
            in_specs=(state_specs(), inbox_specs(),
                      P(None, PEERS_AXIS, GROUPS_AXIS)),
            out_specs=(state_specs(), inbox_specs(), P())),
        donate_argnums=(0, 1))
