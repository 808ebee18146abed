"""Struct-of-arrays raft state for one peer across all groups.

The reference keeps one raft group's state inside the vendored etcd/raft
`raft.Node` object (reference raft.go:48-55).  The TPU-native design replaces
that object with flat int32 arrays batched over the group axis `G`, so that
the per-tick transition of *every* group advances in one XLA computation.

All log positions are 1-based: index 0 is the sentinel "before the log"
position with term 0 (this makes the AppendEntries log-matching check on
`prev_index == 0` fall out of ordinary array math).  The on-device log keeps
only entry *terms* in a ring of capacity W — entry payloads (SQL text) live
host-side in `storage.log`; the device decides ordering/commit, the host owns
bytes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from raftsql_tpu.config import (FOLLOWER, NO_LEADER, NO_VOTE, NO_XFER,
                                RaftConfig)

I32 = jnp.int32
B = jnp.bool_


class PeerState(NamedTuple):
    """Raft state of ONE peer, batched over G groups.

    Shapes:  [G] unless noted.  `match`/`next_idx`/`votes` are the leader /
    candidate views over the peer axis, [G, P].  `log_term` is the log
    metadata ring, [G, W].
    """

    term: jax.Array          # [G] i32 current term
    voted_for: jax.Array     # [G] i32 peer voted for this term, NO_VOTE if none
    role: jax.Array          # [G] i32 FOLLOWER/CANDIDATE/LEADER/PRECANDIDATE
    leader_hint: jax.Array   # [G] i32 last known leader, NO_LEADER if unknown

    commit: jax.Array        # [G] i32 highest committed log index
    log_len: jax.Array       # [G] i32 highest appended log index
    log_term: jax.Array      # [G, W] i32 ring: term of entry i at (i-1) % W

    # Term-transition table: the step's authoritative source for
    # term-of-position reads (the ring above stays write-only in the hot
    # path, serving the windowed/pallas commit rules and test oracles).
    # Slot k (valid iff tbl_pos[k] > 0) says: entries from position
    # tbl_pos[k] up to the next transition carry term tbl_term[k].  Valid
    # slots are right-aligned and ascending in position; slot K-1 always
    # holds the newest transition of a non-empty log.  Terms are known
    # for positions in [tbl_floor(tbl_pos, log_len), log_len]; reads
    # below the floor are guarded exactly like reads that slid out of
    # the W ring (reject + host catch-up).
    tbl_pos: jax.Array       # [G, K] i32 transition start positions
    tbl_term: jax.Array      # [G, K] i32 term starting at tbl_pos[k]

    # Timers (in ticks).
    elapsed: jax.Array       # [G] i32 ticks since last heartbeat/vote grant
    timeout: jax.Array       # [G] i32 randomized election timeout in ticks
    hb_elapsed: jax.Array    # [G] i32 leader ticks since last broadcast

    # Candidate view: votes granted to us this term.
    votes: jax.Array         # [G, P] bool

    # Leader view of each peer (raft Figure 2 volatile leader state).
    match: jax.Array         # [G, P] i32 highest index known replicated on peer
    next_idx: jax.Array      # [G, P] i32 next index to send to peer

    # Active membership configuration as DEVICE data (raftsql_tpu/
    # membership/): which of the P peer slots are voters, per group.
    # `voters_joint` is the OLD voter set while a joint C_old,new config
    # change is in flight (commit/election need a majority of BOTH
    # masks); in the stable state it equals `voters`, degenerating the
    # double-majority to the single one.  Slots outside both masks are
    # learners/spares: they receive AppendEntries and InstallSnapshot
    # but contribute nothing to any quorum and never campaign.  The
    # step only READS these; the host patches them (set_group_config)
    # when a committed conf-change entry applies.
    voters: jax.Array        # [G, P] bool
    voters_joint: jax.Array  # [G, P] bool

    # Leader-lease evidence (config.lease_ticks, core/step.py lease
    # phase): device step at which the newest CURRENT-term append
    # response from each peer was processed while this peer led the
    # group (0 = none).  Strictly an OUTPUT of consensus — no other
    # transition reads it, so carrying it (even disabled) can never
    # perturb a trajectory.  Deliberately volatile: a restart starts
    # from zeros, so a rebooted leader holds no lease until a fresh
    # quorum round confirms it.
    resp_tick: jax.Array     # [G, P] i32

    # Leadership-transfer latch (raft thesis §3.10, core/step.py transfer
    # phase): peer slot this group's LEADER row is transferring to, or
    # NO_XFER.  While set on a leader row the group stops accepting new
    # proposals and re-sends MSG_TIMEONOW to the target each tick once
    # its match has caught up; the row auto-clears the moment the row is
    # no longer leader (deposed by the target's election — completion —
    # or by anyone else).  The host patches it (set_transfer_target) and
    # owns deadline/abort; with every row at NO_XFER the whole phase is
    # gate-false and trajectories are bit-identical to the pre-transfer
    # kernel.  Volatile across restart by design (init gives NO_XFER):
    # a rebooted leader holds no transfer.
    xfer_target: jax.Array   # [G] i32

    rng: jax.Array           # [2]/key PRNG state for election jitter
    tick: jax.Array          # [] i32 step counter (for PRNG folding)


class Inbox(NamedTuple):
    """Dense per-source message slots delivered to one peer.

    Two slots per (group, source): a *vote* slot (RequestVote / PreVote
    req/resp) and an *append* slot (AppendEntries req/resp), distinguished
    by type codes MSG_NONE / MSG_REQ / MSG_RESP, plus — vote slot only —
    MSG_PREREQ / MSG_PRERESP.  This replaces the vendored etcd
    `raftpb.Message` stream (reference raft.go:268-270) with fixed-width
    arrays that map directly onto device memory.

    Overwrite-newest slot semantics are safe: raft tolerates message loss,
    and leaders/candidates re-send every heartbeat tick.
    """

    # Vote slot [G, P]:
    v_type: jax.Array        # i32 MSG_NONE/MSG_REQ/MSG_RESP/MSG_PREREQ/MSG_PRERESP
    v_term: jax.Array        # i32 sender term
    v_last_idx: jax.Array    # i32 (req) candidate last log index
    v_last_term: jax.Array   # i32 (req) candidate last log term
    v_granted: jax.Array     # bool (resp) vote granted

    # Append slot [G, P] (+ [G, P, E] entry terms):
    a_type: jax.Array        # i32 MSG_NONE / MSG_REQ / MSG_RESP
    a_term: jax.Array        # i32 sender term
    a_prev_idx: jax.Array    # i32 (req) index preceding the batch
    a_prev_term: jax.Array   # i32 (req) term of prev_idx
    a_n: jax.Array           # i32 (req) number of entries in batch
    a_ents: jax.Array        # [G, P, E] i32 (req) terms of batch entries
    a_commit: jax.Array      # i32 (req) leader commit index
    a_success: jax.Array     # bool (resp) append accepted
    a_match: jax.Array       # i32 (resp) match index (or conflict hint)


# The outbox has the same schema, indexed [G, dst] instead of [G, src].
Outbox = Inbox


class StepInfo(NamedTuple):
    """Host-facing observations from one step (all [G] unless noted).

    These drive the host side of the durability contract (reference
    raft.go:227-235): WAL save of HardState {term, voted_for, commit} and of
    newly appended entries, payload-log mirroring, and apply-at-commit.
    """

    commit: jax.Array        # i32 commit index after the step
    role: jax.Array          # i32 role after the step
    term: jax.Array          # i32 term after the step
    voted_for: jax.Array     # i32 vote cast this term (WAL HardState)
    leader_hint: jax.Array   # i32 current leader if known
    prop_base: jax.Array     # i32 log index before accepted proposals
    prop_accepted: jax.Array  # i32 number of proposals appended this step
    noop: jax.Array          # bool leader appended a no-op at prop_base
    # Host log-mirroring signals for inbound appends (see step.py):
    app_from: jax.Array      # i32 src peer whose append we accepted, -1 none
    app_start: jax.Array     # i32 first log index written from that append
    app_n: jax.Array         # i32 number of entries written
    app_conflict: jax.Array  # bool append truncated conflicting suffix
    new_log_len: jax.Array   # i32 log length after the step
    # Leader-lease expiry in device-step units (0 = no lease): while
    # `host_step_now + cfg.max_clock_skew < lease`, this peer may serve
    # group g a linearizable read at its current commit index without a
    # quorum round (core/step.py lease phase; always 0 when
    # cfg.lease_ticks == 0).  The §6.4 current-term-commit
    # precondition is already folded in on device.
    lease: jax.Array         # i32 [G]
    # Leadership-transfer latch AFTER the step (PeerState.xfer_target
    # carry): the target while this row still leads and a transfer is
    # armed, NO_XFER otherwise.  The host watches this column drop back
    # to NO_XFER on the (former) leader row to detect completion — the
    # device clears it the tick the row is deposed.
    xfer: jax.Array          # i32 [G]
    # Leader view [G, P]: where each peer's replication stands.  The host
    # uses this to spot followers that have fallen out of the device term
    # ring (next_idx <= log_len - W) OR below the transition-table floor
    # and feed them catch-up appends built from the host payload log
    # (runtime/node.py).
    next_idx: jax.Array      # i32 [G, P] next index to send each peer
    # Lowest position whose term the transition table still knows
    # (core/step.py floor1): the device suppresses real appends to
    # followers below it, so the host must serve them.
    floor: jax.Array         # i32 [G]
    # Scalar i32: minimum timer ticks (across all groups) until ANY
    # election or heartbeat timer could fire, given no inbound messages.
    # The host's event loop skips whole steps while its accumulated
    # timer advance stays below this margin and nothing is staged
    # (runtime/node.py _run) — an idle node costs ~zero CPU between
    # heartbeats instead of a full step per tick interval.
    timer_margin: jax.Array  # i32 []


def init_peer_state(cfg: RaftConfig, self_id: int | jax.Array,
                    seed: int | None = None) -> PeerState:
    """Fresh boot state (empty log, term 0, follower everywhere).

    Election timeouts start randomized per group/peer so that a cold-booted
    cluster doesn't produce a split vote storm in lockstep — the moral
    equivalent of etcd/raft's randomized election timer.
    """
    g, p, w = cfg.num_groups, cfg.num_peers, cfg.log_window
    seed = cfg.seed if seed is None else seed
    key = jax.random.fold_in(jax.random.PRNGKey(seed), jnp.asarray(self_id))
    key, sub = jax.random.split(key)
    timeout = jax.random.randint(
        sub, (g,), cfg.election_ticks, 2 * cfg.election_ticks, dtype=I32)
    voters = jnp.broadcast_to(
        jnp.asarray(initial_voter_row(cfg))[None, :], (g, p))
    # Distinct buffer, not an alias: the two masks are donated together
    # by the jitted step, and a shared buffer trips double-donation.
    voters_joint = jnp.array(voters)
    return PeerState(
        term=jnp.zeros((g,), I32),
        voted_for=jnp.full((g,), NO_VOTE, I32),
        role=jnp.full((g,), FOLLOWER, I32),
        leader_hint=jnp.full((g,), NO_LEADER, I32),
        commit=jnp.zeros((g,), I32),
        log_len=jnp.zeros((g,), I32),
        log_term=jnp.zeros((g, w if cfg.keep_ring else 1), I32),
        tbl_pos=jnp.zeros((g, cfg.term_table_slots), I32),
        tbl_term=jnp.zeros((g, cfg.term_table_slots), I32),
        elapsed=jnp.zeros((g,), I32),
        timeout=timeout,
        hb_elapsed=jnp.zeros((g,), I32),
        votes=jnp.zeros((g, p), B),
        match=jnp.zeros((g, p), I32),
        next_idx=jnp.ones((g, p), I32),
        voters=voters,
        voters_joint=voters_joint,
        resp_tick=jnp.zeros((g, p), I32),
        xfer_target=jnp.full((g,), NO_XFER, I32),
        rng=key,
        tick=jnp.zeros((), I32),
    )


def initial_voter_row(cfg: RaftConfig):
    """[P] bool numpy row of cfg's boot-time voter set (all True when
    cfg.initial_voters is None — the static-cluster default)."""
    import numpy as np

    p = cfg.num_peers
    if cfg.initial_voters is None:
        return np.ones((p,), bool)
    row = np.zeros((p,), bool)
    row[list(cfg.initial_voters)] = True
    return row


def witness_row(cfg: RaftConfig):
    """[P] bool numpy row of cfg's witness slots (all False by default).

    Witness identity is STATIC per config — a compiled constant the
    step indexes with its traced self_id (cluster.py vmaps self_ids),
    never device state: witnesses are a deployment shape, not something
    a log entry changes mid-flight."""
    import numpy as np

    row = np.zeros((cfg.num_peers,), bool)
    if cfg.witnesses:
        row[list(cfg.witnesses)] = True
    return row


@functools.partial(jax.jit, donate_argnums=0)
def set_group_config(state: PeerState, g: jax.Array,
                     voters_row: jax.Array, joint_row: jax.Array,
                     self_is_voter: jax.Array) -> PeerState:
    """Patch group `g`'s active configuration into the device masks.

    Called by the host membership plane when a conf-change log entry
    APPLIES at commit (two-phase joint style: C_old,new sets voters=new
    + voters_joint=old; C_new sets both to new).  `self_is_voter` is
    whether THIS peer remains a voter under the new config: a leader
    removed by the change steps down to follower on apply (raft §6 —
    it led long enough to commit its own removal), and a demoted slot
    can never campaign again (core/step.py gates election timeouts on
    the mask)."""
    g = jnp.asarray(g, I32)
    vrow = jnp.asarray(voters_row, B)
    jrow = jnp.asarray(joint_row, B)
    # A non-voter must not be (or stay) leader/candidate: drop to
    # follower and clear its tally.  It keeps replicating as a learner;
    # the next append teaches it the new leader.
    demote = ~jnp.asarray(self_is_voter, B) & (state.role[g] != FOLLOWER)
    return state._replace(
        voters=state.voters.at[g].set(vrow),
        voters_joint=state.voters_joint.at[g].set(jrow),
        role=state.role.at[g].set(
            jnp.where(demote, FOLLOWER, state.role[g])),
        leader_hint=state.leader_hint.at[g].set(
            jnp.where(demote, NO_LEADER, state.leader_hint[g])),
        votes=state.votes.at[g].set(
            jnp.where(demote, False, state.votes[g])),
    )


def restored_leaves(cfg: RaftConfig, log_terms: dict, hard: dict,
                    starts: dict | None = None) -> dict:
    """The leaves a replayed WAL decides, as HOST arrays [G, ...] by
    PeerState field name (every other leaf is `init_peer_state`'s).
    Nothing here touches a device: the co-located runtimes stack these
    per peer and place the stack under the layout their step runs in
    (runtime/hostplane.py `_build_cluster_arrays`), so a restart never
    stages a peer's state on the default device first.

    Args:
      log_terms: {group: [term of entry start+1, start+2, ...]}
      hard: {group: (term, voted_for, commit)}
      starts: {group: (start, start_term)} — WAL-compaction floors; the
        prefix up to `start` is snapshot-covered (committed + applied),
        entries list begins at start+1.  The boundary term is seeded into
        the ring so prev-term checks at the edge resolve on device.
    """
    import numpy as np

    g_, k_ = cfg.num_groups, cfg.term_table_slots
    # Honor the keep_ring stub contract: the restored pytree must have
    # the same leaf shapes as init_peer_state's, or the post-restart jit
    # programs (and sharded buffers) would retrace against a wide ring
    # the config promised not to carry.
    w = cfg.log_window if cfg.keep_ring else 1
    starts = starts or {}
    term = np.zeros((g_,), np.int32)
    voted = np.full((g_,), NO_VOTE, np.int32)
    commit = np.zeros((g_,), np.int32)
    log_len = np.zeros((g_,), np.int32)
    window = np.zeros((g_, w), np.int32)
    tbl_pos = np.zeros((g_, k_), np.int32)
    tbl_term = np.zeros((g_, k_), np.int32)
    for g in range(g_):
        t, v, c = hard.get(g, (0, NO_VOTE, 0))
        term[g], voted[g], commit[g] = t, v, c
        start, start_term = starts.get(g, (0, 0))
        terms = log_terms.get(g, [])
        log_len[g] = start + len(terms)
        lo = max(start + 1, log_len[g] - w + 1)
        for idx in range(lo, log_len[g] + 1):
            window[g, (idx - 1) % w] = terms[idx - 1 - start]
        if start >= 1 and start > log_len[g] - w:
            window[g, (start - 1) % w] = start_term
        # Term-transition table over the same known span: the boundary
        # (start, start_term) if still adjacent, then every term change
        # in the replayed entries; keep the newest K, right-aligned.
        trans = []
        if start >= 1:
            trans.append((start, start_term))
        last = start_term if start >= 1 else 0
        for idx in range(start + 1, log_len[g] + 1):
            tt = terms[idx - 1 - start]
            if tt != last:
                trans.append((idx, tt))
                last = tt
        trans = trans[-k_:]
        for j, (pos_, term_) in enumerate(trans):
            tbl_pos[g, k_ - len(trans) + j] = pos_
            tbl_term[g, k_ - len(trans) + j] = term_
        # The snapshot floor is committed by construction; hard.commit can
        # trail it only if the marker postdates the last hardstate record.
        commit[g] = min(max(commit[g], start), log_len[g])
    return dict(term=term, voted_for=voted, commit=commit,
                log_len=log_len, log_term=window, tbl_pos=tbl_pos,
                tbl_term=tbl_term)


def restore_peer_state(cfg: RaftConfig, self_id: int,
                       log_terms: dict, hard: dict,
                       seed: int | None = None,
                       starts: dict | None = None) -> PeerState:
    """Rebuild boot state from a replayed WAL (the reference's RestartNode
    path, raft.go:122-134, 161-163): `init_peer_state` with the leaves
    `restored_leaves` (which documents the arguments) decides."""
    leaves = restored_leaves(cfg, log_terms, hard, starts)
    return init_peer_state(cfg, self_id, seed)._replace(
        **{k: jnp.asarray(v) for k, v in leaves.items()})


@functools.partial(jax.jit, donate_argnums=0)
def set_group_config_stacked(states: PeerState, p: jax.Array,
                             g: jax.Array, voters_row: jax.Array,
                             joint_row: jax.Array,
                             self_is_voter: jax.Array) -> PeerState:
    """`set_group_config` over a STACKED cluster state (leaves
    [P, G, ...], runtime/fused.py): patch peer row `p`'s view of group
    `g`.  Each peer row applies a conf entry when ITS OWN commit passes
    the entry — exactly the distributed runtime's timing, co-located."""
    p = jnp.asarray(p, I32)
    g = jnp.asarray(g, I32)
    vrow = jnp.asarray(voters_row, B)
    jrow = jnp.asarray(joint_row, B)
    demote = ~jnp.asarray(self_is_voter, B) \
        & (states.role[p, g] != FOLLOWER)
    return states._replace(
        voters=states.voters.at[p, g].set(vrow),
        voters_joint=states.voters_joint.at[p, g].set(jrow),
        role=states.role.at[p, g].set(
            jnp.where(demote, FOLLOWER, states.role[p, g])),
        leader_hint=states.leader_hint.at[p, g].set(
            jnp.where(demote, NO_LEADER, states.leader_hint[p, g])),
        votes=states.votes.at[p, g].set(
            jnp.where(demote, False, states.votes[p, g])),
    )


@functools.partial(jax.jit, donate_argnums=0)
def set_transfer_target(state: PeerState, g: jax.Array,
                        target: jax.Array) -> PeerState:
    """Arm (target >= 0) or clear (NO_XFER) group `g`'s leadership
    transfer on this peer's row.  Host-plane admin patch, same contract
    as set_group_config: the step only READS xfer_target; arming a
    non-leader row is harmless (the step clears it next tick), and the
    abort path clears it to cleanly re-open the group for proposals."""
    g = jnp.asarray(g, I32)
    return state._replace(
        xfer_target=state.xfer_target.at[g].set(jnp.asarray(target, I32)))


@functools.partial(jax.jit, donate_argnums=0)
def set_transfer_target_stacked(states: PeerState, p: jax.Array,
                                g: jax.Array,
                                target: jax.Array) -> PeerState:
    """`set_transfer_target` over a STACKED cluster state (leaves
    [P, G, ...], runtime/fused.py / runtime/mesh.py): arm or clear peer
    row `p`'s transfer latch for group `g`."""
    p = jnp.asarray(p, I32)
    g = jnp.asarray(g, I32)
    return states._replace(
        xfer_target=states.xfer_target.at[p, g].set(
            jnp.asarray(target, I32)))


@functools.partial(jax.jit, donate_argnums=0)
def install_snapshot_state(state: PeerState, g: jax.Array,
                           last_idx: jax.Array, last_term: jax.Array,
                           sender_term: jax.Array) -> PeerState:
    """Reset group `g`'s device row to a snapshot boundary.

    The follower installed a state-machine image at log position
    `last_idx` (entry term `last_term`): its log becomes exactly that
    prefix — length and commit jump to last_idx, the term ring is cleared
    except the boundary slot, and the row drops to follower so normal
    replication resumes from last_idx + 1 (raft §7 InstallSnapshot; no
    analog in the reference, which never snapshots, db.go:27-29).

    `sender_term` is the sending leader's term: a higher term is adopted
    (vote cleared), exactly as any raft RPC with term > currentTerm.  The
    caller must have already rejected sender_term < currentTerm — this
    function cannot, because the install itself (log/commit jump) must
    not happen for stale senders.
    """
    g = jnp.asarray(g, I32)
    last_idx = jnp.asarray(last_idx, I32)
    # The ring may be a [G, 1] stub (cfg.keep_ring=False): write modulo
    # its actual width, which degenerates harmlessly.
    rw = state.log_term.shape[-1]
    ring = jnp.zeros((rw,), I32).at[(last_idx - 1) % rw].set(
        jnp.asarray(last_term, I32))
    # Table analog of the cleared ring: one transition at the snapshot
    # boundary — terms known exactly for [last_idx, last_idx].
    K = state.tbl_pos.shape[-1]
    tpos = jnp.zeros((K,), I32).at[K - 1].set(last_idx)
    tterm = jnp.zeros((K,), I32).at[K - 1].set(jnp.asarray(last_term, I32))
    sender_term = jnp.asarray(sender_term, I32)
    newer = sender_term > state.term[g]
    return state._replace(
        term=state.term.at[g].set(jnp.maximum(state.term[g], sender_term)),
        voted_for=state.voted_for.at[g].set(
            jnp.where(newer, NO_VOTE, state.voted_for[g])),
        log_len=state.log_len.at[g].set(last_idx),
        commit=state.commit.at[g].set(last_idx),
        log_term=state.log_term.at[g].set(ring),
        tbl_pos=state.tbl_pos.at[g].set(tpos),
        tbl_term=state.tbl_term.at[g].set(tterm),
        role=state.role.at[g].set(FOLLOWER),
        votes=state.votes.at[g].set(False),
        match=state.match.at[g].set(0),
        next_idx=state.next_idx.at[g].set(last_idx + 1),
        elapsed=state.elapsed.at[g].set(0),
        resp_tick=state.resp_tick.at[g].set(0),
        xfer_target=state.xfer_target.at[g].set(NO_XFER),
    )


@functools.partial(jax.jit, donate_argnums=0)
def set_peer_progress(state: PeerState, g: jax.Array, d: jax.Array,
                      next_idx: jax.Array) -> PeerState:
    """Leader-side optimistic advance after shipping a snapshot to peer
    `d`: replication resumes at next_idx = last_idx + 1.  `match` is NOT
    touched: the step clamps next_idx to match+1 from below, so a match
    the peer never acknowledged would block reject-walkback permanently —
    a snapshot sent to a dead peer would strand it.  If the transfer is
    lost, the peer's rejects walk next_idx back and retrigger it; if it
    lands, the next real append's ack advances match."""
    g = jnp.asarray(g, I32)
    d = jnp.asarray(d, I32)
    return state._replace(
        next_idx=state.next_idx.at[g, d].set(jnp.asarray(next_idx, I32)))


def empty_inbox(cfg: RaftConfig) -> Inbox:
    g, p, e = cfg.num_groups, cfg.num_peers, cfg.max_entries_per_msg
    z = jnp.zeros((g, p), I32)
    zb = jnp.zeros((g, p), B)
    return Inbox(
        v_type=z, v_term=z, v_last_idx=z, v_last_term=z, v_granted=zb,
        a_type=z, a_term=z, a_prev_idx=z, a_prev_term=z, a_n=z,
        a_ents=jnp.zeros((g, p, e), I32), a_commit=z,
        a_success=zb, a_match=z,
    )


def term_at_tbl(tbl_pos: jax.Array, tbl_term: jax.Array, log_len: jax.Array,
                idx: jax.Array) -> jax.Array:
    """Term of entry `idx` from the transition table; term_at(0) == 0.

    `idx` may be [...] or [..., X] against tables [..., K].  Because terms
    are nondecreasing in position, the term at idx is the MAX term over
    valid transitions starting at or before idx.  Out of range (idx < 1,
    idx > log_len, or idx below the table floor) returns 0 — callers
    guard floor reads exactly as they guard out-of-ring reads.

    This is the O(K) read that replaced the O(W) ring read in the hot
    step: the [G, P, E] batch-term read alone was 68% of the profiled
    TPU tick at G=32k (see ops/dense.py for why gathers are not an
    option on that backend).
    """
    idx = jnp.asarray(idx)
    squeeze = idx.ndim == tbl_pos.ndim - 1
    idx2 = idx[..., None] if squeeze else idx
    hit = (tbl_pos[..., None, :] > 0) \
        & (tbl_pos[..., None, :] <= idx2[..., None])    # [..., X, K]
    got = jnp.max(jnp.where(hit, tbl_term[..., None, :], 0), axis=-1)
    if squeeze:
        got = got[..., 0]
    else:
        log_len = log_len[..., None]
    valid = (idx >= 1) & (idx <= log_len)
    return jnp.where(valid, got, 0)


def tbl_floor(tbl_pos: jax.Array, log_len: jax.Array) -> jax.Array:
    """Lowest position whose term the table still knows; log_len + 1 for
    an empty table (every read is then out of range anyway)."""
    valid = tbl_pos > 0
    f = jnp.min(jnp.where(valid, tbl_pos, jnp.iinfo(I32).max), axis=-1)
    return jnp.where(valid.any(-1), f, log_len + 1)


def term_at(log_term: jax.Array, log_len: jax.Array, idx: jax.Array,
            window: int) -> jax.Array:
    """Term of entry `idx` from the ring, with term_at(0) == 0.

    `idx` may be [G] or [G, P]-shaped (log arrays broadcast accordingly).
    Out-of-range (idx < 1 or idx > log_len) returns 0.  Positions that have
    slid out of the ring return whatever was overwritten — the host flow
    controller guarantees the engine never asks for those (see
    runtime/node.py flow control and config.log_window).
    """
    # ops.dense.take_last: on TPU this lowers to a fused one-hot
    # select-reduce instead of an XLA gather (which serializes per index
    # on that backend — see ops/dense.py).
    from raftsql_tpu.ops.dense import take_last

    idx = jnp.asarray(idx)
    squeeze = idx.ndim == log_term.ndim - 1
    idx2 = idx[..., None] if squeeze else idx
    got = take_last(log_term, (idx2 - 1) % window)
    if squeeze:
        got = got[..., 0]
    else:
        log_len = log_len[..., None]
    valid = (idx >= 1) & (idx <= log_len)
    return jnp.where(valid, got, 0)
