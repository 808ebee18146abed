"""The batched raft peer transition — one tick for G groups in one XLA program.

This replaces the vendored etcd/raft state machine the reference drives via
`Tick`/`Propose`/`Ready`/`Advance` (reference raft.go:204-245): leader
election, log replication, and quorum commit are expressed as masked dense
int ops over `[G]` / `[G, P]` / `[G, W]` arrays, so one `peer_step` advances
every raft group owned by this peer at once.

Semantics follow the raft paper (Figure 2) plus two etcd-isms the reference
relies on:
  * randomized election timeouts (per group, per peer);
  - a no-op entry appended by a freshly elected leader, so old-term entries
    commit without waiting for client traffic (the reference inherits this
    from etcd/raft; its publish loop skips the empty entries,
    reference raft.go:84-87).

Design notes (TPU-first):
  - No data-dependent control flow: every branch is a `jnp.where` over all
    groups.  Inactive groups cost lanes, not branches.
  - Messages are fixed-slot dense arrays (one vote slot + one append slot
    per (group, src)); overwrite-newest is safe because raft tolerates loss
    and senders re-send every heartbeat tick.
  - The log keeps only terms on device, in a ring of capacity W; payload
    bytes stay host-side.  Flow control (runtime/node.py) keeps the ring
    from overrunning — the analog of the reference's MaxInflightMsgs window
    (reference raft.go:158).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from raftsql_tpu.config import (CANDIDATE, FLOOR_HINT_BIAS, FOLLOWER, LEADER,
                                MSG_NONE, MSG_PREREQ, MSG_PRERESP, MSG_REQ,
                                MSG_RESP, MSG_TIMEONOW, NO_LEADER, NO_VOTE,
                                NO_XFER, PRECANDIDATE, RaftConfig)
from raftsql_tpu.core.state import (I32, Inbox, Outbox, PeerState, StepInfo,
                                    tbl_floor, term_at_tbl, witness_row)
from raftsql_tpu.ops import dense
from raftsql_tpu.ops.quorum import (masked_quorum_commit_index,
                                    masked_quorum_match_index,
                                    masked_vote_win, quorum_commit_index,
                                    quorum_match_index, vote_count)


# The phases of peer_step as they are named in the lowered program and
# in a device trace (jax.named_scope: metadata only, the computation and
# every chaos digest are what they were).  core/cluster.py wraps them in
# `cluster_step`; `raft.pack` is cluster_step_host's packing of the
# host-facing info.
STEP_SCOPES = ("raft.inbox", "raft.votes", "raft.append", "raft.commit",
               "raft.timers", "raft.outbox")


class _PhaseScope:
    """`scope(name)` ends the named scope that is open and opens the
    next, so that peer_step's phases carry names without each becoming
    an indented block; peer_step closes the last one."""

    def __init__(self):
        self._open = None

    def __call__(self, name: str) -> None:
        self.close()
        self._open = jax.named_scope(name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def peer_step(cfg: RaftConfig, state: PeerState, inbox: Inbox,
              prop_n: jax.Array, self_id: jax.Array,
              group_offset: jax.Array | int = 0,
              timer_inc: jax.Array | int = 1,
              force_bcast: jax.Array | bool = False
              ) -> Tuple[PeerState, Outbox, StepInfo]:
    """Advance one peer's view of all G groups by one tick.

    Args:
      cfg: static configuration (shapes, timeouts).
      state: this peer's PeerState.
      inbox: dense message slots received since the last tick.
      prop_n: [G] i32 — number of new local proposals to append if leader
        (capped at cfg.max_entries_per_msg; host queues the rest).
      self_id: scalar i32 — this peer's 0-based id (traced, so the same
        compiled program serves every peer and vmaps over the peer axis).
      group_offset: scalar i32 — global id of group row 0.  Election
        jitter is drawn per GLOBAL group id, so a mesh-sharded run
        (parallel/sharded.py, where this peer sees a G/gg-row block)
        draws bit-identical timeouts to the single-chip run.
      timer_inc: scalar i32, 0 or 1 — how far the real-time timers
        (election `elapsed`, leader `hb_elapsed`) advance this step.
        The host's event-driven loop (runtime/node.py) runs extra
        work-triggered steps with timer_inc=0 so message processing can
        outpace the wall-clock tick without distorting election or
        heartbeat timing; interval-paced steps pass 1 (the reference's
        100 ms Tick(), raft.go:207, is exactly the timer_inc=1 cadence).
        Values > 1 apply several intervals of advance at once — the host
        elides steps while nothing can fire (info.timer_margin) and
        settles the accumulated advance on the next real step.
      force_bcast: scalar bool OR [G] bool — leaders broadcast an
        append/heartbeat round THIS step regardless of heartbeat
        countdown.  The host sets it when a linearizable read
        registers (runtime/node.py read_index / read_join): the
        ReadIndex quorum round must not wait out the heartbeat
        interval.  A [G] mask (the batched-ReadIndex promote,
        runtime/node.py _rb_promote) nudges only the groups with
        reads in flight; it broadcasts against the same [G] hb_fire
        vector a scalar does, so semantics per group are identical.

    Returns:
      (new_state, outbox, info).  `outbox[g, dst]` is the dense message set
      to deliver; `info` carries the host-facing signals (commit advance,
      accepted proposals, accepted append ranges) that drive WAL writes,
      payload mirroring, and apply.
    """
    scope = _PhaseScope()
    try:
        return _peer_step(scope, cfg, state, inbox, prop_n, self_id,
                          group_offset, timer_inc, force_bcast)
    finally:
        scope.close()


def _peer_step(scope: _PhaseScope, cfg: RaftConfig, state: PeerState,
               inbox: Inbox, prop_n, self_id, group_offset, timer_inc,
               force_bcast) -> Tuple[PeerState, Outbox, StepInfo]:
    """peer_step's body; `scope(name)` names the phases that follow it
    (STEP_SCOPES)."""
    G, P, W, E = cfg.num_groups, cfg.num_peers, cfg.log_window, \
        cfg.max_entries_per_msg
    scope("raft.inbox")     # masks, term catch-up, TimeoutNow receipt
    src_ids = jnp.arange(P, dtype=I32)[None, :]                  # [1, P]
    self_onehot = src_ids == self_id                             # [1, P]

    # Active membership configuration (device data, raftsql_tpu/
    # membership/): every quorum below — commit advance, election
    # tally, prevote tally, vote granting — reads these masks, so N
    # groups can sit in N different configurations inside this one
    # program.  The static all-voters default reproduces the old fixed
    # cfg.quorum math bit for bit.  `voter_src[g, p]` = slot p is a
    # voter of group g under EITHER mask (joint consensus counts both);
    # `self_voter[g]` = this peer may campaign.
    #
    # STATIC fast path (cfg.static_full_voters): the masks are known
    # full constants, so every mask gate folds to identity and every
    # quorum is the fixed-threshold kernel — the pre-membership program.
    # The masked kernels with a full mask are bit-identical (property-
    # tested in tests/test_membership.py), so the two paths may never
    # diverge; runtimes flip to the dynamic path (one recompile) the
    # moment a conf entry exists (config.py dynamic_membership).
    voters, jvoters = state.voters, state.voters_joint
    if cfg.static_full_voters:
        voter_src = True           # folds out of every & below
        self_voter = True

        def _vote_win(votes):
            # election_size == cfg.quorum under default geometry; an
            # explicit election_quorum (config.py flexible quorums)
            # just substitutes the static threshold constant.
            return vote_count(votes) >= cfg.election_size
    else:
        voter_src = voters | jvoters                             # [G, P]
        self_voter = jnp.sum(voter_src & self_onehot,
                             axis=-1) > 0                        # [G]

        def _vote_win(votes):
            return masked_vote_win(votes, voters, jvoters,
                                   cfg.election_quorum)

    # Witness self-identity (config.py witnesses): a STATIC [P] bool
    # constant indexed by the traced self_id — witnesses are a
    # deployment shape, never device state, so the same compiled
    # program serves every peer under vmap (core/cluster.py).  The
    # default (no witnesses) keeps a Python False that folds out of
    # every gate below, leaving the program bit-identical.
    if cfg.witnesses:
        self_witness = jnp.asarray(witness_row(cfg))[self_id]    # scalar
    else:
        self_witness = False

    log_term, log_len = state.log_term, state.log_len
    tbl_pos, tbl_term = state.tbl_pos, state.tbl_term
    commit0 = state.commit
    # Every term-of-position read below goes through the O(K) transition
    # table (state.tbl_pos/tbl_term); the O(W) ring is write-only here
    # (it feeds the windowed/pallas commit rules and test oracles).
    # Positions below the table floor are unreadable and guarded like
    # out-of-ring positions.
    floor0 = tbl_floor(tbl_pos, log_len)                          # [G]

    def term_of0(idx):  # reads against the PRE-append log
        return term_at_tbl(tbl_pos, tbl_term, log_len, idx)

    # ---- Phase 1: term catch-up.  Any message with a newer term makes us a
    # follower of that term (raft §5.1) — EXCEPT prevote traffic carrying a
    # *probed* future term: PREREQ (the probe itself) and granted PRERESP
    # (echoing the probed term back) must not bump anyone, or prevote would
    # inflate terms exactly like the elections it prevents.  A REJECTED
    # PRERESP carries the responder's real current term and does bump.
    v_bump = (inbox.v_type == MSG_REQ) | (inbox.v_type == MSG_RESP) \
        | ((inbox.v_type == MSG_PRERESP) & ~inbox.v_granted)
    a_has = inbox.a_type != MSG_NONE
    msg_term = jnp.maximum(
        jnp.max(jnp.where(v_bump, inbox.v_term, 0), axis=-1),
        jnp.max(jnp.where(a_has, inbox.a_term, 0), axis=-1))      # [G]
    bumped = msg_term > state.term
    term = jnp.maximum(state.term, msg_term)
    role = jnp.where(bumped, FOLLOWER, state.role)
    voted = jnp.where(bumped, NO_VOTE, state.voted_for)
    votes = jnp.where(bumped[:, None], False, state.votes)
    leader_hint = jnp.where(bumped, NO_LEADER, state.leader_hint)

    my_last_term = term_of0(log_len)                              # [G]

    # ---- Phase 1b: TimeoutNow receipt (leadership transfer, raft thesis
    # §3.10).  A caught-up transfer target starts a REAL election at
    # term+1 immediately — no prevote probe, which is exactly how the
    # grant bypasses the Phase-2b in-lease refusal for this one peer
    # (every other peer keeps refusing in-lease probes, so nobody else
    # can race the handoff inside the lease).  Gated on the sender's
    # CURRENT term (a stale grant from a deposed leader is ignored) and
    # on self being a voter (learners/spares never campaign, Phase 8).
    # With no transfer armed anywhere (xfer_target all NO_XFER — the
    # shipping default) no MSG_TIMEONOW ever exists and this phase is a
    # no-op: trajectories stay bit-identical to the pre-transfer kernel.
    tnow_fire = ((inbox.v_type == MSG_TIMEONOW)
                 & (inbox.v_term == term[:, None])).any(-1) \
        & (role != LEADER) & self_voter
    if cfg.witnesses:
        # Witnesses never campaign, so they never accept a transfer
        # grant either (the host refuses witness targets up front —
        # runtime TransferRefused — this is the device-side backstop).
        tnow_fire = tnow_fire & ~self_witness
    term = jnp.where(tnow_fire, term + 1, term)
    role = jnp.where(tnow_fire, CANDIDATE, role)
    voted = jnp.where(tnow_fire, self_id, voted)
    votes = jnp.where(tnow_fire[:, None],
                      jnp.broadcast_to(self_onehot, (G, P)), votes)
    leader_hint = jnp.where(tnow_fire, NO_LEADER, leader_hint)

    scope("raft.votes")    # vote and prevote requests, tallies
    # ---- Phase 2: RequestVote requests.  Grant at most one vote per group
    # per tick (voted_for is single-valued); re-granting to the same
    # candidate is idempotent.
    vreq = inbox.v_type == MSG_REQ
    vreq_cur = vreq & (inbox.v_term == term[:, None])
    up2date = (inbox.v_last_term > my_last_term[:, None]) | (
        (inbox.v_last_term == my_last_term[:, None])
        & (inbox.v_last_idx >= log_len[:, None]))
    # voter_src gate: never grant to a candidate WE believe is outside
    # the active configuration — once a removal commits at a majority,
    # the removed peer can no longer assemble a quorum of grants ("no
    # quorum from a removed majority", chaos/invariants.py).
    eligible = vreq_cur & up2date & voter_src & (
        (voted == NO_VOTE)[:, None] | (voted[:, None] == src_ids))
    any_grant = eligible.any(-1)
    grant_to = jnp.argmax(eligible, axis=-1).astype(I32)          # [G]
    grant = eligible & (src_ids == grant_to[:, None])             # [G, P]
    voted = jnp.where(any_grant, grant_to, voted)

    # ---- Phase 2b: PreVote requests.  Grant iff the probe targets a term
    # ahead of ours, the prober's log is up-to-date, and we are NOT inside
    # a live leader's lease (heard from it within one election interval) —
    # the lease test is what starves a partitioned prober while the
    # cluster is healthy.  Prevote grants persist nothing (not voted_for),
    # so any number may be granted per tick, one per source slot.
    preq = inbox.v_type == MSG_PREREQ
    if cfg.prevote:
        in_lease = (leader_hint != NO_LEADER) & \
            (state.elapsed < cfg.election_ticks)
        lease_ok = ~in_lease[:, None]
        if cfg.unsafe_witness_lease and cfg.witnesses:
            # FALSIFICATION ONLY (config.py unsafe_witness_lease): the
            # "witness as always-available tiebreaker" mistake — this
            # witness grants prevotes INSIDE a live lease while its
            # append acks still feed the lease clock (Phase 8b), so an
            # election can complete before the lease expires and the
            # deposed leader serves a stale lease read.  The quorum
            # chaos family must CATCH it.
            lease_ok = lease_ok | self_witness
        pre_grant = preq & (inbox.v_term > term[:, None]) & up2date \
            & voter_src & lease_ok
    else:
        pre_grant = jnp.zeros_like(preq)

    # Vote-slot responses must be stamped with the term their grant/reject
    # was DECIDED at (here, before the Phase-3 prevote promotion can bump
    # our term) — a grant decided at T but stamped T+1 would depose the
    # very candidate it was granted to via the Phase-1 bump rule.
    vterm_resp = term

    # ---- Phase 3: vote tallies.  First the prevote tally (promotes
    # PRECANDIDATE → CANDIDATE, bumping the term only now that a quorum
    # said the election could win), then the real-vote tally — a just-
    # promoted candidate holding its own vote can win leadership in the
    # same tick when P == 1.
    if cfg.prevote:
        got_pre = (inbox.v_type == MSG_PRERESP) & inbox.v_granted \
            & (inbox.v_term == term[:, None] + 1) \
            & (role == PRECANDIDATE)[:, None]
        votes = votes | got_pre
        become_cand = (role == PRECANDIDATE) & _vote_win(votes)
        term = jnp.where(become_cand, term + 1, term)
        role = jnp.where(become_cand, CANDIDATE, role)
        voted = jnp.where(become_cand, self_id, voted)
        votes = jnp.where(become_cand[:, None],
                          jnp.broadcast_to(self_onehot, (G, P)), votes)

    got_vote = (inbox.v_type == MSG_RESP) & (inbox.v_term == term[:, None]) \
        & inbox.v_granted & (role == CANDIDATE)[:, None]
    votes = votes | got_vote
    become_leader = (role == CANDIDATE) & _vote_win(votes)
    role = jnp.where(become_leader, LEADER, role)
    leader_hint = jnp.where(become_leader, self_id, leader_hint)
    next_idx = jnp.where(become_leader[:, None], log_len[:, None] + 1,
                         state.next_idx)
    match = jnp.where(become_leader[:, None], 0, state.match)

    scope("raft.append")    # append requests, responses, proposals
    # ---- Phase 4: AppendEntries requests.  At most one current-term leader
    # exists (election safety), so picking one current-term append per group
    # loses nothing.
    areq = inbox.a_type == MSG_REQ
    areq_cur = areq & (inbox.a_term == term[:, None])
    any_app = areq_cur.any(-1)
    asrc = jnp.argmax(areq_cur, axis=-1).astype(I32)              # [G]
    role = jnp.where(
        any_app & ((role == CANDIDATE) | (role == PRECANDIDATE)),
        FOLLOWER, role)
    leader_hint = jnp.where(any_app, asrc, leader_hint)

    def pick(x):  # gather the chosen source's message fields → [G, ...]
        return dense.pick_peer(x, asrc)

    prev = pick(inbox.a_prev_idx)
    prev_t = pick(inbox.a_prev_term)
    a_n = pick(inbox.a_n)
    a_ents = pick(inbox.a_ents)                                   # [G, E]
    a_commit = pick(inbox.a_commit)

    # Log-matching check — but ONLY against positions whose term is
    # still known: below the table floor the term is gone, and a stale
    # append (old leader, or one raced by an InstallSnapshot that
    # cleared the log metadata) must be rejected rather than trusted —
    # accepting it would conflict-truncate a log it never matched.
    # Two ways to verify a batch:
    #   1. directly at prev (prev above the floor, terms match); or
    #   2. at the batch's LAST overlapping position, when that is above
    #      the floor and terms match there — by the Log Matching
    #      property a shared (index, term) implies the whole prefix
    #      (prev included) matches.  This unsticks a live deadlock: a
    #      restarted follower whose own floor sits above the leader's
    #      serving point would otherwise reject every catch-up append
    #      (its reject hints can only walk next_idx DOWN), while the
    #      anchor check lets it accept the overlap it already holds and
    #      ack match=app_end.
    # prev == 0 is only exempt while the table still covers position 1.
    ov_n = jnp.clip(jnp.minimum(prev + a_n, log_len) - prev, 0, E)  # [G]
    ov_term = term_of0(prev + ov_n)
    batch_ov = dense.pick_batch(a_ents, jnp.maximum(ov_n - 1, 0))
    anchor_ok = (ov_n > 0) & (prev + ov_n >= floor0) \
        & (ov_term == batch_ov)
    prev_ok = ((prev == 0) & (floor0 <= 1)) \
        | ((prev <= log_len) & (prev >= floor0)
           & (term_of0(prev) == prev_t)) \
        | ((prev <= log_len) & anchor_ok)
    accept = any_app & prev_ok & (role != LEADER)

    # Conflict detection reuses the endpoint read from above: by Log
    # Matching, a term mismatch anywhere in the overlap implies one at
    # the LAST overlapping position — one [G] table read replaces a
    # [G, E]-wide per-position scan (which profiled as 34% of the TPU
    # tick, see ops/dense.py).
    conflict = accept & (ov_n > 0) & (ov_term != batch_ov)
    # Ring write of the accepted batch, scatter-free (ops/dense.py): entry
    # e lands at slot (prev+e) % W, so slot w holds batch element
    # (w - prev) mod W when that is < n.  One-hot over E replaces the
    # serialized XLA scatter the TPU path cannot afford.  Positions at or
    # below (new log_len) - W are masked out: an anchor-verified batch
    # may sit arbitrarily deep, and its slots would alias LIVE ring
    # entries of newer positions.
    a_n_w = jnp.clip(a_n, 0, E)
    if cfg.keep_ring:
        wpos = jnp.arange(W, dtype=I32)[None, :]                   # [1, W]
        rel4 = (wpos - prev[:, None]) % W                          # [G, W]
        len_after = jnp.where(conflict, prev + a_n,
                              jnp.maximum(log_len, prev + a_n))    # [G]
        pos4 = prev[:, None] + 1 + rel4
        hit4 = accept[:, None] & (rel4 < a_n_w[:, None]) \
            & (pos4 > len_after[:, None] - W)
        vals4 = dense.ring_gather_values(a_ents, rel4, a_n_w)
        log_term = jnp.where(hit4, vals4, log_term)
    app_end = prev + a_n
    follower_len0 = log_len
    log_len = jnp.where(
        accept,
        jnp.where(conflict, app_end, jnp.maximum(log_len, app_end)),
        log_len)

    # Transition-table merge for the accepted batch.  Old transitions
    # survive up to the first rewritten-and-changed position (everything
    # on conflict-free overlap is unchanged by Log Matching); new
    # transitions come from term changes inside the batch's genuinely
    # new span.  Candidates stay position-ascending by construction
    # (kept old <= boundary < added new), so compaction is a reversed
    # prefix-count that right-aligns the newest K — no sort.
    new_from = jnp.where(conflict, prev, follower_len0)           # [G]
    old_keep = (tbl_pos > 0) & (
        ~(accept & conflict)[:, None] | (tbl_pos <= prev[:, None]))
    erange = jnp.arange(E, dtype=I32)[None, :]
    pos_e = prev[:, None] + 1 + erange                            # [G, E]
    prev_term_known = term_of0(prev)                              # [G]
    ents_shift = jnp.concatenate(
        [prev_term_known[:, None], a_ents[:, :-1]], axis=-1)      # [G, E]
    bnd = a_ents != ents_shift
    new_add = accept[:, None] & (erange < a_n_w[:, None]) \
        & (pos_e > new_from[:, None]) & bnd                       # [G, E]
    K = tbl_pos.shape[-1]
    cand_pos = jnp.concatenate(
        [jnp.where(old_keep, tbl_pos, 0), jnp.where(new_add, pos_e, 0)], -1)
    cand_term = jnp.concatenate(
        [jnp.where(old_keep, tbl_term, 0), jnp.where(new_add, a_ents, 0)],
        -1)                                                       # [G, K+E]
    cvalid = cand_pos > 0
    # r[i] = number of valid candidates strictly after i; keep the newest
    # K and right-align them at slot K-1-r.
    r = jnp.cumsum(cvalid[:, ::-1], axis=-1)[:, ::-1] - cvalid
    keep = cvalid & (r < K)
    slot = jnp.where(keep, K - 1 - r, K)                          # K = drop
    krange = jnp.arange(K, dtype=slot.dtype)
    hit_k = slot[:, :, None] == krange                            # [G,K+E,K]
    merged_pos = jnp.sum(jnp.where(hit_k, cand_pos[:, :, None], 0), axis=1)
    merged_term = jnp.sum(jnp.where(hit_k, cand_term[:, :, None], 0), axis=1)
    tbl_pos = jnp.where(accept[:, None], merged_pos, tbl_pos)
    tbl_term = jnp.where(accept[:, None], merged_term, tbl_term)
    # Raft Fig. 2: commit = min(leaderCommit, index of last new entry).  The
    # clamp to app_end (not log_len) matters: positions beyond the accepted
    # batch are unverified and may diverge from the leader.
    commit = jnp.where(accept,
                       jnp.maximum(commit0, jnp.minimum(a_commit, app_end)),
                       commit0)

    # ---- Phase 5: AppendEntries responses → leader match/next bookkeeping.
    rs = (inbox.a_type == MSG_RESP) & (inbox.a_term == term[:, None]) \
        & (role == LEADER)[:, None]
    rs_ok = rs & inbox.a_success
    rs_fail = rs & ~inbox.a_success
    match = jnp.where(rs_ok, jnp.maximum(match, inbox.a_match), match)
    next_idx = jnp.where(rs_ok, jnp.maximum(next_idx, inbox.a_match + 1),
                         next_idx)
    # On reject, back off to the follower's conflict hint (its log
    # length), the fast-backoff analog of etcd's rejection hints.  A
    # floor-reject resync request (Phase 4's floor_rej: the follower can
    # only verify appends near its tip) arrives EXPLICITLY marked with
    # FLOOR_HINT_BIAS on the hint; strip the bias and JUMP next_idx up
    # to hint + 1.  Ordinary hints only ever walk next_idx down — with
    # the explicit flag, a late in-flight ordinary reject (whose hint a
    # previous reject already walked below) can no longer be mistaken
    # for a resync and re-probe ground the leader already ruled out.  A
    # stale/bogus biased hint self-corrects: the probe append at the
    # jumped prev is itself verified (or floor-rejected with an honest
    # hint) by the follower.
    is_floor_hint = inbox.a_match >= FLOOR_HINT_BIAS
    hint = inbox.a_match - jnp.where(is_floor_hint, FLOOR_HINT_BIAS, 0)
    walked = jnp.clip(jnp.minimum(next_idx - 1, hint + 1), 1, None)
    next_idx = jnp.where(
        rs_fail,
        jnp.where(is_floor_hint, hint + 1, walked),
        next_idx)
    next_idx = jnp.maximum(next_idx, match + 1)

    # ---- Phase 6: proposals (+ the new-leader no-op entry).
    is_leader = role == LEADER
    # Leadership transfer in flight (thesis §3.10 step 1): the group
    # stops accepting NEW proposals so the target's match can converge
    # on a fixed log tip.  Queued proposals stay queued on the host and
    # drain to the new leader (or to us again, after a host abort clears
    # the latch) — never dropped.  All-NO_XFER (the default) makes this
    # mask all-False and n_acc bit-identical to the untransferred kernel.
    transferring = is_leader & (state.xfer_target != NO_XFER)
    n_acc = jnp.where(transferring, 0, prop_n)
    # Flow control: never let uncommitted depth overrun the term ring.  The
    # no-op consumes space too — a flapping leadership under a stalled
    # commit must not grow the log unboundedly.
    space = jnp.maximum(W - 2 * E - (log_len - commit), 0)
    noop_n = (become_leader & (space >= 1)).astype(I32)
    n_acc = jnp.where(is_leader,
                      jnp.minimum(jnp.minimum(n_acc, E), space - noop_n), 0)
    total_app = noop_n + n_acc
    prop_base = log_len + noop_n
    # Appended entries all carry the leader's current term, so this ring
    # write is a pure mask fill (no scatter, no value gather): slot w is
    # written iff (w - log_len) mod W < total_app, i.e. it holds one of
    # positions log_len+1 .. log_len+total_app.
    if cfg.keep_ring:
        rel6 = (wpos - log_len[:, None]) % W                       # [G, W]
        log_term = jnp.where(rel6 < total_app[:, None], term[:, None],
                             log_term)
    # Table push: appends are all at the leader's current term, so at most
    # one new transition — at the first appended position, iff the log's
    # newest term differs.  Right-aligned layout makes this a static
    # shift-left + write of slot K-1.
    push = (total_app > 0) & (tbl_term[:, K - 1] != term)
    shifted_pos = jnp.concatenate(
        [tbl_pos[:, 1:], (log_len + 1)[:, None]], axis=-1)
    shifted_term = jnp.concatenate(
        [tbl_term[:, 1:], term[:, None]], axis=-1)
    tbl_pos = jnp.where(push[:, None], shifted_pos, tbl_pos)
    tbl_term = jnp.where(push[:, None], shifted_term, tbl_term)
    log_len = log_len + total_app

    def term_of1(idx):  # reads against the POST-append log
        return term_at_tbl(tbl_pos, tbl_term, log_len, idx)

    floor1 = tbl_floor(tbl_pos, log_len)                          # [G]
    match = jnp.where(is_leader[:, None] & self_onehot, log_len[:, None],
                      match)

    scope("raft.commit")    # the quorum reduction
    # ---- Phase 7: leader commit advance — the quorum reduction kernel
    # (selected by cfg.commit_rule; all implement raft Fig. 2's leader
    # rule, see ops/commit_scan.py and ops/pallas_quorum.py).
    # All four kernels take the WRITE quorum (config.py flexible
    # quorums): write_size == cfg.quorum under default geometry, so the
    # static constants (and the masked kernels' None size) compile the
    # digest-pinned program unchanged.
    if cfg.commit_rule == "windowed":
        if cfg.static_full_voters:
            from raftsql_tpu.ops.commit_scan import windowed_commit_index
            commit = windowed_commit_index(
                match, log_term, log_len, commit, term, is_leader,
                quorum=cfg.write_size, window=W)
        else:
            from raftsql_tpu.ops.commit_scan import \
                masked_windowed_commit_index
            commit = masked_windowed_commit_index(
                match, log_term, log_len, commit, term, is_leader,
                voters=voters, voters_joint=jvoters, window=W,
                size=cfg.write_quorum)
    elif cfg.commit_rule == "pallas":
        if cfg.static_full_voters:
            from raftsql_tpu.ops.pallas_quorum import \
                pallas_quorum_commit_index
            commit = pallas_quorum_commit_index(
                match, log_term, log_len, commit, term, is_leader,
                quorum=cfg.write_size, window=W)
        else:
            from raftsql_tpu.ops.pallas_quorum import \
                pallas_masked_quorum_commit_index
            commit = pallas_masked_quorum_commit_index(
                match, log_term, log_len, commit, term, is_leader,
                voters=voters, voters_joint=jvoters, window=W,
                size=cfg.write_quorum)
    elif cfg.static_full_voters:
        commit = quorum_commit_index(
            match, log_term, log_len, commit, term, is_leader,
            quorum=cfg.write_size, window=W, term_of=term_of1)
    else:
        commit = masked_quorum_commit_index(
            match, log_term, log_len, commit, term, is_leader,
            voters=voters, voters_joint=jvoters, window=W,
            term_of=term_of1, size=cfg.write_quorum)

    scope("raft.timers")    # timers, election start, leases
    # ---- Phase 8: timers and election start.  tnow_fire counts as a
    # reset: the transfer target just started a REAL election (Phase 1b)
    # and must not immediately re-fire as a PRECANDIDATE on a stale
    # elapsed counter, which would demote the in-flight candidacy.
    reset = any_grant | any_app | tnow_fire
    elapsed = jnp.where(is_leader | reset, 0, state.elapsed + timer_inc)
    # Learners/spares (self outside both masks) never campaign: their
    # timers tick but cannot fire — they follow whoever the voters
    # elect and wait for a conf entry to promote them.
    fire = (role != LEADER) & (elapsed >= state.timeout) & self_voter
    if cfg.witnesses:
        # Witnesses vote and persist but never campaign or lead: their
        # election timers tick (they still grant, and their timer state
        # feeds the lease exclusion window) but cannot fire.
        fire = fire & ~self_witness
    term_resp = term          # term used in responses composed above
    if cfg.prevote:
        # Timeout starts a PROBE, not an election: role flips to
        # PRECANDIDATE at the unchanged term, self-prevote is tallied,
        # nothing is persisted.  The term bumps only in Phase 3 when a
        # quorum grants the probe — so a partitioned peer can fire
        # forever without inflating its term.
        role = jnp.where(fire, PRECANDIDATE, role)
        votes = jnp.where(fire[:, None],
                         jnp.broadcast_to(self_onehot, (G, P)), votes)
    else:
        term = jnp.where(fire, term + 1, term)
        role = jnp.where(fire, CANDIDATE, role)
        voted = jnp.where(fire, self_id, voted)
        votes = jnp.where(fire[:, None],
                          jnp.broadcast_to(self_onehot, (G, P)), votes)
    leader_hint = jnp.where(fire, NO_LEADER, leader_hint)
    elapsed = jnp.where(fire, 0, elapsed)
    # Per-group timeout re-draw via an integer hash (ops/dense.py): the
    # threefry chain this replaces (~40 HLOs) dominated tick wall time on
    # the TPU path; the hash keeps the same contract (deterministic in
    # seed/peer/tick/global gid, uniform over the span).
    gids = jnp.asarray(group_offset, I32) + jnp.arange(G, dtype=I32)
    new_timeout = dense.election_jitter(
        dense.key_data_of(state.rng), state.tick, gids,
        cfg.election_ticks, 2 * cfg.election_ticks)
    timeout = jnp.where(fire, new_timeout, state.timeout)

    hb = jnp.where(is_leader, state.hb_elapsed + timer_inc, 0)
    # commit > commit0: broadcast the new commit index NOW rather than on
    # the next heartbeat — a follower-proposed entry's ack waits on its
    # proposer LEARNING the commit, and heartbeat-paced propagation put a
    # ~heartbeat/2 floor under propose→ack latency under light load.
    hb_fire = is_leader & ((hb >= cfg.heartbeat_ticks) | become_leader
                           | (total_app > 0) | force_bcast
                           | (commit > commit0))
    hb = jnp.where(hb_fire, 0, hb)

    # ---- Phase 8b: leader leases (raft §6.4.1, config.lease_ticks).
    # Evidence = the newest CURRENT-term append response from each peer
    # (success or reject — either way the responder processed an append
    # at our term, which reset its election timer, Phase 8's `reset`):
    # stamp the device step it was processed at.  A response observed
    # at step T answers a round the responder processed at T-1, so the
    # quorum-th largest stamp minus 1 is when a quorum's election
    # timers were last known reset — any NEW quorum must intersect that
    # set (quorum intersection), and the prevote lease check (Phase 2b)
    # keeps every member of it from granting a probe for election_ticks
    # of its own clock.  The lease never feeds back into consensus:
    # resp_tick/lease are write-only outputs, so a disabled lease
    # (lease_ticks == 0, the default) leaves every trajectory
    # bit-identical with the kernel compiled in.
    tick_now = state.tick
    lease_role = role == LEADER          # post-Phase-8 (leaders never fire)
    resp_tick = jnp.where(bumped[:, None], 0, state.resp_tick)
    resp_tick = jnp.where(rs, tick_now, resp_tick)
    resp_tick = jnp.where(become_leader[:, None], 0, resp_tick)
    # The leader's own slot counts as confirmed NOW; non-leaders carry
    # no evidence at all (a deposed-and-reelected leader restarts its
    # lease from scratch).
    resp_tick = jnp.where(
        lease_role[:, None],
        jnp.where(self_onehot, tick_now, resp_tick), 0)
    if cfg.lease_ticks > 0:
        if cfg.static_full_voters:
            # The lease clock is WRITE-quorum evidence (append acks).
            q_tick = quorum_match_index(resp_tick, cfg.write_size)
        else:
            # Joint consensus: the lease needs a quorum of BOTH masks
            # (a read served on the old majority alone could miss a
            # leader elected by the new one, and vice versa).
            q_tick = jnp.minimum(
                masked_quorum_match_index(resp_tick, voters,
                                          cfg.write_quorum),
                masked_quorum_match_index(resp_tick, jvoters,
                                          cfg.write_quorum))
        # §6.4 precondition, folded in on device: the lease read's
        # target is the leader's commit index, which is only current
        # once an entry of its own term has committed.
        cur_ok = (commit >= 1) & (term_of1(commit) == term)
        lease_until = jnp.where(
            lease_role & cur_ok & (q_tick > 0),
            q_tick - 1 + jnp.int32(cfg.lease_ticks), 0)
    else:
        lease_until = jnp.zeros((G,), I32)

    scope("raft.outbox")    # the outbox and the host-facing info
    # ---- Phase 9: compose the outbox.  Write order = priority order:
    # responses first, then candidate vote-request broadcast, then leader
    # append broadcast.  A later write overriding a response is safe: every
    # message carries the sender term, and raft re-sends on the next tick.
    my_last_term2 = term_of1(log_len)

    is_cand = role == CANDIDATE
    cand_bcast = is_cand[:, None] & ~self_onehot
    # Prevote probes broadcast at term+1 (the term an election WOULD use);
    # prevote responses echo the probed term on grant (so the prober's
    # tally can match it against term+1) and our real term on reject (so
    # a stale prober catches up via the Phase-1 bump rule).
    # Responses OUTRANK the probe broadcast in a contended slot: when two
    # precandidates probe each other, each must answer the other's probe
    # (the probe to that peer re-sends next tick — and a granted answer
    # promotes both, breaking the tie through a real election).  If the
    # probe instead clobbered the response, three simultaneous
    # precandidates would starve forever: a probe can only be answered by
    # a non-probing peer, and none remains.
    pre_bcast = (role == PRECANDIDATE)[:, None] & ~self_onehot
    o_v_type = jnp.where(cand_bcast, MSG_REQ,
                         jnp.where(vreq, MSG_RESP,
                                   jnp.where(preq, MSG_PRERESP,
                                             jnp.where(pre_bcast, MSG_PREREQ,
                                                       MSG_NONE))))
    resp_term = jnp.where(pre_grant, inbox.v_term,
                          jnp.broadcast_to(vterm_resp[:, None], (G, P)))
    o_v_term = jnp.where(cand_bcast, term[:, None],
                         jnp.where(vreq | preq, resp_term,
                                   jnp.where(pre_bcast, term[:, None] + 1,
                                             resp_term)))
    o_v_last_idx = jnp.broadcast_to(log_len[:, None], (G, P))
    o_v_last_term = jnp.broadcast_to(my_last_term2[:, None], (G, P))
    o_v_granted = (grant | pre_grant) & ~cand_bcast

    # Leadership transfer, leader side (thesis §3.10 steps 2-3): while a
    # transfer is armed, fire MSG_TIMEONOW at the target once its MATCH
    # covers our whole log — re-sent every tick while the latch holds,
    # so a lost grant costs a tick, not the transfer.  The target must
    # be a real peer and a voter under the ACTIVE configuration (either
    # mask during a joint change — the same eligibility the vote-grant
    # gate enforces, so an electable target is never refused and a
    # learner/spare never granted).  The write tops the vote-slot
    # priority chain for that one dst; a clobbered response re-sends
    # next tick (raft tolerates loss).  All-NO_XFER keeps every gate
    # here false.
    xfer = state.xfer_target                                      # [G]
    tgt_clip = jnp.clip(xfer, 0, P - 1)
    tgt_is_voter = dense.pick_peer(
        (voters | jvoters).astype(I32), tgt_clip) > 0             # [G]
    tgt_caught = dense.pick_peer(match, tgt_clip) >= log_len      # [G]
    send_tnow = transferring & (xfer >= 0) & (xfer < P) \
        & (xfer != self_id) & tgt_is_voter
    if not cfg.unsafe_transfer:
        send_tnow = send_tnow & tgt_caught
    tnow_dst = send_tnow[:, None] & (src_ids == tgt_clip[:, None])  # [G, P]
    o_v_type = jnp.where(tnow_dst, MSG_TIMEONOW, o_v_type)
    o_v_term = jnp.where(tnow_dst, term[:, None], o_v_term)
    o_v_granted = o_v_granted & ~tnow_dst
    if cfg.unsafe_transfer:
        # FALSIFICATION ONLY (config.py unsafe_transfer): fire without
        # the catch-up gate and abdicate the instant the grant goes out
        # — the §3.10 mistake the transfer chaos family must catch.
        role = jnp.where(send_tnow, FOLLOWER, role)
        leader_hint = jnp.where(send_tnow, NO_LEADER, leader_hint)

    # Append responses (to every append request seen, incl. stale-term ones
    # so old leaders step down).
    chosen_mask = areq_cur & (src_ids == asrc[:, None]) & any_app[:, None]
    succ = chosen_mask & accept[:, None]
    # Conflict hint on reject: our pre-append log length — EXCEPT when
    # the reject was a FLOOR reject (prev below what our transition
    # table can verify): then the useful serving point is our full log
    # length, whose prev we can always verify (floor <= newest
    # transition <= log_len), and a hint at-or-beyond the leader's send
    # point tells it to resync UP (Phase 5) instead of walking down —
    # without this, a leader serving below a restarted follower's floor
    # walks next_idx to 1 and the pair livelocks on rejects.
    floor_rej = chosen_mask & ~accept[:, None] & (prev < floor0)[:, None]
    rej_hint = jnp.clip(jnp.minimum(prev - 1, follower_len0), 0, None)
    # Floor rejects carry the follower's full log length PLUS the
    # explicit FLOOR_HINT_BIAS marker (see Phase 5 / config.py): the
    # leader must resync UP to this tip, not walk down.
    resp_match = jnp.where(
        succ, app_end[:, None],
        jnp.where(floor_rej, follower_len0[:, None] + FLOOR_HINT_BIAS,
                  jnp.where(chosen_mask, rej_hint[:, None], 0)))

    # Leader append broadcast: to every peer with pending entries, plus
    # everyone on heartbeat.
    send_app = is_leader[:, None] & ~self_onehot & (
        hb_fire[:, None] | (next_idx <= log_len[:, None]))
    prev_s = jnp.clip(next_idx - 1, 0, log_len[:, None])          # [G, P]
    n_s = jnp.clip(log_len[:, None] - prev_s, 0, E)
    # Term-window guard: every position this message reads (prev_s and
    # the batch entries) must still have a KNOWN term — inside the W
    # ring AND at or above the transition-table floor.  A follower
    # lagging past either limit instead gets an EMPTY heartbeat at
    # prev=0, which resets its election timer either way: a receiver
    # whose own table floor is <= 1 accepts it (matches, carries no
    # entries, commit clamp min(leaderCommit, app_end=0) moves
    # nothing), while one whose floor rose past 1 (post-install, or >K
    # transitions) REJECTS it — harmless churn, since the timer reset
    # rides any_app, not accept.  Either way the laggard cannot depose
    # the live leader by starting elections, cannot win one meanwhile
    # (log up-to-dateness check), and actual catch-up is host-mediated
    # (runtime/node.py) — so safety holds while it lags.
    win_floor = log_len[:, None] - W                              # [G, 1]
    min_acc = jnp.where(prev_s > 0, prev_s,
                        jnp.where(n_s > 0, 1, 0))
    in_window = (min_acc == 0) | ((min_acc > win_floor)
                                  & (min_acc >= floor1[:, None]))
    prev_s = jnp.where(in_window, prev_s, 0)
    n_s = jnp.where(in_window, n_s, 0)
    prev_t_s = term_of1(prev_s)                                   # [G, P]
    ent_pos_s = prev_s[:, :, None] + 1 \
        + jnp.arange(E, dtype=I32)[None, None, :]                 # [G, P, E]
    ents_s = term_of1(ent_pos_s.reshape(G, P * E)).reshape(G, P, E)

    # Pipelined replication (etcd's optimistic sendAppend): advance
    # next_idx past the entries just sent instead of idling an ack round
    # trip — successive ticks then stream DISJOINT batches, so per-group
    # throughput is E entries/tick, not E per RTT, and the propose→commit
    # queue never builds to the flow-control ceiling.  A lost message
    # surfaces as a reject whose conflict hint walks next_idx back
    # (Phase 5), exactly as for any stale next_idx.
    #
    # The advance is capped at max_inflight_msgs batches beyond the
    # follower's acked match (the reference's MaxInflightMsgs window,
    # raft.go:158).  Without the cap, a follower ticking slower than its
    # leader under the newest-wins inbox slot would see only every other
    # (disjoint) batch and reject forever — capped, the leader stalls at
    # the window edge and re-sends the SAME batch each tick until an ack
    # drains it, which a slow follower always eventually processes.
    # maximum(): the cap may sit below a next_idx already learned from a
    # reject hint — stall (never regress) rather than re-send entries the
    # follower already acknowledged holding.
    inflight_cap = match + 1 + cfg.max_inflight_msgs * E
    next_idx = jnp.where(send_app & (n_s > 0),
                         jnp.maximum(next_idx,
                                     jnp.minimum(prev_s + n_s + 1,
                                                 inflight_cap)),
                         next_idx)

    o_a_type = jnp.where(send_app, MSG_REQ,
                         jnp.where(areq, MSG_RESP, MSG_NONE))
    o_a_term = jnp.where(send_app, term[:, None],
                         jnp.broadcast_to(term_resp[:, None], (G, P)))
    o_a_prev_idx = jnp.where(send_app, prev_s, 0)
    o_a_prev_term = jnp.where(send_app, prev_t_s, 0)
    o_a_n = jnp.where(send_app, n_s, 0)
    o_a_ents = jnp.where(send_app[:, :, None], ents_s, 0)
    o_a_commit = jnp.where(send_app, commit[:, None], 0)
    o_a_success = succ & ~send_app
    o_a_match = jnp.where(send_app, 0, resp_match)

    # The two type-code planes are built from Python MSG_* literals, so
    # their jnp.where chains come out weakly-typed — and a jit step
    # traced on a strong empty inbox then RETRACES when its own output
    # is fed back on the next tick (the jit-stability tripwire catches
    # this as a second compile).  Pin them strong to the inbox schema.
    o_v_type = o_v_type.astype(I32)
    o_a_type = o_a_type.astype(I32)
    outbox = Outbox(
        v_type=o_v_type, v_term=o_v_term, v_last_idx=o_v_last_idx,
        v_last_term=o_v_last_term, v_granted=o_v_granted,
        a_type=o_a_type, a_term=o_a_term, a_prev_idx=o_a_prev_idx,
        a_prev_term=o_a_prev_term, a_n=o_a_n, a_ents=o_a_ents,
        a_commit=o_a_commit, a_success=o_a_success, a_match=o_a_match)

    # Transfer latch carry: held only while this row still LEADS the
    # group.  Deposition — by the target's term+1 election (completion),
    # by any other election, or by the unsafe-variant abdication — clears
    # it on device, which is also the host's completion signal (the
    # "xfer" info column below drops to NO_XFER).  A latch armed on a
    # non-leader row (host race with an election) clears the same way.
    xfer = jnp.where(role == LEADER, xfer, NO_XFER)

    new_state = PeerState(
        term=term, voted_for=voted, role=role, leader_hint=leader_hint,
        commit=commit, log_len=log_len, log_term=log_term,
        tbl_pos=tbl_pos, tbl_term=tbl_term,
        elapsed=elapsed, timeout=timeout, hb_elapsed=hb,
        votes=votes, match=match, next_idx=next_idx,
        voters=voters, voters_joint=jvoters,
        resp_tick=resp_tick, xfer_target=xfer,
        rng=state.rng, tick=state.tick + 1)

    # Ticks until any timer could fire with no further input: non-leader
    # election countdown vs leader heartbeat countdown, min over groups,
    # clamped >= 1 (the step that fires a timer resets it, so the true
    # margin after a step is always positive).
    is_leader2 = role == LEADER
    big = jnp.int32(1 << 30)
    elec_rem = jnp.where(is_leader2, big, timeout - elapsed)
    hb_rem = jnp.where(is_leader2, cfg.heartbeat_ticks - hb, big)
    timer_margin = jnp.maximum(
        jnp.minimum(jnp.min(elec_rem), jnp.min(hb_rem)), 1)

    info = StepInfo(
        commit=commit, role=role, term=term, voted_for=voted,
        leader_hint=leader_hint,
        prop_base=prop_base, prop_accepted=n_acc, noop=noop_n > 0,
        app_from=jnp.where(accept, asrc, -1),
        app_start=jnp.where(accept, prev + 1, 0),
        app_n=jnp.where(accept, a_n, 0),
        app_conflict=conflict,
        new_log_len=log_len,
        lease=lease_until,
        xfer=xfer,
        next_idx=next_idx,
        floor=floor1,
        timer_margin=timer_margin)

    return new_state, outbox, info


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))
def peer_step_jit(cfg: RaftConfig, state: PeerState, inbox: Inbox,
                  prop_n: jax.Array, self_id: jax.Array,
                  timer_inc: jax.Array | int = 1,
                  force_bcast: jax.Array | bool = False):
    return peer_step(cfg, state, inbox, prop_n, self_id,
                     timer_inc=timer_inc, force_bcast=force_bcast)


# ---------------------------------------------------------------------------
# Packed host boundary.
#
# The runtime's tick (runtime/node.py) crosses host<->device once per step;
# shipping the Inbox as 14 arrays and reading back Outbox+StepInfo as ~30
# cost ~8x the step kernel itself in per-array dispatch overhead at small G
# (measured: 5.7 ms vs 0.7 ms per step, 3 contended processes, CPU
# backend).  The packed forms move ONE array each way; the slices/stack
# below happen inside the compiled program where XLA fuses them to nothing.

# Column order of the packed [G, P, IB_NCOLS + E] message block (shared by
# inbox and outbox; a_ents occupies the trailing E columns).
MSG_FIELDS = ("v_type", "v_term", "v_last_idx", "v_last_term", "v_granted",
              "a_type", "a_term", "a_prev_idx", "a_prev_term", "a_n",
              "a_commit", "a_success", "a_match")
IB_NCOLS = len(MSG_FIELDS)
# Column order of the packed [G, INFO_NCOLS] StepInfo block (next_idx and
# timer_margin ride alongside, unpacked).
INFO_FIELDS = ("commit", "role", "term", "voted_for", "leader_hint",
               "prop_base", "prop_accepted", "noop", "app_from",
               "app_start", "app_n", "app_conflict", "new_log_len",
               "floor", "lease", "xfer")
INFO_NCOLS = len(INFO_FIELDS)


def unpack_inbox(packed: jax.Array) -> Inbox:
    f = {n: packed[:, :, i] for i, n in enumerate(MSG_FIELDS)}
    f["v_granted"] = f["v_granted"].astype(bool)
    f["a_success"] = f["a_success"].astype(bool)
    return Inbox(a_ents=packed[:, :, IB_NCOLS:], **f)


def pack_outbox(ob: Outbox) -> jax.Array:
    head = jnp.stack([getattr(ob, n).astype(I32) for n in MSG_FIELDS],
                     axis=-1)
    return jnp.concatenate([head, ob.a_ents.astype(I32)], axis=-1)


def pack_info(info: StepInfo) -> jax.Array:
    return jnp.stack([getattr(info, n).astype(I32) for n in INFO_FIELDS],
                     axis=-1)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))
def peer_step_packed(cfg: RaftConfig, state: PeerState, packed: jax.Array,
                     prop_n: jax.Array, self_id: jax.Array,
                     timer_inc: jax.Array | int = 1,
                     force_bcast: jax.Array | bool = False):
    """peer_step with single-array host I/O: `packed` is
    [G, P, IB_NCOLS+E] i32; returns (state, packed_outbox [G, P,
    IB_NCOLS+E], packed_info [G, INFO_NCOLS], next_idx [G, P],
    timer_margin [])."""
    st, ob, info = peer_step(cfg, state, unpack_inbox(packed), prop_n,
                             self_id, timer_inc=timer_inc,
                             force_bcast=force_bcast)
    return (st, pack_outbox(ob), pack_info(info), info.next_idx,
            info.timer_margin)
