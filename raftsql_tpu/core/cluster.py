"""Fused whole-cluster stepping: P peers × G groups in one device program.

The reference runs each raft peer as a separate OS process wired by HTTP
streams (reference raft.go:248-266, Procfile).  On TPU, when a cluster's
peers are co-located on one chip (the benchmark configuration in
BASELINE.json), we instead *stack* all P peers' states on the leading axis,
vmap the peer transition over it, and deliver messages by transposing the
outbox — src→dst becomes dst→src with a single `swapaxes`, entirely
on-device.  Consensus for the whole cluster then advances via `lax.scan`
with zero host round-trips per tick.

The same `peer_step` also serves the distributed deployment (one PeerState
per host, transport carrying outboxes over DCN) — see runtime/node.py and
transport/.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.core.state import (I32, Inbox, Outbox, PeerState, StepInfo,
                                    empty_inbox, init_peer_state)
from raftsql_tpu.core.step import pack_info, peer_step


def init_cluster_state(cfg: RaftConfig, seed: int | None = None) -> PeerState:
    """Stacked PeerState with a leading peers axis: every leaf [P, ...]."""
    states = [init_peer_state(cfg, p, seed) for p in range(cfg.num_peers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def empty_cluster_inbox(cfg: RaftConfig) -> Inbox:
    boxes = [empty_inbox(cfg) for _ in range(cfg.num_peers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *boxes)


def deliver(outbox: Outbox) -> Inbox:
    """In-device message delivery: [src, G, dst, ...] -> [dst, G, src, ...].

    This transpose is the entire transport for co-located peers — the moral
    equivalent of the reference's rafthttp streams (raft.go:230, 268-270)
    collapsing into a data-layout change.  On a multi-chip mesh with the
    peer axis sharded, the same operation becomes an `all_to_all` over ICI
    (see parallel/sharded.py).
    """
    return jax.tree.map(lambda x: jnp.swapaxes(x, 0, 2), outbox)


def cluster_step(cfg: RaftConfig, states: PeerState, inboxes: Inbox,
                 prop_n: jax.Array, timer_inc: jax.Array | int = 1
                 ) -> Tuple[PeerState, Inbox, StepInfo]:
    """One tick for the whole co-located cluster.

    Args:
      states: stacked PeerState, leaves [P, ...].
      inboxes: stacked Inbox, leaves [P, G, P, ...].
      prop_n: [P, G] i32 — proposals submitted at each peer this tick (only
        the leader's are accepted; host routes via leader_hint).
      timer_inc: scalar (lockstep) or [P] i32 — PER-PEER election/
        heartbeat timer advance this step.  Real deployments never tick
        in lockstep; a [P] vector lets peers drift (chaos clock-skew
        schedules, and any future per-peer pacing).  Each peer's scalar
        reaches core/step.py's timer_inc unchanged, so timer semantics
        per peer are identical to the distributed runtime's.

    Returns:
      (new_states, delivered_inboxes_for_next_tick, stacked_infos).
    """
    def _one(st, ib, pn, sid, t):
        return peer_step(cfg, st, ib, pn, sid, timer_inc=t)

    # A stable name for the whole step in the lowered program and in a
    # device trace, around peer_step's own phase names (core/step.py
    # STEP_SCOPES); metadata only.
    with jax.named_scope("cluster_step"):
        self_ids = jnp.arange(cfg.num_peers, dtype=I32)
        ti = jnp.broadcast_to(jnp.asarray(timer_inc, I32),
                              (cfg.num_peers,))
        new_states, outboxes, infos = jax.vmap(_one)(
            states, inboxes, prop_n, self_ids, ti)
        with jax.named_scope("raft.deliver"):
            delivered = deliver(outboxes)
    return new_states, delivered, infos


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def cluster_step_jit(cfg: RaftConfig, states: PeerState, inboxes: Inbox,
                     prop_n: jax.Array, timer_inc: jax.Array | int = 1):
    return cluster_step(cfg, states, inboxes, prop_n, timer_inc)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def cluster_step_host(cfg: RaftConfig, states: PeerState, inboxes: Inbox,
                      prop_n: jax.Array, timer_inc: jax.Array | int = 1):
    """Fused step for the DURABLE co-located runtime (runtime/fused.py):
    messages stay on device (the delivered inboxes are returned as
    opaque carry), and the host-facing StepInfo crosses as ONE packed
    [P, G, INFO_NCOLS] array (core/step.py pack_info) — the host pays a
    single transfer per tick however many peers and groups advance.

    The extra scalar `busy` reports device-only protocol work the
    packed info cannot show — vote traffic, entry-carrying appends, and
    REJECTED append responses (a post-restart log-reconciliation walk
    is nothing but probe/reject rounds with zero host-visible effect).
    The runtime's idle-parking loop must keep full pace while it is
    set; steady-state heartbeats (empty REQ, successful RESP) do not
    count, so a settled cluster still parks."""
    from raftsql_tpu.config import MSG_REQ, MSG_RESP

    st, ib, infos = cluster_step(cfg, states, inboxes, prop_n, timer_inc)
    busy = (jnp.any(ib.v_type != 0)
            | jnp.any((ib.a_type == MSG_REQ) & (ib.a_n > 0))
            | jnp.any((ib.a_type == MSG_RESP) & ~ib.a_success))
    with jax.named_scope("raft.pack"):
        packed = jax.vmap(pack_info)(infos)
    return st, ib, packed, busy


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1, 2))
def cluster_multistep_host(cfg: RaftConfig, states: PeerState,
                           inboxes: Inbox, steps: int, prop_n: jax.Array,
                           timer_inc: jax.Array | int = 1):
    """`steps` fused steps in ONE dispatch, for the co-located durable
    runtime (runtime/fused.py steps_per_dispatch): the fixed cost of a
    dispatch and of its readback is paid once per S consensus steps
    instead of once per step, and a proposal entering at the dispatch
    boundary commits INSIDE the dispatch (the 3-step pipeline runs to
    completion before the host's durable barrier).

    Safe for the single-process cluster only: intra-dispatch message
    exchange is not individually durable, which is sound there because
    the process is the failure domain — a crash loses every peer at
    once and replay rebuilds from the WALs the host wrote (all S steps'
    appends + the final hard state) before anything was published.

    Proposals arrive PER STEP (`prop_n` is [S, P, G] — the host chunks
    its backlog ≤E per step, so one dispatch accepts and commits up to
    S×E per group); packed host-facing info returns PER STEP, stacked
    [S, P, G, C], so the host replays its durable phases in step
    order.  busy is OR-reduced across steps."""
    from raftsql_tpu.config import MSG_REQ, MSG_RESP

    def body(carry, prop_t):
        st, ib = carry
        st, ib, info = cluster_step(cfg, st, ib, prop_t, timer_inc)
        busy_s = (jnp.any(ib.v_type != 0)
                  | jnp.any((ib.a_type == MSG_REQ) & (ib.a_n > 0))
                  | jnp.any((ib.a_type == MSG_RESP) & ~ib.a_success))
        return (st, ib), (jax.vmap(pack_info)(info), busy_s)

    (states, inboxes), (pinfos, busys) = jax.lax.scan(
        body, (states, inboxes), prop_n, length=steps)
    return states, inboxes, pinfos, jnp.any(busys)


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1, 2))
def cluster_run(cfg: RaftConfig, states: PeerState, inboxes: Inbox,
                num_ticks: int, prop_n: jax.Array
                ) -> Tuple[PeerState, Inbox, StepInfo]:
    """Scan `num_ticks` fused steps on device; prop_n is [T, P, G].

    Returns the final state plus per-tick stacked infos [T, P, G] — the
    benchmark harness reduces those on device to commit counts so only
    scalars cross the host boundary.
    """

    def body(carry, prop_t):
        st, ib = carry
        st, ib, info = cluster_step(cfg, st, ib, prop_t)
        return (st, ib), info

    (states, inboxes), infos = jax.lax.scan(body, (states, inboxes), prop_n,
                                            length=num_ticks)
    return states, inboxes, infos
