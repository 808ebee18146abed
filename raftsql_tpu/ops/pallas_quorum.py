"""Hand-written Pallas TPU kernel for the quorum commit reduction.

The quorum/commit advance (`ops.quorum.quorum_commit_index`) is the hot
reduction of the batched consensus step — the math of vendored etcd/raft's
`maybeCommit` (driven from the reference's event loop, raft.go:224-235)
over ALL groups at once.  XLA's fused sort+gather handles it well at small
P; this kernel removes the sort entirely:

  q-th largest of P match values == max_i { match[i] : #{j : match[j] >=
  match[i]} >= quorum }

which is an O(P^2) comparison network — P static VPU passes over a [Gb, P]
block, no data movement.  The entry-term lookup is a one-hot reduction over
the ring axis instead of a gather (gathers are the thing to avoid on the
VPU; a masked sum over W lanes fuses).

Blocks stream G in `block_g`-row tiles through VMEM; all shapes static.
Off a TPU the kernel runs in interpreter mode (slow, but keeps tests
hermetic on the CPU CI platform); on a TPU it is always compiled.

Standing: `commit_rule="point"` is the default at every P and this
kernel is off the served path.  The round-5 rules race on a chip (at
`e932a09`, 2026-07-31; the capture left the tree in PR 21 and stays in
git history) had `point` ahead at every benched shape — 287.8M vs 78.8M
commits/s at G=10k/P=3 — and at G=2k/P=15 this kernel's compile did not
finish inside the bench timeout.  It is kept as a tested reference of
the comparison-network idea; ROADMAP Queue 3 item 3 removes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

I32 = jnp.int32


def _interpret() -> bool:
    """Interpreter mode off a TPU only — on a TPU the kernel is compiled
    by Mosaic, never interpreted, and no caller can ask otherwise."""
    return jax.default_backend() != "tpu"


def _kernel(quorum: int, window: int,
            match_ref, log_term_ref, log_len_ref, commit_ref, term_ref,
            leader_ref, out_ref):
    match = match_ref[:]                      # [Gb, P]
    ring = log_term_ref[:]                    # [Gb, W]
    log_len = log_len_ref[:]                  # [Gb, 1]
    commit = commit_ref[:]                    # [Gb, 1]
    term = term_ref[:]                        # [Gb, 1]
    is_leader = leader_ref[:] != 0            # [Gb, 1]
    P = match.shape[-1]

    # q-th largest via the comparison network (static P-pass loop).
    cand = jnp.zeros_like(commit)             # [Gb, 1]
    for i in range(P):
        mi = match[:, i:i + 1]                # [Gb, 1]
        cnt = jnp.sum((match >= mi).astype(I32), axis=-1, keepdims=True)
        cand = jnp.where((cnt >= quorum) & (mi > cand), mi, cand)

    # term_of(cand) without a gather: one-hot over the ring axis.
    slot = (cand - 1) % window                # [Gb, 1]
    lanes = jax.lax.broadcasted_iota(I32, ring.shape, 1)
    cand_term = jnp.sum(jnp.where(lanes == slot, ring, 0), axis=-1,
                        keepdims=True)
    valid = (cand >= 1) & (cand <= log_len)
    cand_term = jnp.where(valid, cand_term, 0)

    ok = is_leader & (cand_term == term) & (cand > commit)
    out_ref[:] = jnp.where(ok, cand, commit)


def pallas_quorum_commit_index(match: jax.Array, log_term: jax.Array,
                               log_len: jax.Array, commit: jax.Array,
                               term: jax.Array, is_leader: jax.Array,
                               *, quorum: int, window: int,
                               block_g: int = 1024) -> jax.Array:
    """Drop-in replacement for `ops.quorum.quorum_commit_index`."""
    G, P = match.shape
    gb = min(block_g, G)
    pad = (-G) % gb
    col = lambda x: x.astype(I32).reshape(G, 1)
    args = (match.astype(I32), log_term.astype(I32), col(log_len),
            col(commit), col(term), col(is_leader))
    if pad:
        args = tuple(jnp.pad(x, ((0, pad), (0, 0))) for x in args)
    gp = G + pad

    widths = (P, window, 1, 1, 1, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, quorum, window),
        grid=(gp // gb,),
        in_specs=[pl.BlockSpec((gb, w), lambda i: (i, 0)) for w in widths],
        out_specs=pl.BlockSpec((gb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((gp, 1), I32),
        interpret=_interpret(),
    )(*args)
    return out[:G, 0]


# ---------------------------------------------------------------------------
# Mask-weighted variant (dynamic membership, raftsql_tpu/membership/):
# the static quorum constant becomes per-group [G, P] voter masks (plus
# the second joint-consensus mask), still one comparison network — the
# count just multiplies by the mask and the threshold is a per-row
# popcount majority.  With full masks this reproduces the static kernel
# exactly (tests/test_membership.py property-tests both paths).

_NEG = -(1 << 30)


def _masked_kernel(window: int, size,
                   match_ref, vot_ref, jvot_ref, log_term_ref,
                   log_len_ref, commit_ref, term_ref, leader_ref,
                   out_ref):
    match = match_ref[:]                      # [Gb, P]
    vot = vot_ref[:] != 0                     # [Gb, P]
    jvot = jvot_ref[:] != 0                   # [Gb, P]
    ring = log_term_ref[:]                    # [Gb, W]
    log_len = log_len_ref[:]                  # [Gb, 1]
    commit = commit_ref[:]                    # [Gb, 1]
    term = term_ref[:]                        # [Gb, 1]
    is_leader = leader_ref[:] != 0            # [Gb, 1]
    P = match.shape[-1]

    def qidx(mask):
        m = jnp.where(mask, match, _NEG)
        mi32 = mask.astype(I32)
        nv = jnp.sum(mi32, axis=-1, keepdims=True)      # [Gb, 1]
        need = nv // 2 + 1
        if size is not None:
            # Flexible write quorum on FULL masks only (mask_threshold
            # contract, ops/quorum.py): reduced masks keep majority.
            need = jnp.where(nv == P, I32(size), need)
        cand = jnp.full_like(commit, _NEG)
        for i in range(P):
            mi = m[:, i:i + 1]
            cnt = jnp.sum((m >= mi).astype(I32) * mi32, axis=-1,
                          keepdims=True)
            ok = mask[:, i:i + 1] & (cnt >= need) & (mi > cand)
            cand = jnp.where(ok, mi, cand)
        # Empty mask (all-learner group): no quorum index exists.
        return jnp.where(nv > 0, jnp.maximum(cand, 0), 0)

    # Joint consensus: the candidate must hold on BOTH masks.
    cand = jnp.minimum(qidx(vot), qidx(jvot))

    slot = (cand - 1) % window                # [Gb, 1]
    lanes = jax.lax.broadcasted_iota(I32, ring.shape, 1)
    cand_term = jnp.sum(jnp.where(lanes == slot, ring, 0), axis=-1,
                        keepdims=True)
    valid = (cand >= 1) & (cand <= log_len)
    cand_term = jnp.where(valid, cand_term, 0)

    ok = is_leader & (cand_term == term) & (cand > commit)
    out_ref[:] = jnp.where(ok, cand, commit)


def pallas_masked_quorum_commit_index(
        match: jax.Array, log_term: jax.Array, log_len: jax.Array,
        commit: jax.Array, term: jax.Array, is_leader: jax.Array,
        *, voters: jax.Array, voters_joint: jax.Array, window: int,
        size=None, block_g: int = 1024) -> jax.Array:
    """Mask-weighted drop-in for `ops.quorum.masked_quorum_commit_index`."""
    G, P = match.shape
    gb = min(block_g, G)
    pad = (-G) % gb
    col = lambda x: x.astype(I32).reshape(G, 1)
    args = (match.astype(I32), voters.astype(I32),
            voters_joint.astype(I32), log_term.astype(I32),
            col(log_len), col(commit), col(term), col(is_leader))
    if pad:
        args = tuple(jnp.pad(x, ((0, pad), (0, 0))) for x in args)
    gp = G + pad

    widths = (P, P, P, window, 1, 1, 1, 1)
    out = pl.pallas_call(
        functools.partial(_masked_kernel, window, size),
        grid=(gp // gb,),
        in_specs=[pl.BlockSpec((gb, w), lambda i: (i, 0)) for w in widths],
        out_specs=pl.BlockSpec((gb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((gp, 1), I32),
        interpret=_interpret(),
    )(*args)
    return out[:G, 0]
