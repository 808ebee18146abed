"""Unit tests for the quorum / commit-scan / pallas kernels against a
straightforward numpy model of raft's commit rule (Figure 2 leader rule:
advance commit to the largest N replicated on a quorum with term match)."""
import numpy as np
import jax.numpy as jnp
import pytest

from raftsql_tpu.ops.commit_scan import (commit_latency_ticks,
                                         running_commit,
                                         windowed_commit_index)
from raftsql_tpu.ops.pallas_quorum import pallas_quorum_commit_index
from raftsql_tpu.ops.quorum import quorum_commit_index, quorum_match_index


def _random_case(rng, G=64, P=5, W=32):
    log_len = rng.integers(0, W, G).astype(np.int32)
    commit = np.array([rng.integers(0, l + 1) for l in log_len], np.int32)
    term = rng.integers(1, 5, G).astype(np.int32)
    # Ring with plausible terms at resident positions.
    log_term = np.zeros((G, W), np.int32)
    for g in range(G):
        t = 1
        for n in range(1, log_len[g] + 1):
            if rng.random() < 0.2 and t < term[g]:
                t += 1
            log_term[g, (n - 1) % W] = t
    match = np.minimum(rng.integers(0, W, (G, P)), log_len[:, None])
    match = match.astype(np.int32)
    is_leader = rng.random(G) < 0.7
    return match, log_term, log_len, commit, term, is_leader


def _model_commit(match, log_term, log_len, commit, term, is_leader,
                  quorum, point_only):
    """Direct per-group evaluation of the leader commit rule."""
    G, P = match.shape
    W = log_term.shape[1]
    out = commit.copy()
    for g in range(G):
        if not is_leader[g]:
            continue
        qm = int(np.sort(match[g])[P - quorum])
        cands = [qm] if point_only else range(qm, commit[g], -1)
        for n in cands:
            if n <= commit[g] or n < 1 or n > log_len[g]:
                continue
            if log_term[g, (n - 1) % W] == term[g]:
                out[g] = max(out[g], n)
                break
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_quorum_commit_matches_model(seed):
    rng = np.random.default_rng(seed)
    match, log_term, log_len, commit, term, is_leader = _random_case(rng)
    got = np.asarray(quorum_commit_index(
        jnp.asarray(match), jnp.asarray(log_term), jnp.asarray(log_len),
        jnp.asarray(commit), jnp.asarray(term), jnp.asarray(is_leader),
        quorum=3, window=32))
    want = _model_commit(match, log_term, log_len, commit, term, is_leader,
                         3, point_only=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_windowed_commit_matches_model(seed):
    rng = np.random.default_rng(seed)
    match, log_term, log_len, commit, term, is_leader = _random_case(rng)
    got = np.asarray(windowed_commit_index(
        jnp.asarray(match), jnp.asarray(log_term), jnp.asarray(log_len),
        jnp.asarray(commit), jnp.asarray(term), jnp.asarray(is_leader),
        quorum=3, window=32))
    want = _model_commit(match, log_term, log_len, commit, term, is_leader,
                         3, point_only=False)
    np.testing.assert_array_equal(got, want)


def test_windowed_never_below_point():
    # The windowed rule commits whenever the point rule does, plus cases
    # where the quorum index sits on an old-term entry.
    rng = np.random.default_rng(7)
    for _ in range(5):
        match, log_term, log_len, commit, term, is_leader = _random_case(rng)
        a = np.asarray(quorum_commit_index(
            jnp.asarray(match), jnp.asarray(log_term), jnp.asarray(log_len),
            jnp.asarray(commit), jnp.asarray(term), jnp.asarray(is_leader),
            quorum=3, window=32))
        b = np.asarray(windowed_commit_index(
            jnp.asarray(match), jnp.asarray(log_term), jnp.asarray(log_len),
            jnp.asarray(commit), jnp.asarray(term), jnp.asarray(is_leader),
            quorum=3, window=32))
        assert (b >= a).all()


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("P,quorum", [(3, 2), (5, 3)])
def test_pallas_quorum_matches_reference(seed, P, quorum):
    rng = np.random.default_rng(seed)
    match, log_term, log_len, commit, term, is_leader = _random_case(
        rng, G=100, P=P)
    args = (jnp.asarray(match), jnp.asarray(log_term), jnp.asarray(log_len),
            jnp.asarray(commit), jnp.asarray(term), jnp.asarray(is_leader))
    want = np.asarray(quorum_commit_index(*args, quorum=quorum, window=32))
    got = np.asarray(pallas_quorum_commit_index(
        *args, quorum=quorum, window=32, block_g=32))
    np.testing.assert_array_equal(got, want)


def test_quorum_match_index_is_qth_largest():
    m = jnp.asarray([[3, 1, 2], [5, 5, 0]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(quorum_match_index(m, 2)), [2, 5])


def test_running_commit_and_latency():
    cand = jnp.asarray([[0, 1], [2, 0], [1, 3], [0, 2]], jnp.int32)
    traj = np.asarray(running_commit(cand))
    np.testing.assert_array_equal(traj, [[0, 1], [2, 1], [2, 3], [2, 3]])
    lat = np.asarray(commit_latency_ticks(jnp.asarray(traj),
                                          jnp.asarray([2, 3], jnp.int32)))
    np.testing.assert_array_equal(lat, [1, 2])
    # Never-committed target -> T.
    lat2 = np.asarray(commit_latency_ticks(jnp.asarray(traj),
                                           jnp.asarray([9, 3], jnp.int32)))
    np.testing.assert_array_equal(lat2, [4, 2])


@pytest.mark.parametrize("rule", ["windowed", "pallas"])
def test_cluster_converges_under_alternate_commit_rules(rule):
    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.core import cluster

    cfg = RaftConfig(num_groups=4, num_peers=3, log_window=32,
                     max_entries_per_msg=4, commit_rule=rule)
    st = cluster.init_cluster_state(cfg)
    ib = cluster.empty_cluster_inbox(cfg)
    st, ib, _ = cluster.cluster_run(cfg, st, ib, 60,
                                    jnp.zeros((60, 3, 4), jnp.int32))
    roles = np.asarray(st.role)
    assert ((roles == 2).sum(axis=0) == 1).all(), roles
    st, ib, _ = cluster.cluster_run(cfg, st, ib, 20,
                                    jnp.full((20, 3, 4), 2, jnp.int32))
    assert (np.asarray(st.commit) >= 3).all()


# ---------------------------------------------------------------------------
# Dense (one-hot) gather path — the lowering the TPU deployment actually
# runs (ops/dense.py).  CI is CPU-only, where use_dense() picks the native
# gather, so these tests pin both paths explicitly and (a) check the dense
# primitives against their gather duals eagerly, (b) run a full fused
# cluster and require BIT-IDENTICAL state trajectories under both
# lowerings.
# ---------------------------------------------------------------------------


def test_dense_primitives_match_gather_duals(monkeypatch):
    from raftsql_tpu.ops import dense

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 50, (3, 40, 64)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 64, (3, 40, 9)), jnp.int32)
    monkeypatch.setenv("RAFTSQL_DENSE", "1")
    got = dense.take_last(x, idx)
    monkeypatch.setenv("RAFTSQL_DENSE", "0")
    want = dense.take_last(x, idx)
    assert (np.asarray(got) == np.asarray(want)).all()

    vals = jnp.asarray(rng.integers(0, 90, (40, 8)), jnp.int32)
    rel = jnp.asarray(rng.integers(0, 64, (40, 64)), jnp.int32)
    n = jnp.asarray(rng.integers(0, 9, (40,)), jnp.int32)
    monkeypatch.setenv("RAFTSQL_DENSE", "1")
    got = dense.ring_gather_values(vals, rel, n)
    monkeypatch.setenv("RAFTSQL_DENSE", "0")
    want = dense.ring_gather_values(vals, rel, n)
    assert (np.asarray(got) == np.asarray(want)).all()

    # pick_peer / pick_batch are dense on every backend; check vs numpy.
    xb = jnp.asarray(rng.integers(0, 99, (40, 3, 5)), jnp.int32)
    src = jnp.asarray(rng.integers(0, 3, (40,)), jnp.int32)
    got = np.asarray(dense.pick_peer(xb, src))
    want = np.asarray(xb)[np.arange(40), np.asarray(src)]
    assert (got == want).all()
    got = np.asarray(dense.pick_batch(vals, n % 8))
    want = np.asarray(vals)[np.arange(40), np.asarray(n % 8)]
    assert (got == want).all()


def test_cluster_trajectory_identical_on_dense_path(monkeypatch):
    """The dense lowering must be a pure implementation detail: the same
    seed and proposal schedule produce bit-identical PeerState on both
    paths.  (Fresh jit wrappers per path — the env var is read at trace
    time, so reusing cluster_step_jit's cache would mask the flip.)"""
    import functools

    import jax

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.core import cluster

    cfg = RaftConfig(num_groups=8, num_peers=3, log_window=32,
                     max_entries_per_msg=4, seed=13)

    def run(path):
        monkeypatch.setenv("RAFTSQL_DENSE", path)
        step = jax.jit(functools.partial(cluster.cluster_step, cfg))
        st = cluster.init_cluster_state(cfg)
        ib = cluster.empty_cluster_inbox(cfg)
        rng = np.random.default_rng(5)
        for t in range(60):
            props = jnp.asarray(
                (rng.random((cfg.num_peers, cfg.num_groups)) < 0.4)
                .astype(np.int32))
            st, ib, _ = step(st, ib, props)
        return st

    a, b = run("1"), run("0")
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert (np.asarray(la) == np.asarray(lb)).all()
