"""WAL + codec unit tests (durability and wire layers)."""
import os

import pytest

from raftsql_tpu.config import MSG_REQ, MSG_RESP
from raftsql_tpu.storage.wal import WAL, wal_exists
from raftsql_tpu.transport.base import (AppendRec, ProposalRec, TickBatch,
                                        VoteRec)
from raftsql_tpu.transport.codec import decode_batch, encode_batch


def hard_of(states):
    """{group: (term, vote, commit)} as the function WAL.compact asks
    for the hard states of the groups a doomed segment names."""
    def fn(names):
        rows = [states[g] for g in names]
        return tuple(zip(*rows)) if rows else ((), (), ())
    return fn


class TestWAL:
    def test_roundtrip(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d)
        w.append_entry(0, 1, 1, b"CREATE TABLE t")
        w.append_entry(0, 2, 1, b"INSERT 1")
        w.append_entry(1, 1, 2, b"other group")
        w.set_hardstate(0, 1, 0, 2)
        w.sync()
        w.close()
        assert wal_exists(d)
        groups = WAL.replay(d)
        assert groups[0].log_len == 2
        assert groups[0].entries == [(1, b"CREATE TABLE t"), (1, b"INSERT 1")]
        assert groups[0].hard.term == 1
        assert groups[0].hard.commit == 2
        assert groups[1].entries == [(2, b"other group")]

    def test_conflict_truncation_on_replay(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d)
        w.append_entry(0, 1, 1, b"a")
        w.append_entry(0, 2, 1, b"b")
        w.append_entry(0, 3, 1, b"c")
        # Overwrite index 2 with a term-2 entry (leader change).
        w.append_entry(0, 2, 2, b"b2")
        w.close()
        groups = WAL.replay(d)
        assert groups[0].entries == [(1, b"a"), (2, b"b2")]

    def test_same_term_overlap_keeps_suffix(self, tmp_path):
        """A re-accepted duplicate append (same index+term, e.g. a stale
        retransmission) must NOT truncate durably-acked suffix entries —
        same index+term implies same entry (raft log matching)."""
        d = str(tmp_path / "w")
        w = WAL(d)
        for i in range(1, 6):
            w.append_entry(0, i, 1, f"e{i}".encode())
        w.append_entry(0, 3, 1, b"e3")      # stale duplicate of entry 3
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.entries == [(1, f"e{i}".encode()) for i in range(1, 6)]

    def test_torn_tail_dropped(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d)
        w.append_entry(0, 1, 1, b"good")
        w.close()
        path = os.path.join(d, "wal-0.log")
        with open(path, "ab") as f:
            f.write(b"\x01\x02\x03garbage")
        groups = WAL.replay(d)
        assert groups[0].entries == [(1, b"good")]

    def test_append_after_reopen(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d)
        w.append_entry(0, 1, 1, b"one")
        w.close()
        w2 = WAL(d)
        w2.append_entry(0, 2, 1, b"two")
        w2.close()
        groups = WAL.replay(d)
        assert [e[1] for e in groups[0].entries] == [b"one", b"two"]

    def test_empty_replay(self, tmp_path):
        assert WAL.replay(str(tmp_path / "nope")) == {}


class TestSegmentation:
    """Segmented WAL: rotation at sync boundaries, replay concatenation,
    compaction by whole-segment deletion (etcd/wal's segment-dir shape,
    reference raft.go:99-117)."""

    def test_rotation_and_replay(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=256)
        for i in range(1, 41):
            w.append_entry(0, i, 1, f"entry-{i:03d}".encode())
            w.set_hardstate(0, 1, -1, i)
            w.sync()
        w.close()
        segs = sorted(p.name for p in (tmp_path / "w").glob("wal-*.log"))
        assert len(segs) > 2, segs           # actually rotated
        gl = WAL.replay(d)[0]
        assert gl.log_len == 40
        assert [e[1] for e in gl.entries] == [
            f"entry-{i:03d}".encode() for i in range(1, 41)]
        assert gl.hard.commit == 40          # last hardstate wins

    def test_reopen_appends_to_highest_segment(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=128)
        for i in range(1, 11):
            w.append_entry(0, i, 1, b"x" * 20)
            w.sync()
        w.close()
        n_before = len(list((tmp_path / "w").glob("wal-*.log")))
        w2 = WAL(d, segment_bytes=128)
        w2.append_entry(0, 11, 1, b"after-reopen")
        w2.sync()
        w2.close()
        assert len(list((tmp_path / "w").glob("wal-*.log"))) >= n_before
        gl = WAL.replay(d)[0]
        assert gl.log_len == 11
        assert gl.entries[-1] == (1, b"after-reopen")

    def test_compact_deletes_covered_segments(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=256)
        for i in range(1, 41):
            w.append_entry(0, i, 2, f"e{i}".encode())
            w.set_hardstate(0, 2, 0, i)
            w.sync()
        segs0 = sorted((tmp_path / "w").glob("wal-*.log"))
        assert len(segs0) > 3
        deleted = w.compact({0: (30, 2)}, hard_of({0: (2, 0, 40)}))
        assert deleted > 0
        segs1 = sorted((tmp_path / "w").glob("wal-*.log"))
        assert len(segs1) < len(segs0)
        # Replay after dropping segments: floor honored, suffix intact.
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.start == 30
        assert gl.start_term == 2
        assert gl.log_len == 40
        assert [e[1] for e in gl.entries] == [
            f"e{i}".encode() for i in range(31, 41)]
        assert gl.hard == type(gl.hard)(term=2, vote=0, commit=40)

    def test_compact_never_deletes_uncovered(self, tmp_path):
        """A segment holding entries above the floor must survive, and
        so must everything after it (contiguity)."""
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=256)
        for i in range(1, 41):
            w.append_entry(0, i, 1, f"e{i}".encode())
            w.sync()
        deleted = w.compact({0: (5, 1)}, hard_of({0: (1, -1, 40)}))
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.start == 5
        assert gl.log_len == 40
        assert [e[1] for e in gl.entries] == [
            f"e{i}".encode() for i in range(6, 41)]

    def test_compact_multi_group_blocks_on_uncompacted_group(self,
                                                             tmp_path):
        """A segment is only deletable when EVERY group's records in it
        are covered; one lagging group pins it."""
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=200)
        for i in range(1, 21):
            w.append_entry(0, i, 1, f"a{i}".encode())
            w.append_entry(1, i, 1, f"b{i}".encode())
            w.sync()
        # Only group 0 has a floor; group 1 pins every segment.
        hard = hard_of({0: (1, -1, 20), 1: (1, -1, 20)})
        assert w.compact({0: (15, 1)}, hard) == 0
        # Give group 1 a floor too: early segments can go.
        assert w.compact({0: (15, 1), 1: (15, 1)}, hard) > 0
        w.close()
        groups = WAL.replay(d)
        assert groups[0].start == 15 and groups[1].start == 15
        assert groups[0].log_len == 20 and groups[1].log_len == 20

    def test_compact_marker_replay_keeps_suffix(self, tmp_path):
        """REC_COMPACT drops only the covered prefix (REC_SNAPSHOT also
        drops the suffix — different semantics, both replayed here)."""
        d = str(tmp_path / "w")
        w = WAL(d)
        for i in range(1, 11):
            w.append_entry(0, i, 1, f"e{i}".encode())
        w.mark_compact(0, 4, 1)
        w.append_entry(1, 1, 1, b"x1")
        w.set_snapshot(1, 7, 3)              # install: suffix must go too
        w.close()
        groups = WAL.replay(d)
        assert groups[0].start == 4
        assert [e[1] for e in groups[0].entries] == [
            f"e{i}".encode() for i in range(5, 11)]
        assert groups[1].start == 7
        assert groups[1].entries == []

    def test_dedup_baseline_replay_highest_floor_wins(self, tmp_path):
        """REC_DEDUP replay: the baseline comes back verbatim, and a
        later (higher-floor) record supersedes an earlier one."""
        d = str(tmp_path / "w")
        w = WAL(d, native=False)
        w.append_entry(0, 1, 1, b"e1")
        assert w.set_dedup(0, 1, [(1, 42)])
        w.append_entry(0, 2, 1, b"e2")
        w.append_entry(0, 3, 1, b"e3")
        assert w.set_dedup(0, 2, [(1, 42), (2, 77)])
        w.sync()
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.dedup == (2, [(1, 42), (2, 77)])

    def test_dedup_baseline_survives_segment_unlink(self, tmp_path):
        """The dedup baseline obeys the hard-state survival contract:
        compaction re-asserts it into the active segment before
        unlinking the closed segment that held it — the doomed segment
        may hold the only record scrubbing a compacted-away
        forward-retry duplicate."""
        d = str(tmp_path / "w")
        w = WAL(d, native=False, segment_bytes=256)
        w.append_entry(0, 1, 1, b"first-copy")
        assert w.set_dedup(0, 1, [(1, 42)])
        for i in range(2, 41):
            w.append_entry(0, i, 1, f"e{i}".encode())
            w.set_hardstate(0, 1, -1, i)
            w.sync()
        assert w.compact({0: (30, 1)}, hard_of({0: (1, -1, 40)})) > 0
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.start == 30
        assert gl.dedup == (1, [(1, 42)])

    def test_torn_mid_sequence_drops_later_segments(self, tmp_path):
        """A tear in a non-final segment is real corruption: replay keeps
        only the clean prefix, never skips over the damage."""
        d = str(tmp_path / "w")
        w = WAL(d, segment_bytes=64)
        for i in range(1, 9):
            w.append_entry(0, i, 1, b"y" * 30)
            w.sync()
        w.close()
        segs = sorted((tmp_path / "w").glob("wal-*.log"))
        assert len(segs) >= 3
        # Corrupt the middle segment's first record.
        mid = segs[len(segs) // 2]
        blob = bytearray(mid.read_bytes())
        blob[10] ^= 0xFF
        mid.write_bytes(bytes(blob))
        gl = WAL.replay(d)[0]
        assert 0 < gl.log_len < 8


class TestCodec:
    def test_roundtrip(self):
        batch = TickBatch(
            votes=[VoteRec(group=3, type=MSG_REQ, term=7, last_idx=9,
                           last_term=6),
                   VoteRec(group=0, type=MSG_RESP, term=7, granted=True)],
            appends=[
                AppendRec(group=2, type=MSG_REQ, term=5, prev_idx=10,
                          prev_term=4, ent_terms=[5, 5],
                          payloads=[b"INSERT a", b""], commit=9),
                AppendRec(group=2, type=MSG_RESP, term=5, success=True,
                          match=12),
            ],
            proposals=[ProposalRec(group=1, payload=b"CREATE TABLE x")])
        out = decode_batch(encode_batch(batch))
        assert out == batch

    def test_columnar_roundtrip(self):
        import numpy as np

        from raftsql_tpu.transport.base import ColRecs

        def cols(nv, na):
            c = ColRecs()
            if nv:
                c.v_group = np.arange(nv, dtype=np.int32)
                c.v_type = np.full(nv, MSG_REQ, np.int32)
                c.v_term = np.arange(nv, dtype=np.int32) + 3
                c.v_last_idx = np.arange(nv, dtype=np.int32) * 2
                c.v_last_term = np.arange(nv, dtype=np.int32)
                c.v_granted = (np.arange(nv, dtype=np.int32) % 2)
            if na:
                c.a_group = np.arange(na, dtype=np.int32) + 1
                c.a_type = np.full(na, MSG_RESP, np.int32)
                c.a_term = np.arange(na, dtype=np.int32) + 9
                c.a_prev_idx = np.arange(na, dtype=np.int32)
                c.a_prev_term = np.arange(na, dtype=np.int32)
                c.a_commit = np.arange(na, dtype=np.int32) * 3
                c.a_success = (np.arange(na, dtype=np.int32) % 2)
                c.a_match = np.arange(na, dtype=np.int32) + 5
                c.a_seq = np.arange(na, dtype=np.int64) + (1 << 40)
            return c

        for nv, na in ((2, 3), (2, 0), (0, 3)):
            # Mixed with record sections: both must survive together.
            b = TickBatch(appends=[AppendRec(
                group=0, type=MSG_REQ, term=1, ent_terms=[1],
                payloads=[b"x"], seq=4)])
            b.cols = cols(nv, na)
            out = decode_batch(encode_batch(b))
            assert out.appends == b.appends
            assert (out.cols is not None) == bool(nv or na)
            for f in ("v_group", "v_type", "v_term", "v_last_idx",
                      "v_last_term", "v_granted"):
                want = getattr(b.cols, f)
                got = getattr(out.cols, f)
                if nv:
                    assert (np.asarray(got) == np.asarray(want)).all(), f
                else:
                    assert got is None or len(got) == 0
            for f in ("a_group", "a_type", "a_term", "a_prev_idx",
                      "a_prev_term", "a_commit", "a_success", "a_match",
                      "a_seq"):
                want = getattr(b.cols, f)
                got = getattr(out.cols, f)
                if na:
                    assert (np.asarray(got) == np.asarray(want)).all(), f
                    if f == "a_seq":
                        assert got.dtype == np.int64
                else:
                    assert got is None or len(got) == 0

    def test_empty(self):
        assert decode_batch(encode_batch(TickBatch())).empty()

    def test_payload_count_mismatch_asserts(self):
        bad = TickBatch(appends=[AppendRec(
            group=0, type=MSG_REQ, term=1, ent_terms=[1], payloads=[])])
        with pytest.raises(AssertionError):
            encode_batch(bad)

    def test_truncated_columnar_section_is_codec_error(self):
        """A truncated/corrupt trailing ColSection must fail as a codec
        error (struct.error, like the record sections), not a ValueError
        deep inside numpy frombuffer."""
        import struct

        import numpy as np

        from raftsql_tpu.transport.base import ColRecs

        c = ColRecs()
        c.a_group = np.arange(4, dtype=np.int32)
        c.a_type = np.full(4, MSG_RESP, np.int32)
        c.a_term = np.ones(4, np.int32)
        c.a_prev_idx = np.zeros(4, np.int32)
        c.a_prev_term = np.zeros(4, np.int32)
        c.a_commit = np.zeros(4, np.int32)
        c.a_success = np.ones(4, np.int32)
        c.a_match = np.arange(4, dtype=np.int32)
        c.a_seq = np.arange(4, dtype=np.int64)
        blob = encode_batch(TickBatch(cols=c))
        # Drop tail bytes at several depths: mid-a_seq, mid-columns, and
        # right after the declared count.
        for cut in (8, len(blob) // 2, len(blob) - 4):
            with pytest.raises(struct.error):
                decode_batch(blob[:len(blob) - cut])
        # Corrupt count: a huge declared na over an empty remainder.
        head = encode_batch(TickBatch())
        with pytest.raises(struct.error):
            decode_batch(head + struct.pack("<I", 0)
                         + struct.pack("<I", 1 << 28))


class TestEnvelope:
    def test_wrap_unwrap(self):
        from raftsql_tpu.runtime.envelope import unwrap, wrap
        data = wrap(b"INSERT INTO t VALUES (1)")
        pid, payload = unwrap(data)
        assert pid is not None
        assert payload == b"INSERT INTO t VALUES (1)"

    def test_bare_entries_pass_through(self):
        from raftsql_tpu.runtime.envelope import unwrap
        assert unwrap(b"") == (None, b"")

    def test_distinct_ids(self):
        from raftsql_tpu.runtime.envelope import unwrap, wrap
        a, b = wrap(b"x"), wrap(b"x")
        assert a != b
        assert unwrap(a)[1] == unwrap(b)[1] == b"x"

    def test_dedup_window(self):
        from raftsql_tpu.runtime.envelope import DedupWindow
        w = DedupWindow(cap=3)
        assert not w.seen(1)
        assert w.seen(1)           # duplicate caught
        assert not w.seen(2)
        assert not w.seen(3)
        assert not w.seen(4)       # evicts 1
        assert not w.seen(1)       # 1 slid out of the window

    def test_dedup_pairs_upto_and_restore(self):
        """The window snapshots consistently at an applied index: a
        transfer at idx 20 must ship ids applied at or below 20 and NOT
        the live tail beyond it (runtime/node.py InstallSnapshot)."""
        from raftsql_tpu.runtime.envelope import DedupWindow
        w = DedupWindow()
        for idx, pid in ((10, 100), (20, 200), (30, 300)):
            assert not w.seen(pid, idx)
        pairs = w.pairs_upto(20)
        assert pairs == [(10, 100), (20, 200)]
        r = DedupWindow()
        r.restore(pairs)
        assert r.seen(100) and r.seen(200)
        assert not r.seen(300)      # beyond the transfer: not skipped

    def test_snapshot_blob_framing(self):
        from raftsql_tpu.runtime.envelope import (unwrap_snapshot,
                                                  wrap_snapshot)
        pairs = [(5, 111), (9, 2**63 + 7)]
        blob = wrap_snapshot(pairs, b"sm-state-bytes")
        got, sm = unwrap_snapshot(blob)
        assert got == pairs
        assert sm == b"sm-state-bytes"

    def test_snapshot_blob_bare_fallback(self):
        """Blobs without the framing magic are treated as bare SM state
        (back-compat with directly staged SnapshotRecs in tests)."""
        from raftsql_tpu.runtime.envelope import unwrap_snapshot
        assert unwrap_snapshot(b"{}") == (None, b"{}")
        assert unwrap_snapshot(b"") == (None, b"")


class TestPayloadLog:
    def test_try_term_of(self):
        """Floor-safe term lookup for client-thread callers (ReadIndex):
        below-floor and beyond-log return None, never an assert/wrap."""
        from raftsql_tpu.storage.log import PayloadLog
        pl = PayloadLog(1)
        pl.put(0, 1, [b"a", b"b", b"c", b"d"], [1, 1, 2, 2])
        assert pl.try_term_of(0, 0) == 0
        assert pl.try_term_of(0, 3) == 2
        assert pl.try_term_of(0, 5) is None       # beyond the log
        pl.compact(0, 2, 1)
        assert pl.try_term_of(0, 2) == 1          # boundary term kept
        assert pl.try_term_of(0, 1) is None       # below the floor

    def test_try_slice_floor_race_paths(self):
        """try_slice degrades to None when the requested range dips
        below a (concurrently advancing) compaction floor — the atomic
        check-then-slice the send path relies on."""
        from raftsql_tpu.storage.log import PayloadLog
        pl = PayloadLog(1)
        pl.put(0, 1, [b"a", b"b", b"c", b"d", b"e"], [1] * 5)
        assert pl.try_slice(0, 2, 3) == [b"b", b"c", b"d"]
        pl.compact(0, 3, 1)
        assert pl.try_slice(0, 2, 3) is None      # starts below floor
        assert pl.try_slice(0, 4, 2) == [b"d", b"e"]
        # A short tail read returns what exists (caller length-checks),
        # never wraps to the list head.
        assert pl.try_slice(0, 5, 4) == [b"e"]

    def test_try_tail_with_terms_boundary(self):
        """Atomic (prev_term, entries) read for catch-up appends: the
        floor's retained boundary term serves prev_term exactly at the
        edge, and a compacted-away start returns None (InstallSnapshot
        territory)."""
        from raftsql_tpu.storage.log import PayloadLog
        pl = PayloadLog(1)
        pl.put(0, 1, [b"a", b"b", b"c", b"d"], [1, 2, 2, 3])
        prev, ents = pl.try_tail_with_terms(0, 1, 2)
        assert prev == 0 and ents == [(1, b"a"), (2, b"b")]
        pl.compact(0, 2, 2)
        assert pl.try_tail_with_terms(0, 2, 2) is None   # at the floor
        prev, ents = pl.try_tail_with_terms(0, 3, 4)
        assert prev == 2                  # boundary term, not a wrap
        assert ents == [(2, b"c"), (3, b"d")]

    def test_try_accessors_race_live_compactor(self):
        """Hammer try_term_of/try_slice/try_tail_with_terms from a
        reader thread while the owner thread compacts: every result is
        either None or internally consistent (terms match what was
        written at those absolute positions) — no asserts, no wrapped
        negative indexes, no torn (start, lists) reads."""
        import threading
        from raftsql_tpu.storage.log import PayloadLog
        pl = PayloadLog(1)
        N = 400
        pl.put(0, 1, [b"%d" % i for i in range(1, N + 1)],
               list(range(1, N + 1)))        # term i at index i
        stop = threading.Event()
        errors = []

        def reader():
            try:
                while not stop.is_set():
                    for idx in (1, N // 3, N // 2, N):
                        t = pl.try_term_of(0, idx)
                        assert t is None or t == idx, (idx, t)
                        got = pl.try_slice(0, idx, 3)
                        assert got is None \
                            or got == [b"%d" % i for i in
                                       range(idx, min(idx + 3, N + 1))]
                        tail = pl.try_tail_with_terms(0, idx, 2)
                        if tail is not None:
                            prev, ents = tail
                            assert prev == idx - 1
                            assert all(t == i for (t, _), i in
                                       zip(ents, range(idx, idx + 2)))
            except Exception as e:          # pragma: no cover - failure
                errors.append(e)

        th = threading.Thread(target=reader)
        th.start()
        try:
            for floor in range(2, N, 7):
                pl.compact(0, floor, floor)
        finally:
            stop.set()
            th.join(timeout=10)
        assert not errors, errors[0]


class TestNativeWAL:
    """The C++ write path (native/wal.cc) must be byte-identical to the
    Python writer and fully interoperable with Python replay."""

    @pytest.fixture()
    def native(self):
        from raftsql_tpu.native.build import load_native_wal
        lib = load_native_wal()
        if lib is None:
            pytest.skip("native toolchain unavailable")
        return lib

    @staticmethod
    def _write_all(w: WAL) -> None:
        w.append_entry(0, 1, 1, b"CREATE TABLE t")
        w.append_entry(0, 2, 1, b"")
        w.append_entry(7, 1, 3, b"x" * 1000)
        w.set_hardstate(0, 1, -1, 2)
        w.set_hardstate(7, 3, 2, 1)
        w.append_entries([1, 1], [1, 2], [2, 2], [b"batch-a", b"batch-b"])
        w.sync()
        w.close()

    def test_byte_identical_to_python(self, native, tmp_path):
        dn, dp = str(tmp_path / "n"), str(tmp_path / "p")
        wn, wp = WAL(dn, native=True), WAL(dp, native=False)
        assert wn.is_native and not wp.is_native
        self._write_all(wn)
        self._write_all(wp)
        with open(wn.path, "rb") as f:
            n_bytes = f.read()
        with open(wp.path, "rb") as f:
            p_bytes = f.read()
        assert n_bytes == p_bytes
        assert len(n_bytes) > 0

    def test_native_write_python_replay(self, native, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, native=True)
        self._write_all(w)
        groups = WAL.replay(d)
        assert groups[0].entries == [(1, b"CREATE TABLE t"), (1, b"")]
        assert groups[0].hard.vote == -1
        assert groups[7].entries == [(3, b"x" * 1000)]
        assert groups[7].hard.vote == 2
        assert groups[1].entries == [(2, b"batch-a"), (2, b"batch-b")]

    def test_reopen_across_backends(self, native, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, native=True)
        w.append_entry(0, 1, 1, b"one")
        w.sync()
        w.close()
        w2 = WAL(d, native=False)
        w2.append_entry(0, 2, 1, b"two")
        w2.close()
        groups = WAL.replay(d)
        assert [e[1] for e in groups[0].entries] == [b"one", b"two"]


class TestBatchedHardstates:
    def test_batched_hardstates_replay(self, tmp_path):
        """set_hardstates (one native call per tick) must replay exactly
        like per-group set_hardstate, including NO_VOTE (-1) votes."""
        import numpy as np
        d = str(tmp_path / "hsb")
        w = WAL(d)
        w.append_entry(0, 1, 1, b"a")
        w.append_entry(2, 1, 1, b"b")
        w.set_hardstates(np.asarray([0, 2, 5]),
                         np.asarray([3, 4, 9]),
                         np.asarray([-1, 1, 0]),
                         np.asarray([1, 1, 0]))
        w.sync()
        w.close()
        groups = WAL.replay(d)
        h0, h2, h5 = groups[0].hard, groups[2].hard, groups[5].hard
        assert (h0.term, h0.vote, h0.commit) == (3, -1, 1)
        assert (h2.term, h2.vote, h2.commit) == (4, 1, 1)
        assert (h5.term, h5.vote, h5.commit) == (9, 0, 0)

    def test_batched_hardstates_python_fallback(self, tmp_path):
        import numpy as np
        d = str(tmp_path / "hsf")
        w = WAL(d, native=False)
        assert w._lib is None
        w.set_hardstates(np.asarray([1]), np.asarray([7]),
                         np.asarray([-1]), np.asarray([5]))
        w.sync()
        w.close()
        h = WAL.replay(d)[1].hard
        assert (h.term, h.vote, h.commit) == (7, -1, 5)


class TestRangeRecords:
    """Type-5 RANGE records: one framed record per same-term entry run
    (the fused tick's batched WAL form).  Replay must expand a RANGE to
    exactly the entry sequence its per-entry form would produce."""

    def test_roundtrip_equivalent_to_entries(self, tmp_path):
        dr, de = str(tmp_path / "r"), str(tmp_path / "e")
        wr, we = WAL(dr, native=False), WAL(de, native=False)
        datas = [b"a", b"", b"ccc", b"dd", b"e"]
        wr.append_ranges([0, 0, 3], [1, 4, 1], [3, 2, 0], [1, 1, 2],
                         datas)
        for i, d in enumerate(datas):
            we.append_entry(0, i + 1, 1, d)
        wr.close()
        we.close()
        gr, ge = WAL.replay(dr), WAL.replay(de)
        assert gr[0].entries == ge[0].entries
        assert 3 not in gr          # zero-count range writes nothing
        # ...including its segment stats: a phantom (group, start-1)
        # max-index entry would block compaction of the segment for a
        # group that may never earn a durable floor.
        assert 3 not in wr._active_stats.max_idx
        # And the range file is smaller: one header per run, not entry.
        assert os.path.getsize(wr.path) < os.path.getsize(we.path)

    def test_native_byte_identical(self, tmp_path):
        from raftsql_tpu.native.build import load_native_wal
        if load_native_wal() is None:
            pytest.skip("native toolchain unavailable")
        dn, dp = str(tmp_path / "n"), str(tmp_path / "p")
        wn, wp = WAL(dn, native=True), WAL(dp, native=False)
        for w in (wn, wp):
            w.append_ranges([2, 5], [1, 11], [2, 3], [4, 9],
                            [b"x", b"yy", b"", b"zzz", b"w" * 300])
            w.sync()
            w.close()
        with open(wn.path, "rb") as f:
            nb = f.read()
        with open(wp.path, "rb") as f:
            pb = f.read()
        assert nb == pb and len(nb) > 0
        g = WAL.replay(dn)
        assert g[2].entries == [(4, b"x"), (4, b"yy")]
        assert g[5].entries == [(9, b""), (9, b"zzz"), (9, b"w" * 300)]

    def test_range_conflict_truncates(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, native=False)
        w.append_ranges([0], [1], [4], [1], [b"a", b"b", b"c", b"d"])
        # New-term range overwriting 3.. truncates the old suffix.
        w.append_ranges([0], [3], [2], [2], [b"c2", b"d2"])
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.entries == [(1, b"a"), (1, b"b"), (2, b"c2"), (2, b"d2")]

    def test_range_torn_tail(self, tmp_path):
        d = str(tmp_path / "w")
        w = WAL(d, native=False)
        w.append_ranges([0], [1], [2], [1], [b"good1", b"good2"])
        w.sync()
        w.append_ranges([0], [3], [2], [1], [b"lost1", b"lost2"])
        w.close()
        with open(w.path, "r+b") as f:
            f.truncate(os.path.getsize(w.path) - 3)   # tear mid-record
        gl = WAL.replay(d)[0]
        assert gl.entries == [(1, b"good1"), (1, b"good2")]

    def test_range_segment_stats_gate_compaction(self, tmp_path):
        """_stats_for must see RANGE max indexes: a closed segment whose
        ranges are NOT covered by the floor must survive compact()."""
        d = str(tmp_path / "w")
        w = WAL(d, native=False, segment_bytes=64)
        w.append_ranges([0], [1], [4], [1], [b"a" * 30] * 4)
        w.sync()                       # exceeds 64 bytes -> rotates
        w.append_ranges([0], [5], [2], [1], [b"b" * 30] * 2)
        w.sync()
        assert len(sorted((tmp_path / "w").glob("wal-*.log"))) >= 2
        # Drop the stats cache so compact() re-scans the closed segment
        # from bytes (the _stats_for parse under test).
        w._closed_stats.clear()
        # Floor at 2 does not cover the first segment's range 1-4.
        removed = w.compact({0: (2, 1)}, hard_of({0: (1, -1, 0)}))
        assert removed == 0
        # Floor at 6 covers both closed ranges.
        removed = w.compact({0: (6, 1)}, hard_of({0: (1, -1, 0)}))
        assert removed >= 1
        w.close()
        gl = WAL.replay(d)[0]
        assert gl.start == 6 and gl.log_len == 6 and gl.entries == []
