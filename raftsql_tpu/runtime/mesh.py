"""MeshClusterNode — the durable runtime SPMD over a real device mesh.

Everything before this subsystem ran G groups on ONE device; a
MULTICHIP pod shows 8 healthy devices and 7 of them idle.  This module
promotes the fused runtime to the mesh: the per-tick consensus program
runs under `Mesh` + `shard_map` with G sharded over a `groups` axis
(parallel/sharded.py — DrJAX-style MapReduce-over-shard_map is the
programming model: per-group math is embarrassingly parallel, zero
collectives on the group axis, and the optional `peers` axis rides one
all_to_all over ICI for the message exchange), while the DURABLE HOST
PLANE is sharded to match:

  * per-local-shard WAL dirs — each peer's log splits into one
    directory (one append stream + one fsync stream) per group shard
    (ShardedWAL below: data_dir/p<i>/s<j>), so the host's durable
    barrier parallelizes the way the device plane does;
  * per-shard publish workers — one ordered worker per group shard
    drains commits to the apply plane (ClusterHostPlane's publish
    seam), so the host side finally gets real cores;
  * per-shard state-machine placement — the server deployment lays
    SQLite files out under db/s<j>/ (server/main.py build_mesh_node).

The host phase itself (propose queues, WAL fsync barriers, commit
publish, membership apply-at-commit) is runtime/hostplane.py
ClusterHostPlane, SHARED with the single-device FusedClusterNode — the
two runtimes differ only in `_device_step`.  The durable ordering
argument is unchanged on the mesh because the host still interposes
every peer's WAL fsync between dispatches: what was rafthttp between
processes in the reference (raft.go:230) is a collective between
chips here.

Per-peer clock skew is fully plumbed: `timer_inc` [P] shards over the
`peers` axis (parallel/sharded.py timer_spec), so chaos SkewWindow
schedules run on the mesh exactly as on the fused runtime — the old
`MeshLockstepOnlyError` frontier is closed.

Payload note: one host process drives the whole mesh (the
single-controller model), so payload mirroring between peers stays a
host-memory copy exactly as in the fused runtime — only consensus math
and message metadata ride the mesh.

Testable without hardware: force a multi-device CPU platform with
`XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu`
(tests/conftest.py does this for the whole suite).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
from jax.sharding import NamedSharding

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.core.cluster import (empty_cluster_inbox,
                                      init_cluster_state)
from raftsql_tpu.parallel.sharded import (GROUPS_AXIS, PEERS_AXIS,
                                          cluster_shardings,
                                          make_mesh,
                                          make_sharded_cluster_step_host,
                                          prop_spec,
                                          timer_spec)
from raftsql_tpu.runtime.hostplane import ClusterHostPlane
from raftsql_tpu.storage.wal import (DEFAULT_SEGMENT_BYTES, WAL,
                                     wal_exists)

MESH_META = "MESHMETA"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh description for the consensus runtime.

    `peer_shards × group_shards` devices arranged as the
    ('peers', 'groups') mesh of parallel/sharded.py.  The group axis is
    the scale dimension (data-parallel, zero collectives); shard the
    peer axis only when one group's peers should span chips (the
    message exchange then rides all_to_all over ICI).
    """

    peer_shards: int = 1
    group_shards: int = 1

    def __post_init__(self) -> None:
        if self.peer_shards <= 0 or self.group_shards <= 0:
            raise ValueError(
                f"mesh axes must be positive, got "
                f"{self.peer_shards}x{self.group_shards}")

    @property
    def total_devices(self) -> int:
        return self.peer_shards * self.group_shards

    def validate(self, cfg: RaftConfig) -> None:
        if cfg.num_peers % self.peer_shards:
            raise ValueError(f"num_peers {cfg.num_peers} not divisible "
                             f"by peer shards {self.peer_shards}")
        if cfg.num_groups % self.group_shards:
            raise ValueError(f"num_groups {cfg.num_groups} not "
                             f"divisible by group shards "
                             f"{self.group_shards}")

    def build(self, devices=None):
        """Materialize the jax Mesh over the first
        `total_devices` devices."""
        return make_mesh(self.peer_shards, self.group_shards,
                         devices=devices)

    @staticmethod
    def for_groups(cfg: RaftConfig, devices=None,
                   peer_shards: int = 1) -> "MeshConfig":
        """The widest groups-only mesh this host can run: the largest
        group-shard count that divides cfg.num_groups and fits the
        visible devices (after reserving `peer_shards` of them per
        group shard)."""
        n = len(jax.devices() if devices is None else devices)
        avail = max(1, n // peer_shards)
        gg = max(j for j in range(1, avail + 1)
                 if cfg.num_groups % j == 0)
        return MeshConfig(peer_shards=peer_shards, group_shards=gg)


class ShardedWAL:
    """A peer's durable log split per group shard.

    Implements the WAL surface the host plane writes through
    (append_ranges / set_hardstates / set_conf / epoch_mark / sync /
    compact / close), routing each group to the shard WAL that owns its
    block — group g lives in shard g // groups_per_shard, matching the
    device mesh's block layout, so one directory holds exactly the
    groups one device shard computes.  Each shard is a full
    storage/wal.py WAL (same record formats, same repair, same
    compaction), so every durability property is inherited per shard;
    cross-shard atomicity is not needed because the host plane's
    barrier semantics are per-peer fsync-before-next-dispatch, and
    sync() here syncs every dirty shard before returning.
    """

    def __init__(self, dirname: str, num_shards: int,
                 groups_per_shard: int,
                 segment_bytes: int = DEFAULT_SEGMENT_BYTES):
        self.dirname = dirname
        self.num_shards = num_shards
        self._gl = groups_per_shard
        self.shards = [WAL(d, segment_bytes=segment_bytes)
                       for d in self.shard_dirs(dirname, num_shards)]

    @staticmethod
    def shard_dirs(dirname: str, num_shards: int) -> List[str]:
        return [os.path.join(dirname, f"s{j}") for j in range(num_shards)]

    @classmethod
    def exists(cls, dirname: str, num_shards: int) -> bool:
        return any(wal_exists(d)
                   for d in cls.shard_dirs(dirname, num_shards))

    @classmethod
    def replay(cls, dirname: str, num_shards: int,
               groups_per_shard: int):
        """Merged per-group replay across every shard dir.  Groups are
        disjoint across shards by construction; a group found in the
        wrong shard means the directory was written under a different
        group-shard count — re-sharding an existing data dir is
        unsupported (fail loudly, never silently mis-route appends)."""
        merged = {}
        for j, d in enumerate(cls.shard_dirs(dirname, num_shards)):
            if not wal_exists(d):
                continue
            for g, gl in WAL.replay(d).items():
                if g // groups_per_shard != j:
                    raise ValueError(
                        f"{dirname}: group {g} replayed from shard {j} "
                        f"but belongs to shard {g // groups_per_shard} "
                        "— this WAL was written under a different "
                        "group-shard count (re-sharding an existing "
                        "data dir is unsupported)")
                merged[g] = gl
        return merged

    @classmethod
    def repair_epochs(cls, dirname: str, committed: int,
                      num_shards: int) -> None:
        for d in cls.shard_dirs(dirname, num_shards):
            if wal_exists(d):
                WAL.repair_epochs(d, committed)

    @property
    def is_native(self) -> bool:
        """Every shard this host writes goes through the native fast
        path (a pod host's null sinks for foreign shards do not count)."""
        return all(s.is_native for s in self.shards
                   if isinstance(s, WAL))

    # -- observability fan-out -----------------------------------------

    @property
    def obs(self):
        return self.shards[0].obs

    @obs.setter
    def obs(self, tracer) -> None:
        for s in self.shards:
            s.obs = tracer

    # -- routed write surface ------------------------------------------

    def _shard(self, group: int) -> WAL:
        return self.shards[group // self._gl]

    def append_ranges(self, groups, starts, counts, terms,
                      datas) -> None:
        by: Dict[int, Tuple[list, list, list, list, list]] = {}
        pos = 0
        for g, st, c, tm in zip(groups, starts, counts, terms):
            g = int(g)
            b = by.setdefault(g // self._gl, ([], [], [], [], []))
            b[0].append(g)
            b[1].append(st)
            b[2].append(c)
            b[3].append(tm)
            b[4].extend(datas[pos:pos + c])
            pos += c
        for j, b in by.items():
            self.shards[j].append_ranges(*b)

    def set_hardstates(self, groups, terms, votes, commits) -> None:
        ga = np.asarray(groups)
        sh = ga // self._gl
        ta, va, ca = (np.asarray(terms), np.asarray(votes),
                      np.asarray(commits))
        for j in np.unique(sh):
            m = sh == j
            self.shards[int(j)].set_hardstates(ga[m], ta[m], va[m],
                                               ca[m])

    def set_conf(self, group: int, index: int, kind: int, voters: int,
                 joint: int, learners: int) -> None:
        self._shard(group).set_conf(group, index, kind, voters, joint,
                                    learners)

    def epoch_mark(self, no: int, end: bool) -> None:
        # Dispatch framing lands in every shard that the dispatch may
        # touch.  (The mesh runtime pins steps-per-dispatch to 1, so
        # this is never reached in practice — kept for API parity.)
        for s in self.shards:
            s.epoch_mark(no, end)

    def sync(self) -> None:
        # Serial over shards: WAL.sync returns immediately when a shard
        # has nothing pending, and the host plane already overlaps this
        # call across peers (its per-peer sync pool), so the barrier
        # costs ~max(dirty shard fsyncs) across peers.
        for s in self.shards:
            s.sync()

    @property
    def shard_syncs(self) -> int:
        """Shard streams that had something to flush at a barrier,
        cumulative over the shards this host writes (the host plane's
        wal.shard_syncs counter; a WAL of one stream has no such
        attribute)."""
        return sum(s.syncs for s in self.shards if isinstance(s, WAL))

    def written(self) -> Tuple[int, int]:
        """Summed over the shards this host writes (WAL.written)."""
        got = [s.written() for s in self.shards if isinstance(s, WAL)]
        return sum(b for b, _ in got), sum(n for _, n in got)

    def compact(self, floors, hard) -> int:
        """`floors`: the floors that moved; a shard none of them lies
        in is left alone.  `hard`: the function of group ids
        WAL.compact takes, handed to each shard as it is."""
        deleted = 0
        for j, s in enumerate(self.shards):
            fj = {g: v for g, v in floors.items() if g // self._gl == j}
            if fj:
                deleted += s.compact(fj, hard)
        return deleted

    def seed_floors(self, floors) -> None:
        for j, s in enumerate(self.shards):
            fj = {g: v for g, v in floors.items() if g // self._gl == j}
            if fj:
                s.seed_floors(fj)

    def disk_bytes(self) -> int:
        return sum(s.disk_bytes() for s in self.shards
                   if isinstance(s, WAL))

    @property
    def segments_pinned(self) -> int:
        return sum(s.segments_pinned for s in self.shards
                   if isinstance(s, WAL))

    def close(self) -> None:
        for s in self.shards:
            s.close()


class MeshClusterNode(ClusterHostPlane):
    """The durable runtime SPMD over a multi-chip mesh.

    Same host plane as FusedClusterNode (runtime/hostplane.py) — WALs,
    payload mirroring, fsync-before-next-dispatch, publish — with four
    mesh-specific choices (see module docstring): the shard_map'd
    device step with per-peer `timer_inc` sharded alongside, a boot
    state whose every leaf is born on its shards, per-peer WALs split
    per group shard (ShardedWAL), and one publish worker per group
    shard.
    """

    # The per-shard WAL layout supersedes the single-file group-commit
    # layout (each shard dir is its own append+fsync stream).
    supports_group_commit = False

    def __init__(self, cfg: RaftConfig, data_dir: str, mesh,
                 seed: Optional[int] = None):
        gg = mesh.shape[GROUPS_AXIS]
        pp = mesh.shape[PEERS_AXIS]
        MeshConfig(peer_shards=pp, group_shards=gg).validate(cfg)
        self.mesh = mesh
        self._gg = gg
        self._g_loc = cfg.num_groups // gg
        self._check_mesh_meta(data_dir, gg)
        # The sharded step dispatches exactly one consensus step, so
        # this runtime (and the pod's, built on it) takes no `steps`:
        # the host plane's bare default, one step a dispatch.
        super().__init__(cfg, data_dir, seed)
        self._sharded_step = make_sharded_cluster_step_host(cfg, mesh)
        self._ti_spec = NamedSharding(mesh, timer_spec())
        # Host inputs go straight to their shards: jnp.asarray would
        # land the whole [P, G] block on the default device first and
        # leave the re-layout to the dispatch.
        self._prop_spec = NamedSharding(mesh, prop_spec())
        self._placement: Optional[dict] = None
        self._ti_ones = jax.device_put(
            np.ones((cfg.num_peers,), np.int32), self._ti_spec)

    @staticmethod
    def _check_mesh_meta(data_dir: str, gg: int) -> None:
        """Refuse to open a data dir written under a different
        group-shard count: the per-shard WAL layout routes each group's
        records by the CURRENT shard count, so re-sharding in place
        would scatter one group's history across directories."""
        os.makedirs(data_dir, exist_ok=True)
        path = os.path.join(data_dir, MESH_META)
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                meta = json.load(f)
            if meta.get("group_shards") != gg:
                raise ValueError(
                    f"{data_dir}: written with group_shards="
                    f"{meta.get('group_shards')}, opened with {gg} — "
                    "re-sharding an existing data dir is unsupported; "
                    "use a fresh dir (or the original shard count)")
        else:
            with open(path, "w", encoding="utf-8") as f:
                json.dump({"group_shards": gg}, f)

    def enable_membership(self, initial_voters=None) -> None:
        # The sharded step closure captured the construction-time cfg;
        # rebuild it after the host plane leaves the static-full-voter
        # fast path (config.py dynamic_membership) so the mesh program
        # reads the masks membership will patch.
        super().enable_membership(initial_voters)
        self._sharded_step = make_sharded_cluster_step_host(self.cfg,
                                                            self.mesh)

    def _group_shard_of(self, group: int) -> int:
        """Which mesh group shard owns `group` — the `shard` column of
        the /metrics hot-groups table, so the placement story (ROADMAP:
        traffic-aware leadership migration) can see which device shard
        a hot group's load lands on before deciding to move it."""
        return group // self._g_loc

    # -- host-plane seams (runtime/hostplane.py) ------------------------

    def _new_wal(self, dirname: str) -> ShardedWAL:
        return ShardedWAL(dirname, self._gg, self._g_loc,
                          segment_bytes=self.cfg.wal_segment_bytes)

    def _wal_exists(self, dirname: str) -> bool:
        return ShardedWAL.exists(dirname, self._gg)

    def _wal_replay(self, dirname: str):
        return ShardedWAL.replay(dirname, self._gg, self._g_loc)

    def _wal_repair_epochs(self, dirname: str, committed: int) -> None:
        ShardedWAL.repair_epochs(dirname, committed, self._gg)

    def _build_cluster_arrays(self, restored: Optional[dict], seed):
        """Every leaf of the boot state is BORN under the sharding the
        step keeps it in (donated in and out from then on): the fresh
        cluster by one jitted initialiser whose outputs are sharded, so
        each device fills in its own [P/pp, G/gg, ...] blocks, and what
        a replay decides straight from host memory to its shards.  No
        device ever holds a whole [P, G, ...] array, on a first boot or
        on a restart."""
        cfg = self.cfg
        st_sh, ib_sh = cluster_shardings(self.mesh)
        states, inboxes = jax.jit(
            lambda: (init_cluster_state(cfg, seed),
                     empty_cluster_inbox(cfg)),
            out_shardings=(st_sh, ib_sh))()
        if restored is not None:
            states = states._replace(
                **{k: jax.device_put(v, getattr(st_sh, k))
                   for k, v in restored.items()})
        return states, inboxes

    def _pub_shard_groups(self) -> List[np.ndarray]:
        # One ordered publish worker per group shard, each owning the
        # shard's contiguous group block (disjoint by construction, so
        # per-group commit order is each worker's FIFO).
        return [np.arange(j * self._g_loc, (j + 1) * self._g_loc)
                for j in range(self._gg)]

    # -- the device step ------------------------------------------------

    def _put_inputs(self, prop_n: np.ndarray,
                    timer_inc: Optional[np.ndarray] = None):
        """The tick's host inputs on their shards (the host plane times
        this apart, as `mesh_put`): `prop_n` [P, G] over the mesh and
        `timer_inc`, the per-peer [P] timer advance (chaos skew
        schedules; None = lockstep), over the `peers` axis, so each
        device block advances exactly its own peers' clocks,
        bit-identically to the fused runtime's cluster_step."""
        if timer_inc is None:
            ti = self._ti_ones
        else:
            ti = jax.device_put(np.asarray(timer_inc, np.int32),
                                self._ti_spec)
        return jax.device_put(prop_n, self._prop_spec), ti

    def _device_step(self, prop_n: jax.Array, timer_inc: jax.Array):
        """One SPMD tick over the mesh, on inputs `_put_inputs` laid
        out."""
        self.states, self.inboxes, pinfo_dev, busy = self._sharded_step(
            self.states, self.inboxes, prop_n, timer_inc)
        if self._placement is None:
            self._placement = self._observe_placement(pinfo_dev)
        return pinfo_dev, busy

    def _observe_placement(self, pinfo_dev) -> dict:
        """Which devices hold the shards of the step's carry and of its
        host-facing output, read off the FIRST dispatch's results (tick
        thread only: the carry is donated every step).  The layout is
        fixed by the shard_map specs from then on, so once is enough;
        /healthz publishes it so a four-chip run can be told from one
        that gathered everything onto the first device."""
        def held_by(x):
            return sorted(s.device.id for s in x.addressable_shards)
        return {
            "peer_shards": self.mesh.shape[PEERS_AXIS],
            "group_shards": self._gg,
            "mesh_devices": [int(d.id) for d in self.mesh.devices.flat],
            "state_devices": held_by(self.states.commit),
            "inbox_devices": held_by(self.inboxes.a_type),
            "info_devices": held_by(pinfo_dev),
            "state_shard_shape": list(
                self.states.commit.addressable_shards[0].data.shape),
        }

    def mesh_doc(self) -> Optional[dict]:
        """The observed placement (None until the first dispatch)."""
        return self._placement
