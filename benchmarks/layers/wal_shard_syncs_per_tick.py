"""Shard streams of the sharded WALs that had something to flush at a
barrier, per tick of the window, all peers together (`wal.shard_syncs`
over `ticks`; runtime/mesh.py `ShardedWAL.shard_syncs`): at most peers x
group shards.  A WAL of one stream has no shards: 0 under `--fused`.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.shard_syncs", "ticks")
