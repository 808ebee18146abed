"""Host time a tick spends laying its inputs (`prop_n`, `timer_inc`) over
the mesh's shards before the step can be launched: the `mesh_put` phase
(runtime/hostplane.py `tick`, around MeshClusterNode `_put_inputs`),
`total_ms` difference per tick of the window.  The phase does not exist
under `--fused`: None there.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "mesh_put")
