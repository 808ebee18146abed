"""The device step's phases by name: the join between a device trace and
the `jax.named_scope`s of core/step.py (`raft.inbox` ... `raft.outbox`)
and core/cluster.py (`cluster_step`, `raft.deliver`, `raft.pack`).

A TPU trace names an operation by its optimized HLO line
(`%fusion.44 = ...`) and carries no scope (looked at by hand, PERF.md
section 7).  The scope IS in the compiled program's text, where every
instruction has `metadata={op_name="jit(cluster_step_host)/cluster_step/
vmap(raft.outbox)/reduce_max"}` and a fusion carries its root's.  So the
phase of a traced operation is found by compiling the same step for the
same shape on the same backend and looking its name up:

    op_scopes(text)     {instruction name: scope} from a compiled text
    step_scopes(G, P)   the same for the served step at G groups, P peers
                        (`group_shards=N`: the `--mesh` step over N devices,
                        whose collectives lie in `raft.mesh_reduce`)
    by_scope(ops, m)    a trace's [name, seconds] operations summed a scope

and, for a result line of benchmarks/run.py (`--trace 1`), by hand:

    python -m raftsql_tpu.obs.scopes --groups 10000 --peers 3 RESULT.json
    python -m raftsql_tpu.obs.scopes --groups 10000 --peers 3 \
        --group-shards 4 RESULT.json      # a --mesh --group-shards 4 cell

prints each of `breakdown.device_ops` with its phase and the phases'
sums.  Run it where the traced engine ran (JAX_PLATFORMS decides the
backend as for every entry point): instruction names are the
compiler's, stable for one program on one backend and version only.
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

# `%name = type op(...), ..., metadata={... op_name="path" ...}`
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name="([^"]*)"')
_SCOPE = re.compile(r"\b(raft\.[a-z_]+|cluster_step)\b")
# The mesh step's own names beside core/step.py's STEP_SCOPES.
MESH_SCOPES = ("raft.deliver", "raft.mesh_reduce", "raft.pack")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost named scope on an instruction's `op_name` path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of a compiled
    program's text that lies in a named scope."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scope = scope_of(m.group(2))
            if scope is not None:
                out[m.group(1)] = scope
    return out


def step_scopes(groups: int, peers: int,
                group_shards: int = 0) -> Dict[str, str]:
    """op_scopes() of the step a `--fused` server runs at this shape
    with every other flag at its default — or, with `group_shards`, of
    the step a `--mesh --group-shards N` server runs over this
    process's first N devices (parallel/sharded.py) — compiled for this
    process's backend, and compiled anew: the persistent cache's key
    leaves metadata out, so a cached program carries the scope names of
    whichever source compiled it first (none, before PR 26)."""
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.core import cluster

    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     tick_interval_s=0.01)
    if group_shards:
        from jax.sharding import NamedSharding

        from raftsql_tpu.parallel import sharded

        mesh = sharded.make_mesh(1, group_shards)
        shapes = jax.eval_shape(lambda: (
            cluster.init_cluster_state(cfg),
            cluster.empty_cluster_inbox(cfg)))
        states, inboxes = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            shapes, sharded.cluster_shardings(mesh))
        lowered = sharded.make_sharded_cluster_step_host(cfg, mesh).lower(
            states, inboxes,
            jax.ShapeDtypeStruct(
                (peers, groups), jnp.int32,
                sharding=NamedSharding(mesh, sharded.prop_spec())),
            jax.ShapeDtypeStruct(
                (peers,), jnp.int32,
                sharding=NamedSharding(mesh, sharded.timer_spec())))
    else:
        lowered = cluster.cluster_step_host.lower(
            cfg, cluster.init_cluster_state(cfg),
            cluster.empty_cluster_inbox(cfg),
            jnp.zeros((peers, groups), jnp.int32),
            jnp.ones((peers,), jnp.int32))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    return op_scopes(text)


def by_scope(ops: Iterable[Tuple[str, float]],
             scopes: Dict[str, str]) -> List[Tuple[str, float]]:
    """Sum a trace's (operation name, seconds) pairs by scope, largest
    first; an operation the program does not hold (the module's own
    event, another program's) counts under its own name."""
    sums: Dict[str, float] = {}
    for name, seconds in ops:
        key = scopes.get(name, name)
        sums[key] = sums.get(key, 0.0) + seconds
    return sorted(sums.items(), key=lambda kv: -kv[1])


def main(argv: List[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m raftsql_tpu.obs.scopes",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", type=int, required=True)
    ap.add_argument("--peers", type=int, default=3)
    ap.add_argument("--group-shards", type=int, default=0,
                    help="the --mesh step over this many devices "
                         "(0: the --fused step)")
    ap.add_argument("result", nargs="?",
                    help="a file whose last line is a --trace 1 result "
                         "of benchmarks/run.py; without it the map "
                         "itself is printed")
    args = ap.parse_args(argv)
    from raftsql_tpu.utils.device import select_device
    select_device()
    scopes = step_scopes(args.groups, args.peers, args.group_shards)
    if args.result is None:
        json.dump(scopes, sys.stdout, indent=1, sort_keys=True)
        print()
        return 0
    with open(args.result) as f:
        result = json.loads(f.read().strip().splitlines()[-1])
    ops = result["breakdown"]["device_ops"]
    for name, seconds in ops:
        print(f"{seconds:12.6f} s  {scopes.get(name, '-'):14s} {name}")
    print("by phase:")
    for scope, seconds in by_scope(ops, scopes):
        print(f"{seconds:12.6f} s  {scope}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
