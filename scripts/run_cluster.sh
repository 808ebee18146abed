#!/bin/sh
# Local 3-node cluster without goreman — same topology as the Procfile
# (reference Procfile:2-4).  Ctrl-C stops all nodes.  The nodes step on
# the CPU: three processes cannot share one chip (see Procfile).
set -e
cd "$(dirname "$0")/.."
CLUSTER=http://127.0.0.1:12379,http://127.0.0.1:22379,http://127.0.0.1:32379
PIDS=""
trap 'kill $PIDS 2>/dev/null || true' INT TERM EXIT
for i in 1 2 3; do
    JAX_PLATFORMS=cpu python -m raftsql_tpu.server.main --id $i --cluster "$CLUSTER" \
        --port ${i}2380 "$@" &
    PIDS="$PIDS $!"
done
wait
