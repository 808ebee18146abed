# Build/test harness — parity with the reference Makefile (build, test,
# vet targets; reference Makefile:1-23), adapted to the Python/C++ tree.

PY ?= python
SEED ?= 0

.PHONY: all native native-check native-sanitize test vet bench chaos chaos-membership chaos-procs \
	chaos-mesh chaos-reads chaos-transfer chaos-reshard chaos-quorum chaos-pod chaos-replica \
	chaos-overload trace prom-lint clean

# The mesh families and tests need a multi-device platform; 8 virtual
# CPU devices is the no-hardware testing recipe (tests/conftest.py).
MESH_ENV = JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8"

# "Build" = compile the native C++ components (storage fast path).
all: native

native:
	$(PY) -c "from raftsql_tpu.native.build import load_native_wal, \
	              load_native_apply; \
	          print('native wal:', 'ok' if load_native_wal() else 'UNAVAILABLE'); \
	          print('native apply:', 'ok' if load_native_apply() else 'UNAVAILABLE')"

# Build-check the native GROUP-COMMIT path (the views' group bias over
# wal.cc): write through per-peer views of one shared native WAL,
# replay, and assert the per-peer split round-trips.  Fails if the
# toolchain is present but the group-commit ABI is broken; degrades to
# a SKIP where no compiler exists (the Python backend covers those
# hosts).
native-check:
	$(PY) scripts/check_native_gc.py

# Serving smoke (scripts/serving_smoke.py): a --fused --workers 2
# deployment driven by the native loadgen; asserts zero errors and a
# req/s floor.  SMOKE_SECONDS/SMOKE_CLIENTS/SMOKE_MIN_RPS override.
serving-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/serving_smoke.py

# make test captures output like the reference (Makefile:10-15).
test:
	$(PY) -m pytest tests/ -q 2>&1 | tee test.out

# Static analysis stand-in for `go vet`: compile every source file,
# then the raftlint suite (raftsql_tpu/analysis/) — the five classic
# AST rules plus the project-invariant checkers: jit-stability,
# wall-clock/unseeded-random determinism, thread-ownership,
# fail-closed, memory-model.  `python -m raftsql_tpu.analysis --list`
# enumerates the rules; suppress per line with
# `# raftlint: disable=<rule> -- why`.
vet:
	$(PY) -m compileall -q raftsql_tpu tests bench.py chip_smoke.py __graft_entry__.py \
	      scripts
	$(PY) scripts/vet.py

# Needs a chip: an unpinned parent whose probe does not report a tpu
# exits non-zero and prints no result (BENCH_PLATFORM=cpu = development).
bench:
	$(PY) bench.py

# Deterministic chaos scenario (raftsql_tpu/chaos/): seeded partitions,
# crashes, fsync/torn-write faults + invariant checking, run TWICE and
# digest-compared to prove the seed reproduces bit-for-bit.
#   make chaos SEED=17
chaos:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --seed $(SEED) --ticks 240 --runs 2

# Sweep one seed through EVERY scenario family of the fault matrix
# (asym partitions, clock skew, wire corruption, ENOSPC, fsync stalls,
# compaction+crash, compaction+InstallSnapshot+crash, real-TCP chaos).
# Deterministic families run twice and must digest-match; all families
# must pass every invariant.  See README "Chaos fault matrix".
#   make chaos-matrix SEED=17
chaos-matrix:
	$(MESH_ENV) $(PY) -m raftsql_tpu.chaos.run \
	  --matrix --seed $(SEED)

# Mesh-skew chaos (runtime/mesh.py MeshClusterNode): the fused skew
# family's schedule on the MESH runtime — per-peer clock drift through
# the shard_map'd step's sharded timer vector, a crash + replay from
# the per-shard WAL dirs, run twice and digest-compared.  Closes the
# old MeshLockstepOnlyError frontier.
#   make chaos-mesh SEED=17
chaos-mesh:
	$(MESH_ENV) $(PY) -m raftsql_tpu.chaos.run \
	  --family mesh_skew --seed $(SEED)

# Membership-churn chaos (raftsql_tpu/membership/): SIGKILL a voter,
# boot a fresh spare, add-learner -> promote (joint consensus) ->
# remove the dead member, under drops + a second crash.  Deterministic:
# runs the seed twice and digest-compares, and every invariant
# (including "no quorum from a removed majority") must hold.
#   make chaos-membership SEED=17
chaos-membership:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --family membership --seed $(SEED)

# Read-plane nemesis (raftsql_tpu/chaos/): lease / ReadIndex /
# session / follower reads racing writes under clock skew, asymmetric
# partitions, leader kills and crashes — the fused family run twice
# and digest-compared, the LEASE-FALSIFICATION sensitivity pair (a
# deliberately mis-sized lease bound under 4x skew MUST be caught by
# the read-linearizability invariant; the same schedule with a correct
# bound must pass), and the process-plane read nemesis over real
# server processes (verdict digests compared).
#   make chaos-reads SEED=17
chaos-reads:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --reads --seed $(SEED)

# Leadership-transfer nemesis (raftsql_tpu/chaos/): graceful transfers
# (core/step.py TimeoutNow kernel, thesis §3.10) racing drops,
# leader-targeted partitions, one-directional cuts, clock skew and
# crash+restart under live acked-PUT load — the fused family run twice
# and digest-compared with a no-availability-loss-during-transfer
# invariant (bounded proposal stall, aborted transfers leave the group
# serving), the BROKEN-KERNEL falsification pair (a kernel that
# abdicates before the target caught up MUST be caught on a directed
# lagging-target schedule; the correct kernel must pass the same
# schedule), and the process-plane POST /transfer nemesis over real
# server processes (verdict digests compared).
#   make chaos-transfer SEED=17
chaos-transfer:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --transfers --seed $(SEED)

# Elastic-keyspace nemesis (raftsql_tpu/reshard/): seeded group
# SPLIT / MERGE / MIGRATE schedules racing partitions, message drops,
# whole-cluster crash+restart, coordinator SIGKILL mid-verb (rebuilt
# from the raft-log journal fold alone) and a disk fault on the
# migrate snapshot ship — under live acked-PUT load, checked by
# NoAckedWriteLost (every acked write readable in exactly one
# post-reshard group, WAL-fold post-mortem after every restart) and
# NoAvailabilityLoss (writes outside the moving range never stall past
# a bound; verbs always resolve).  The family runs twice and is
# digest-compared, then the PREMATURE-FLIP falsification pair: a
# coordinator that flips the router before the destination durably
# applied the copies MUST be caught on a directed copy-starving
# schedule; the correct coordinator must complete the same schedule.
#   make chaos-reshard SEED=17
chaos-reshard:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --reshard --seed $(SEED)

# Quorum-geometry nemesis (raftsql_tpu/chaos/): flexible write /
# election quorums and witness peers under fire.  The witness-cluster
# family (2 full voters + 1 witness, W=E=2) runs twice and is
# digest-compared — the witness must replicate (witness_appends) but
# never publish, with exactly one apply/shard stream fewer than WAL
# streams — then TWO falsification pairs: (a) a non-intersecting
# W=1/E=2 geometry (config-refused without unsafe_quorum_geometry;
# asserted) MUST be caught as divergent committed slots when a
# partitioned pinned leader solo-commits against the majority's
# rewrite, and the same schedule at W=2 must pass; (b) a witness
# wrongly counted toward the LEASE quorum (unsafe_witness_lease) MUST
# be caught as a stale lease read when it grants a prevote inside the
# deposed leader's live lease, and the honest witness must pass the
# same schedule.
#   make chaos-quorum SEED=17
chaos-quorum:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --quorum --seed $(SEED)

# Multi-host pod chaos (raftsql_tpu/chaos/pod.py): a seeded nemesis
# over a REAL 2-process pod (raftsql_tpu/pod/ — dry-run multi-process
# on one box, TcpPodTransport collective, one group shard durable per
# host).  Three incarnations per run: a propose-plane cut at one
# origin, SIGKILL of the NON-coordinator host (pod-wide fail-stop
# abort), SIGKILL of the COORDINATOR, then a fault-free audit
# incarnation — every acked write must survive the merged cross-host
# replay (durability), apply exactly once post-dedup (re-offer retry
# tokens), and every host must fold to the identical state
# (convergence).  Runs the seed TWICE (plan + verdict digests must
# match), then the PREMATURE-ACK falsification pair: acks written
# before any durability plus a scripted crash MUST be caught by the
# durability invariant; honest acks on the same schedule must pass.
#   make chaos-pod SEED=17
chaos-pod:
	$(MESH_ENV) $(PY) -m raftsql_tpu.chaos.run \
	  --pod --seed $(SEED)

# Read-replica tier chaos (raftsql_tpu/chaos/replica.py): a seeded
# nemesis over a fused engine publishing the shm delta stream
# (--replica-listen) and REAL `python -m raftsql_tpu.replica`
# processes subscribed through nemesis-owned TCP proxies — a
# subscription cut + heal, a replica SIGKILL + respawn, and one
# flipped stream bit — under an acked-write workload probing session
# and linear reads at every replica.  StaleReadNever: a 200 answer
# below the mode's bound is the violation, a 421 refusal never is;
# the audit requires exact convergence and the corruption surfacing
# as a CRC failure.  Runs the seed TWICE (plan + verdict digests must
# match), then the UNSAFE-SERVE falsification pair: a replica with
# every fail-closed gate skipped under a never-healed cut MUST be
# caught serving stale; the same schedule with the gates on must
# pass by refusing.
#   make chaos-replica SEED=17
chaos-replica:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --replica --seed $(SEED)

# Overload-control chaos (raftsql_tpu/overload/): a seeded OPEN-LOOP
# nemesis offering ~2x the engine's drain rate — burst windows,
# hot-group skew, device-step deadlines on a fraction of writes,
# slow-fsync stalls, and a mid-overload crash+restart — against the
# bounded admission controller attached exactly as the server attaches
# it.  Invariants: the propose backlog never exceeds the hard cap
# (OVERLOAD-MEMORY, measured against the engine's actual queues every
# tick), every acked write survives the restart replay, goodput clears
# the plan's floor despite the overload, and no group starves.  The
# seed runs TWICE (plan + result digests must match bit-for-bit),
# then the falsification pair: the identical schedule with NO
# admission controller MUST be caught by OVERLOAD-MEMORY, and with
# the bounded controller must pass.
#   make chaos-overload SEED=17
chaos-overload:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --overload --seed $(SEED)

# Process-plane chaos (raftsql_tpu/chaos/proc.py): a seeded nemesis
# over REAL server/main.py OS processes — leader-targeted + random
# SIGKILL, SIGSTOP/SIGCONT stalls, a rolling-restart storm (clean
# SIGTERM + same-port rebinds), env-injected disk faults
# (RAFTSQL_FSIO_FAULTS: ENOSPC on a WAL write + hard process exit at a
# WAL fsync) — under a live acked-PUT workload.  The seed runs TWICE:
# schedule + invariant-verdict digests must match (committed history
# crosses real kernel scheduling, so tick-for-tick replay is out of
# scope on this plane — see README "Process-plane chaos").
#   make chaos-procs SEED=17
chaos-procs:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.chaos.run \
	  --procs --seed $(SEED)

# Metrics lint (scripts/check_prom.py): boot a --fused node per HTTP
# plane (aio + threaded), drive writes, scrape GET /metrics?format=prom
# and the Accept-negotiated path, validate the exposition under a
# strict parser, and check every JSON /metrics field round-trips into
# a Prometheus sample.  --url scrapes a live node instead.
prom-lint:
	JAX_PLATFORMS=cpu $(PY) scripts/check_prom.py

# Observability demo (raftsql_tpu/obs/): run a traced fused cluster and
# emit Chrome trace-event JSON — load trace.json at ui.perfetto.dev or
# chrome://tracing.  The same spans/counters are live on a running
# server at GET /trace and GET /events (enable with --trace).
trace:
	JAX_PLATFORMS=cpu $(PY) -m raftsql_tpu.obs.trace_demo --out trace.json

# AddressSanitizer + UBSan pass over the native WAL stress harness
# (scripts/native_sanitize.py; add --san tsan for the full trio).
# Degrades to SKIP where no g++ exists — those hosts run the Python
# WAL backend.
native-sanitize:
	$(PY) scripts/native_sanitize.py

# ThreadSanitizer pass over the native WAL's locking (SURVEY.md §5.2):
# 4 threads x appends/hardstate/compact/snapshot/sync on one handle.
tsan:
	g++ -O1 -g -std=c++17 -fsanitize=thread -fPIC \
	    -o /tmp/wal_stress_tsan \
	    raftsql_tpu/native/wal_stress.cc raftsql_tpu/native/wal.cc
	rm -rf /tmp/wal_tsan_dir && mkdir -p /tmp/wal_tsan_dir
	/tmp/wal_stress_tsan /tmp/wal_tsan_dir 2000

clean:
	rm -f test.out flight-*.json raftsql_tpu/native/_native_*.so \
	      raftsql_tpu/native/_wal_stress_* raftsql_tpu/native/_http_load*
	find . -name __pycache__ -type d -exec rm -rf {} +

# The durable product paths, quick local shapes (one JSON line each).
bench-durable:
	BENCH_CHILD=1 BENCH_PLATFORM=cpu BENCH_CONFIG=durable \
	  BENCH_DURABLE_MODE=fused BENCH_E=32 python bench.py

bench-http:
	BENCH_CHILD=1 BENCH_PLATFORM=cpu BENCH_CONFIG=http \
	  BENCH_HTTP_SECONDS=8 python bench.py
