# igaming-platform-tpu build/test/bench targets.

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test test-loaded chip-smoke lint lint-json lint-changed lint-sarif lint-update-baseline ci-static bench bench-all bench-fused bench-mesh bench-hostprof bench-trend bench-paced bench-replicas drill eval native proto run-risk run-wallet dryrun clean soak soak-wire soak-chaos soak-fleet-chaos soak-chaos-ledger soak-slo soak-online soak-drift soak-session soak-deadline replay-verify fleet api-test migrate-up migrate-down migrate-status seed docker-build docker-push infra-up infra-down

all: native test

# The tier-1 suite as the driver runs it (/root/TESTS_LAST_RUN.json):
# 'not slow', six xdist workers, one file per worker at a time, on the
# virtual 8-device CPU mesh (tests/conftest.py). The driver's clock is
# 1470 s; `timeout` holds a local run to the same.
TIER1 = timeout -k 10 1470 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
	--continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly

test:
	$(TIER1)

# Rehearsal of the driver's machine: the same command held to one core.
# It must end in half the clock (735 s) with the same count.
test-loaded:
	taskset -c 0 $(TIER1)

# The quickest proof the system still starts on the chip: one process,
# the real server over gRPC at the flagship width, trainer hot-swap,
# every Pallas kernel, the data=4 mesh when four devices are visible.
# Needs a TPU; exits non-zero without one (never a CPU fallback).
chip-smoke:
	$(PY) chip_smoke.py

# In-tree static analyzer (no linter ships in this image): rule engine
# with JAX hot-path (JX*), lock-discipline (CC*), metrics/measurement
# (MX*), and hygiene (PY*) analyzers; scoped `# noqa: <RULE-ID>`
# suppression and a shrink-only baseline (tools/analysis/baseline.json).
# Catalog: docs/static-analysis.md. `lint-json` emits machine output.
lint:
	$(PY) -m tools.analysis

lint-json:
	$(PY) -m tools.analysis --format=json

# Incremental mode: findings only in git-changed files (cross-file rules
# still see the whole repo; stale-baseline enforcement skipped).
lint-changed:
	$(PY) -m tools.analysis --changed-only

# SARIF 2.1.0 for CI inline annotation (deterministic, golden-pinned).
lint-sarif:
	$(PY) -m tools.analysis --format=sarif

lint-update-baseline:
	$(PY) -m tools.analysis --update-baseline

# The one static gate CI calls: SARIF analyzer pass (analysis.sarif is
# the upload artifact for inline annotation; the exit code fails the
# target on any non-baselined finding) THEN the perf-trajectory gate
# (tools/benchtrend.py --gate: regressions over the committed
# *_rNN.json series are fatal). Ordered so code findings surface before
# perf flags; either failing fails the target.
ci-static:
	$(PY) -m tools.analysis --format=sarif > analysis.sarif
	$(PY) tools/benchtrend.py --gate

# Headline benchmark (driver contract: one JSON line). Needs a TPU
# (or an explicit JAX_PLATFORMS=cpu rig); a failed arm is the exit code.
bench:
	$(PY) bench.py

# The full benchmark matrix (five BASELINE configs + wallet pipeline):
# one child process per config, the parent never touches JAX, a failed
# child fails the run.
bench-all:
	$(PY) benchmarks/run_all.py

# Fused-graph A/B (PR 14): fused vs split with drift sketching AND an
# active shadow candidate — honest dispatches/RPC, device-step p99 and
# open-loop paced e2e p99 per arm -> FUSED_r14.json (gated: fused arm
# must measure 1.0 dispatches/RPC, latency no worse within noise).
bench-fused:
	$(PY) bench.py --fused

# Slot-sharded state A/B (ISSUE 15): sharded vs replicated feature
# cache + session ring over a forced K-device CPU mesh — bit-exact
# parity, per-chip capacity/HBM (the 1/K claim, measured), honest
# dispatches/RPC and paced p99 per arm -> MESH_r15.json. Gated on
# parity/capacity/dispatches; NEVER on host-side scaling (single-core
# control-rig caveat recorded in the artifact).
BENCH_MESH_K ?= 4
bench-mesh:
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=$(BENCH_MESH_K)" BENCH_MESH_K=$(BENCH_MESH_K) $(PY) bench.py --mesh

# Host-plane cost observatory (ISSUE 16): the stateful serving path
# (index wire, device feature cache, session plane) profiled end to end
# — per-stage µs/row table, interval-union stage coverage, folded-stack
# flamegraph (speedscope at /debug/hostprofz), GC pause accounting with
# in-flight-RPC attribution, and a profiler-on/off/off A/B/A ->
# HOSTPROF_r16.json. Gated on coverage >= 0.90, flamegraph content
# (session bookkeeping + RPC decode named), GC accounting, and the
# on/off ratio >= HOSTPROF_AB_BAR (default 0.90).
bench-hostprof:
	$(PY) bench.py --hostprof

# Perf-trajectory table over every committed *_rNN.json artifact:
# flat-out txns/s + paced/e2e p99 per revision with within-noise
# regression flags (same family+source series only). `--gate` (the
# BENCH_TREND_GATE=1 form) makes flags fatal for CI.
bench-trend:
	$(PY) tools/benchtrend.py $(if $(BENCH_TREND_GATE),--gate,)

# Paced-arrival latency gate (deadline scheduler, PR 11): open-loop
# Poisson ScoreTransaction load at BENCH_PACED_RATE (default 2000 rps on
# the 1-core control rig) with risk-deadline-ms on every request,
# against a production replica process. Exits non-zero unless e2e RPC
# p99 < SLO_OBJECTIVE_MS AND zero requests were scored after their
# deadline. The same arm runs inside `make soak-deadline`.
BENCH_PACED_RATE ?= 2000
bench-paced:
	BENCH_PACED_RATE=$(BENCH_PACED_RATE) $(PY) benchmarks/soak.py --deadline --paced-only

# Deadline-scheduler soak: paced arm + flat-out no-regression A/B +
# burn->shed closed-loop drill (injected latency -> fast burn alert ->
# bulk sheds with pushback -> interactive recovers -> bulk resumes) +
# bit-exact ledger replay across the paced+shed run -> DEADLINE_r12.json.
soak-deadline:
	$(PY) benchmarks/soak.py --deadline

# Replica scaling curve: K wallet replica OS processes over one shared
# PG-wire database (REPLICA_KS, REPLICA_CYCLES; POSTGRES_URL for live PG).
bench-replicas:
	$(PY) benchmarks/replicas.py

# End-to-end rehearsal of the on-device capture script in CPU mode
# (all six artifact stages into a scratch dir, asserted non-empty+JSON).
drill:
	CAPTURE_DRILL=1 $(CPU_ENV) $(PY) -m pytest tests/test_device_capture_drill.py -q

soak:
	$(PY) benchmarks/soak.py

# Sustained mixed load at the gRPC wire (SOAK_DURATION_S, default 60s).
soak-wire:
	$(PY) benchmarks/soak.py --wire

# Follower-kill chaos soak (CHAOS_r06-style artifact).
soak-chaos:
	$(PY) benchmarks/soak.py --chaos

# Fleet chaos: K replica processes behind the account-affinity router,
# replica SIGKILL + brownout + link-drop under load -> FLEET_CHAOS
# artifact (FLEET_REPLICAS, FLEET_CHAOS_DURATION_S, FLEET_FAULTS).
soak-fleet-chaos:
	$(PY) benchmarks/soak.py --fleet-chaos

# Ledger chaos: fs-outage + sink-outage + forced-degraded window +
# mid-run SIGKILL of the server process, then bit-exact replay of the
# surviving decision WAL -> REPLAY_r08.json (LEDGER_CHAOS_DURATION_S).
soak-chaos-ledger:
	$(PY) benchmarks/soak.py --chaos-ledger

# SLO-plane chaos: fleet rig with a device.dispatch latency fault on one
# replica (burn-rate alert + budget attribution + one auto profile) and
# a SIGKILL on another (/debug/fleetz stays live, stale-stamped), plus
# the observability-overhead A/B -> SLO_r09.json (SLO_SOAK_DURATION_S).
soak-slo:
	$(PY) benchmarks/soak.py --slo-chaos

# Online-learning chaos: one production server with the full loop
# (ONLINE_LOOP=1) under live load — ledger-mined hard negatives,
# in-server learner + shadow scoring, gated auto-promotion, injected
# quality regression forcing auto-rollback, SIGKILL mid-loop, then
# bit-exact replay across the promotion boundary + the shadow-overhead
# A/B -> ONLINE_r10.json (ONLINE_SOAK_DURATION_S).
soak-online:
	$(PY) benchmarks/soak.py --online-chaos

# Drift-observatory chaos: clean baseline -> pin reference -> injected
# --drift-ramp must raise the input drift alert and hold promotion via
# the drift_quiet gate -> ramp removal must clear within bound; then a
# 3-replica fleet serves merged drift state (/debug/fleetz) through a
# replica SIGKILL, plus the sketch-on/off overhead A/B
# -> DRIFT_r11.json with explicit gates.
soak-drift:
	$(PY) benchmarks/soak.py --drift-chaos

# Stateful-sequence-scoring chaos: a seeded coordinated fraud ring must
# be flagged by the session path and provably missed by the
# aggregate-only baseline; then a production WIRE_MODE=index replica
# under CLOCK-eviction churn + a mid-run SIGKILL racks up >= 100k
# stateful decisions whose session_state_hash all replay bit-exact,
# with dispatches-per-RPC unchanged and session-on/off A/B within noise
# -> SESSION_r13.json with explicit gates.
soak-session:
	$(PY) benchmarks/soak.py --session-chaos

# Bit-exact decision replay smoke (tier-1-adjacent): score a seeded
# batch under CHAOS_PLAN (ledger-append faults), replay the ledger with
# tools/replay.py, diff every output field — heuristic tier included.
replay-verify:
	JAX_PLATFORMS=cpu $(PY) -m tools.replay --verify

# Boot a local scoring fleet (FLEET_K replicas, default 3) and print
# the replica table; Ctrl-C tears it down.
fleet:
	$(PY) benchmarks/fleet.py

# API smoke against RUNNING services (the reference's grpcurl api-test).
api-test:
	$(PY) benchmarks/smoke.py

# Schema migrations for the Postgres store of record (DATABASE_URL).
migrate-up:
	$(PY) -m igaming_platform_tpu.platform.migrations '$(DATABASE_URL)' up

migrate-down:
	$(PY) -m igaming_platform_tpu.platform.migrations '$(DATABASE_URL)' down $(TARGET)

migrate-status:
	$(PY) -m igaming_platform_tpu.platform.migrations '$(DATABASE_URL)' status

# Dev fixture accounts through the real pipeline (DATABASE_URL, as run-wallet).
seed:
	$(PY) -m igaming_platform_tpu.platform.seed

# Model quality on labeled synthetic fraud: trains multitask + GBDT and
# writes EVAL.json (AUC / PR / calibration; trained > mock > rules).
# The model-validate capability of the reference Makefile:215-225.
# Quality, not speed: the committed file is a JAX_PLATFORMS=cpu run.
eval:
	$(PY) -m igaming_platform_tpu.train.eval --out EVAL.json

# Native runtime pieces (C++ feature store).
native:
	sh native/build.sh

# Regenerate protobuf code (wire contract under proto/).
proto:
	protoc -I proto --python_out=igaming_platform_tpu/proto_gen \
	  proto/risk/v1/risk.proto proto/wallet/v1/wallet.proto \
	  proto/grpc/health/v1/health.proto \
	  proto/grpc/reflection/v1alpha/reflection.proto

# Service processes.
run-risk:
	$(PY) -m igaming_platform_tpu.serve.server

run-wallet:
	$(PY) -m igaming_platform_tpu.platform.server

# LTV batch job: wallet DB -> per-player segments (one device pass).
ltv-job:
	$(PY) -m igaming_platform_tpu.serve.ltv_job $(DB)

# Image build/publish (the reference Makefile:191-209 equivalents).
# One image serves both services (CMD selects); REGISTRY/TAG override.
REGISTRY ?= localhost:5000
TAG ?= latest
IMAGE = $(REGISTRY)/igaming-platform-tpu:$(TAG)

docker-build:
	docker build -f deploy/Dockerfile -t $(IMAGE) .

docker-push: docker-build
	docker push $(IMAGE)

# Infra stack up/down (stores profile adds PG/Redis/RabbitMQ/ClickHouse).
infra-up:
	docker compose -f deploy/docker-compose.yml --profile stores up -d

infra-down:
	docker compose -f deploy/docker-compose.yml --profile stores down

# Multi-chip sharding validation on virtual CPU devices.
dryrun:
	$(CPU_ENV) $(PY) __graft_entry__.py

clean:
	rm -rf native/lib .pytest_cache .jax_cache chiprun_out
	find . -name __pycache__ -type d -exec rm -rf {} +
