# igaming-platform-tpu build/test/drill targets.

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test test-loaded chip-smoke lint lint-json lint-changed lint-sarif lint-update-baseline ci-static eval native proto run-risk run-wallet ltv-job dryrun clean soak-chaos soak-fleet-chaos soak-chaos-ledger soak-slo soak-online soak-drift soak-session soak-deadline replay-verify fleet api-test migrate-up migrate-down migrate-status seed docker-build docker-push infra-up infra-down

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

# The one static gate CI calls: the analyzer's SARIF pass (analysis.sarif
# is the upload artifact for inline annotation; the exit code fails the
# target on any non-baselined finding).
ci-static:
	$(PY) -m tools.analysis --format=sarif > analysis.sarif

# The fault drills (tools/drills/soak.py): each boots its own replica
# processes on the CPU rig, breaks them on purpose, prints one JSON line
# and exits non-zero when a gate on a guarantee misses. A drill writes its
# artifact where its variable says (DEADLINE_OUT, SLO_ARTIFACT, ...), by
# default under build/. None measures speed: the benchmark is
# `python3 -m chipbench.run --workload <cell>` on a TPU (BENCHMARK.json).

# Deadline-scheduler drill: open-loop Poisson ScoreTransaction load at
# BENCH_PACED_RATE (default 2000 rps) with risk-deadline-ms on every
# request (e2e p99 under SLO_OBJECTIVE_MS, zero requests scored after
# their deadline) + burn->shed closed loop (injected latency -> fast burn
# alert -> bulk sheds with pushback -> interactive recovers -> bulk
# resumes) + bit-exact ledger replay across the paced+shed run.
soak-deadline:
	$(PY) -m tools.drills.soak --deadline

# Follower-kill chaos drill (prints its artifact).
soak-chaos:
	$(PY) -m tools.drills.soak --chaos

# Fleet chaos: K replica processes behind the account-affinity router,
# replica SIGKILL + brownout + link-drop under load; prints its artifact
# (FLEET_REPLICAS, FLEET_CHAOS_DURATION_S, FLEET_FAULTS).
soak-fleet-chaos:
	$(PY) -m tools.drills.soak --fleet-chaos

# Ledger chaos: fs-outage + sink-outage + forced-degraded window +
# mid-run SIGKILL of the server process, then bit-exact replay of the
# surviving decision WAL -> LEDGER_CHAOS_OUT (LEDGER_CHAOS_DURATION_S).
soak-chaos-ledger:
	$(PY) -m tools.drills.soak --chaos-ledger

# SLO-plane chaos: fleet rig with a device.dispatch latency fault on one
# replica (burn-rate alert + budget attribution + one auto profile) and
# a SIGKILL on another (/debug/fleetz stays live, stale-stamped)
# -> SLO_ARTIFACT (SLO_SOAK_DURATION_S).
soak-slo:
	$(PY) -m tools.drills.soak --slo-chaos

# Online-learning chaos: one production server with the full loop
# (ONLINE_LOOP=1) under live load — ledger-mined hard negatives,
# in-server learner + shadow scoring, gated auto-promotion, injected
# quality regression forcing auto-rollback, SIGKILL mid-loop, then
# bit-exact replay across the promotion boundary -> ONLINE_CHAOS_OUT
# (ONLINE_SOAK_DURATION_S).
soak-online:
	$(PY) -m tools.drills.soak --online-chaos

# Drift-observatory chaos: clean baseline -> pin reference -> injected
# --drift-ramp must raise the input drift alert and hold promotion via
# the drift_quiet gate -> ramp removal must clear within bound; then a
# 3-replica fleet serves merged drift state (/debug/fleetz) through a
# replica SIGKILL -> DRIFT_ARTIFACT with explicit gates.
soak-drift:
	$(PY) -m tools.drills.soak --drift-chaos

# Stateful-sequence-scoring chaos: a seeded coordinated fraud ring must
# be flagged by the session path and provably missed by the
# aggregate-only baseline; then a production WIRE_MODE=index replica
# under CLOCK-eviction churn + a mid-run SIGKILL racks up >= 100k
# stateful decisions whose session_state_hash all replay bit-exact,
# with dispatches-per-RPC unchanged -> SESSION_OUT with explicit gates.
soak-session:
	$(PY) -m tools.drills.soak --session-chaos

# Bit-exact decision replay smoke (tier-1-adjacent): score a seeded
# batch under CHAOS_PLAN (ledger-append faults), replay the ledger with
# tools/replay.py, diff every output field — heuristic tier included.
replay-verify:
	JAX_PLATFORMS=cpu $(PY) -m tools.replay --verify

# Boot a local scoring fleet (FLEET_K replicas, default 3) and print
# the replica table; Ctrl-C tears it down.
fleet:
	$(PY) -m tools.drills.fleet

# API smoke against RUNNING services (the reference's grpcurl api-test).
api-test:
	$(PY) -m tools.drills.smoke

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
