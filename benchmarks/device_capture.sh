#!/bin/sh
# On-device artifact capture: every harness, one after another, each as
# its OWN process (a chip belongs to one process at a time; this shell
# never touches JAX). Each harness exits non-zero when it finds no
# accelerator unless JAX_PLATFORMS=cpu asks for a CPU rig, and names the
# device it ran on in its output.
#
# Usage: sh benchmarks/device_capture.sh [OUT_DIR]   (default chiprun_out/capture)
# Env:   CAPTURE_QUICK=1  -> tiny parameters; the CI drill runs this in
#        CPU mode and asserts all artifacts appear non-empty and
#        JSON-parseable (tests/test_device_capture_drill.py) — the
#        script's paths/env/redirection are exercised end-to-end.
set -x
cd "$(dirname "$0")/.." || exit 1
OUT=${1:-chiprun_out/capture}
mkdir -p "$OUT"

if [ "${CAPTURE_QUICK}" = "1" ]; then
    BENCH_ENV="BENCH_ITERS=4 BENCH_WARMUP=1 BENCH_BATCH=1024 BENCH_E2E_DURATION_S=2 BENCH_E2E_ROWS_PER_RPC=1024 BENCH_E2E_CONCURRENCY=2"
    SOAK_S=2
    MATRIX_CONFIGS="single_txn wallet"
    EVAL_ARGS="--n-train 3000 --n-test 1500 --steps 25"
    PARITY_ARGS="--rows 2000 --steps 40"
else
    BENCH_ENV=""
    SOAK_S=60
    MATRIX_CONFIGS=""
    EVAL_ARGS=""
    PARITY_ARGS=""
fi

# 1. Headline driver bench (the round's official metric shape).
timeout 1200 env $BENCH_ENV python bench.py > "$OUT/BENCH_device.json" 2> "$OUT/BENCH_device.log"

# 2. Sustained wire soak, int8 transport — every-window compliance.
timeout 1500 env WIRE_DTYPE=int8 SOAK_DURATION_S=$SOAK_S python benchmarks/soak.py --wire \
  > "$OUT/SOAK_int8.json" 2> "$OUT/SOAK_int8.log"

# 3. Sustained wire soak, default f32.
timeout 1500 env SOAK_DURATION_S=$SOAK_S python benchmarks/soak.py --wire \
  > "$OUT/SOAK_f32.json" 2> "$OUT/SOAK_f32.log"

# 3b. Paced soak at 110k txns/s offered: latency AT the SLO rate.
timeout 1500 env SOAK_DURATION_S=$SOAK_S SOAK_TARGET_RATE=110000 python benchmarks/soak.py --wire \
  > "$OUT/SOAK_paced110k.json" 2> "$OUT/SOAK_paced110k.log"

# 4. Benchmark matrix (full by default; two host-safe configs in QUICK).
timeout 5400 python benchmarks/run_all.py $MATRIX_CONFIGS > "$OUT/BENCH_MATRIX.json" 2> "$OUT/BENCH_MATRIX.log"

# 5. Model-quality eval on device.
timeout 3600 python -m igaming_platform_tpu.train.eval $EVAL_ARGS --out "$OUT/EVAL_device.json" \
  > "$OUT/EVAL_device.log" 2>&1

# 6. Trained-model TPU-vs-CPU numerics parity.
timeout 3600 python -m igaming_platform_tpu.train.device_parity $PARITY_ARGS --out "$OUT/DEVICE_PARITY.json" \
  > "$OUT/DEVICE_PARITY.log" 2>&1

echo done
