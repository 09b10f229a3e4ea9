"""Run every BASELINE config and print one JSON line per result.

Usage: python benchmarks/run_all.py [config ...]
Configs: grpc_e2e grpc_e2e_index single_txn replay sequence ltv train
wallet wallet_wire wallet_pg (default: all). grpc_e2e_index is the
device-resident feature-cache arm (index-mode wire frames, HBM table —
serve/device_cache.py); its artifact line carries the same schema plus
`wire_mode`, and both e2e lines separate `bulk_shed` from `errors`.
Both e2e arms also carry a `stage_breakdown` block (per-stage p50/p99 +
stage coverage of the RPC span, sourced from the flight recorder —
obs/flight.py) so the artifact itself says whether a gap is wire decode,
feature gather, the device step, or readback.

Several configs run one after another, each in its OWN subprocess: the
serving configs leave device queues / batcher threads / allocator state
behind that distort later measurements in the same process. A chip
belongs to one process at a time, so the parent NEVER touches JAX — it
only spawns one child at a time and relays its line; the device named on
each line is the one the child reports. A child that fails makes the
parent exit non-zero. BENCH_NO_ISOLATE=1 runs everything in this process.
"""

import json
import os
import subprocess
import sys

from configs import ALL_CONFIGS


def run_config(name: str) -> dict:
    """One config in THIS process (the only JAX process of its run)."""
    from igaming_platform_tpu.core.devices import (
        device_label,
        enable_persistent_compile_cache,
        require_device,
    )

    require_device()
    # Children of one matrix share compiled executables through the
    # persistent cache; every process resolves the same directory.
    enable_persistent_compile_cache()
    result = ALL_CONFIGS[name]()
    import jax

    label = device_label()
    result.update(config=name, device=str(jax.devices()[0]),
                  platform=label["platform"], device_kind=label["kind"],
                  device_count=label["count"])
    return result


def main() -> int:
    names = sys.argv[1:] or list(ALL_CONFIGS)
    unknown = [n for n in names if ALL_CONFIGS.get(n) is None]
    if unknown:
        print(json.dumps({"error": f"unknown config(s): {unknown}"}))
        return 2
    isolate = len(names) > 1 and os.environ.get("BENCH_NO_ISOLATE") != "1"
    failed = 0
    for name in names:
        if not isolate:
            print(json.dumps(run_config(name)), flush=True)
            continue
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), name],
                capture_output=True, text=True, timeout=900,
            )
        except subprocess.TimeoutExpired:
            # One hung config must not abort the remaining ones.
            failed += 1
            print(json.dumps({"config": name, "error": "timeout after 900s"}),
                  flush=True)
            continue
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        if proc.returncode != 0 or not line.startswith("{"):
            failed += 1
            line = json.dumps({
                "config": name, "error": f"rc={proc.returncode}",
                "stderr_tail": proc.stderr[-300:],
            })
        print(line, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
