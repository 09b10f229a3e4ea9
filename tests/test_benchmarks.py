"""Keep the benchmark configs executable (tiny sizes, CPU)."""

import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))

from configs import (  # noqa: E402
    config1_single_txn_latency,
    config2_replay_throughput,
    config3_sequence_throughput,
    config4_ltv_batch_throughput,
    config5_training_throughput,
    config6_wallet_ops,
    config7_wallet_wire,
    config8_wallet_pg,
)


def test_config1_runs():
    r = config1_single_txn_latency(n_requests=30, batch_size=32)
    assert r["value"] > 0 and r["unit"] == "ms"


def test_config2_runs():
    r = config2_replay_throughput(n_events=300, batch_size=64,
                                  store_max_accounts=4096)
    assert r["events"] == 300
    assert r["value"] > 0


def test_config3_runs():
    r = config3_sequence_throughput(batch=4, seq_len=32, iters=2, long_s=128)
    assert r["value"] > 0


def test_config4_runs():
    r = config4_ltv_batch_throughput(rows=1000, iters=2)
    assert r["value"] > 0


def test_config5_runs():
    r = config5_training_throughput(steps=3, batch_size=128)
    assert r["value"] > 0


def test_config6_runs():
    r = config6_wallet_ops(n_threads=2, cycles=4)
    assert r["value"] > 0 and r["unit"] == "ops/s"
    assert r["errors"] == 0 and r["store_errors"] == 0
    assert r["store_ops_per_sec"] > 0
    assert r["ops"] == 2 * 4 * 3  # threads x cycles x ops-per-cycle


def test_config7_runs():
    r = config7_wallet_wire(n_threads=2, cycles=3)
    assert r["value"] > 0 and r["unit"] == "ops/s"
    # Real localhost gRPC with real deadlines: tolerate a single blown
    # deadline on an overloaded CI host. The artifact's `errors` field
    # itself stays strict — this budget is test-only.
    assert r["errors"] <= 1
    assert r["ops"] >= 2 * 3 * 3 - 1


def test_config8_runs():
    r = config8_wallet_pg(n_threads=2, cycles=3)
    assert r["value"] > 0 and r["unit"] == "ops/s"
    assert "sqlite-backed PG server" in r["backend"]  # honest labeling
    assert r["errors"] <= 1
    assert r["ops"] >= 2 * 3 * 3 - 1
