"""Fleet + server surfaces of the drift observatory: /debug/driftz on a
full RiskServer (GET snapshot, POST pin/save/load), the FIXED
POST /debug/outcomes contract (accepted/unknown counts, 400 on
malformed), and /debug/fleetz serving merged per-replica drift state —
counts preserved across the merge, mixed edges rejected loudly, dead
replicas stale-stamped without blocking the plane."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from igaming_platform_tpu.core.config import (
    BatcherConfig,
    RiskServiceConfig,
    ScoringConfig,
)
from igaming_platform_tpu.obs import drift as dm
from igaming_platform_tpu.obs import fleetview as fv
from igaming_platform_tpu.obs.metrics import ServiceMetrics
from igaming_platform_tpu.train.fraudgen import generate_labeled


def _sketch_vec(seed: int, n: int):
    rng = np.random.default_rng(seed)
    x, _y, _k = generate_labeled(rng, n)
    return dm.np_sketch(x, rng.integers(0, 101, n), rng.integers(1, 4, n))


def _driftz_payload(seed: int, n: int, *, edges_fp: str | None = None,
                    ref: dm.DriftReference | None = None) -> dict:
    vec = _sketch_vec(seed, n)
    payload = {
        "edges": {"fingerprint": edges_fp or dm.edges_fingerprint()},
        "window": {"rows": n, "vec": vec.tolist()},
        "alerts": {"input": False, "score": False, "calibration": False},
        "input": {"max_feature_psi": 0.01},
    }
    if ref is not None:
        payload["reference"] = ref.meta()
        payload["reference_state"] = ref.to_json()
    return payload


def _sidecar(driftz: dict | None, hang: bool = False):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if hang:
                time.sleep(30)
                return
            if self.path == "/metrics":
                body, ctype = "", "text/plain"
            elif self.path == "/debug/driftz" and driftz is not None:
                body, ctype = json.dumps(driftz), "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            data = body.encode()
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"127.0.0.1:{httpd.server_address[1]}"


# ---------------------------------------------------------------------------
# fleet_drift_block: merge properties


def test_fleet_block_preserves_counts_and_computes_fleet_psi():
    ref = dm.DriftReference.from_sketch(_sketch_vec(99, 600), source="fleet")
    payloads = [(f"r{i}", _driftz_payload(i, 100 * (i + 1), ref=ref))
                for i in range(3)]
    block = dm.fleet_drift_block(payloads)
    assert block["rows"] == 100 + 200 + 300  # merge preserves counts
    assert block["merge_errors"] == []
    assert "fleet_psi" in block
    assert block["fleet_psi"]["reference_fingerprint"] == ref.fingerprint()
    # Same-process traffic vs a same-generator reference: tiny PSI.
    assert block["fleet_psi"]["max_feature_psi"] < 0.25
    per = {r["replica"]: r for r in block["replicas"]}
    assert per["r1"]["window_rows"] == 200


def test_fleet_block_rejects_mixed_edges_loudly_but_serves_rest():
    good = [(f"r{i}", _driftz_payload(i, 100)) for i in range(2)]
    bad = ("r2", _driftz_payload(5, 50, edges_fp="feedfacefeedface"))
    block = dm.fleet_drift_block(good + [bad])
    # The incompatible replica is REPORTED, not silently summed.
    assert any("r2" in e and "fingerprint mismatch" in e
               for e in block["merge_errors"])
    assert block["rows"] == 200  # only compatible replicas merged


def test_fleet_block_reference_mismatch_skips_psi():
    ref_a = dm.DriftReference.from_sketch(_sketch_vec(1, 200), source="a")
    ref_b = dm.DriftReference.from_sketch(_sketch_vec(2, 200), source="b")
    block = dm.fleet_drift_block([
        ("r0", _driftz_payload(3, 100, ref=ref_a)),
        ("r1", _driftz_payload(4, 100, ref=ref_b)),
    ])
    assert "fleet_psi" not in block
    assert sorted(block["reference_mismatch"]) == sorted(
        [ref_a.fingerprint(), ref_b.fingerprint()])


# ---------------------------------------------------------------------------
# FleetView end-to-end: scrape + merge + staleness


def test_fleetz_serves_merged_drift_with_dead_replica_stale_stamped():
    alive1, addr1 = _sidecar(_driftz_payload(1, 120))
    alive2, addr2 = _sidecar(_driftz_payload(2, 80))
    dead, dead_addr = _sidecar(None)
    dead.shutdown()
    dead.server_close()
    view = fv.FleetView({"r0": addr1, "r1": addr2, "rX": dead_addr},
                        interval_s=0.2, timeout_s=0.3, stale_after_s=1.0,
                        metrics=ServiceMetrics("risk"))
    try:
        view.scrape_once()
        scrapes = view.scrapes_total
        snap = view.snapshot()
        assert view.scrapes_total == scrapes, "snapshot must not scrape"
        fd = snap["fleet_drift"]
        assert fd["rows"] == 200  # both live replicas merged exactly
        assert fd["merge_errors"] == []
        by_rid = {r["replica"]: r for r in snap["replicas"]}
        assert by_rid["rX"]["stale"] is True
        drift_rows = {r["replica"]: r for r in fd["replicas"]}
        assert drift_rows["rX"]["window_rows"] is None  # dead: no claim
        assert drift_rows["r0"]["alerts"] == {
            "input": False, "score": False, "calibration": False}
    finally:
        view.stop()
        alive1.shutdown()
        alive1.server_close()
        alive2.shutdown()
        alive2.server_close()


def test_fleetz_mixed_edges_land_in_merge_errors():
    ok, addr_ok = _sidecar(_driftz_payload(1, 60))
    bad, addr_bad = _sidecar(_driftz_payload(2, 40,
                                             edges_fp="0badc0de0badc0de"))
    view = fv.FleetView({"ok": addr_ok, "bad": addr_bad},
                        interval_s=0.2, timeout_s=0.3, stale_after_s=1.0)
    try:
        view.scrape_once()
        snap = view.snapshot()
        assert snap["fleet_drift"]["rows"] == 60
        assert any("fingerprint mismatch" in e
                   for e in snap["histogram_merge_errors"])
    finally:
        view.stop()
        ok.shutdown()
        ok.server_close()
        bad.shutdown()
        bad.server_close()


# ---------------------------------------------------------------------------
# Full RiskServer: /debug/driftz + the fixed /debug/outcomes


@pytest.fixture(scope="module")
def drift_server(tmp_path_factory):
    import os

    from igaming_platform_tpu.serve.server import RiskServer

    ledger_dir = str(tmp_path_factory.mktemp("drift-ledger"))
    saved = {k: os.environ.get(k) for k in ("LEDGER_DIR", "DRIFT")}
    os.environ["LEDGER_DIR"] = ledger_dir
    os.environ.pop("DRIFT", None)
    cfg = RiskServiceConfig(
        scoring=ScoringConfig(),
        batcher=BatcherConfig(batch_size=32, max_wait_ms=1),
    )
    server = RiskServer(cfg, grpc_port=0, http_port=0, store_max_accounts=4096)
    try:
        yield server
    finally:
        server.shutdown(grace=5)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _post(base: str, path: str, payload: dict) -> tuple[int, dict]:
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(), method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


def test_driftz_endpoint_pin_and_snapshot(drift_server, tmp_path):
    from igaming_platform_tpu.serve.scorer import ScoreRequest

    base = f"http://localhost:{drift_server.http_port}"
    with urllib.request.urlopen(f"{base}/debug/driftz", timeout=10) as r:
        snap = json.load(r)
    assert snap["edges"]["fingerprint"] == dm.edges_fingerprint()
    assert snap["reference"] is None
    # Pinning an empty window is a loud 400, never a garbage reference.
    code, body = _post(base, "/debug/driftz", {"action": "pin_reference"})
    assert code == 400 and "rows" in body["error"]
    # Traffic fills the window; a thin-floor pin then succeeds.
    drift_server.engine.score_batch(
        [ScoreRequest(account_id=f"dz-{i}", amount=1000 + 37 * i)
         for i in range(48)])
    assert drift_server.drift.drain(10)
    code, body = _post(base, "/debug/driftz",
                       {"action": "pin_reference", "min_rows": 16})
    assert code == 200 and body["ok"] and body["reference"]["rows"] >= 48
    # Save + load round-trip through the endpoint.
    ref_path = str(tmp_path / "pinned.json")
    code, _ = _post(base, "/debug/driftz",
                    {"action": "save", "path": ref_path})
    assert code == 200
    code, body = _post(base, "/debug/driftz",
                       {"action": "load", "path": ref_path})
    assert code == 200
    with urllib.request.urlopen(f"{base}/debug/driftz", timeout=10) as r:
        snap = json.load(r)
    assert snap["reference"]["rows"] >= 48
    assert snap["window"]["rows"] >= 48
    code, _ = _post(base, "/debug/driftz", {"action": "bogus"})
    assert code == 400


def test_outcomes_endpoint_counts_and_rejects_malformed(drift_server):
    from igaming_platform_tpu.serve.scorer import ScoreRequest

    base = f"http://localhost:{drift_server.http_port}"
    resp = drift_server.engine.score(
        ScoreRequest(account_id="oc-1", amount=70_000,
                     tx_type="withdraw"))
    assert resp.decision_id
    # Known id: accepted, not unknown.
    code, body = _post(base, "/debug/outcomes", {"outcomes": [
        {"decision_id": resp.decision_id, "label": 1,
         "source": "chargeback"}]})
    assert code == 200
    assert body == {"accepted": 1, "unknown": 0, "submitted": 1}
    # Foreign id: still appended (at-least-once) but counted unknown —
    # the soak harness can now SEE a dropped backfill join.
    code, body = _post(base, "/debug/outcomes", {"outcomes": [
        {"decision_id": "d-ffffffffffffffff-0000001.0", "label": 0}]})
    assert code == 200
    assert body["accepted"] == 1 and body["unknown"] == 1
    # Malformed rows are a 400, never a silent 200.
    code, body = _post(base, "/debug/outcomes",
                       {"outcomes": [{"label": 1}]})
    assert code == 400 and "decision_id" in body["error"]
    code, _ = _post(base, "/debug/outcomes", {"outcomes": "nope"})
    assert code == 400
    req = urllib.request.Request(
        f"{base}/debug/outcomes", data=b"not json", method="POST")
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(req, timeout=10)
    assert exc_info.value.code == 400


def test_ledger_knows_decision_bounds():
    from igaming_platform_tpu.serve import ledger as ledger_mod

    ledger = drift_server_ledger = None  # noqa: F841 — readability
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ledger = ledger_mod.DecisionLedger(d)
        try:
            batch = ledger_mod._PendingBatch(
                prefix="d-aaaa-1", ts=0.0, n=3,
                score=np.zeros(3, np.int32), action=np.ones(3, np.int32),
                reason_mask=np.zeros(3, np.int32),
                rule_score=np.zeros(3, np.int32),
                ml_score=np.zeros(3, np.float32),
                x=None, bl=np.zeros(3, bool),
                account_ids=["a", "b", "c"], amounts=[1, 2, 3],
                tx_codes=["bet"] * 3,
                tier_codes=np.zeros(3, np.uint8),
                serving_state="serving", wire_mode="batch",
                model_version="mock", params_fp="0" * 16,
                block_threshold=80, review_threshold=50, trace_id="")
            assert ledger.append_columns(batch)
            assert ledger.knows_decision("d-aaaa-1.0")
            assert ledger.knows_decision("d-aaaa-1.2")
            assert not ledger.knows_decision("d-aaaa-1.3")  # beyond n
            assert not ledger.knows_decision("d-bbbb-9.0")
            assert not ledger.knows_decision("garbage")
        finally:
            ledger.close()
