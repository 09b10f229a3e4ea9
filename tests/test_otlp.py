"""OTLP/HTTP span export: envelope shape, drain semantics, failure drop."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from igaming_platform_tpu.obs.otlp import OtlpExporter, encode_spans, exporter_from_env
from igaming_platform_tpu.obs.tracing import SpanCollector, span


class _FakeCollector:
    def __init__(self, status=200):
        self.requests: list[dict] = []
        self.status = status
        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                size = int(self.headers.get("Content-Length", 0))
                fake.requests.append({
                    "path": self.path,
                    "content_type": self.headers.get("Content-Type"),
                    "body": json.loads(self.rfile.read(size)),
                })
                self.send_response(fake.status)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def close(self):
        self.httpd.shutdown()


def test_flush_exports_otlp_json_and_drains():
    fake = _FakeCollector()
    collector = SpanCollector()
    try:
        with span("score.decode", collector=collector, batch=8192):
            pass
        with span("score.dispatch", collector=collector):
            pass
        exp = OtlpExporter(fake.url, "risk", collector=collector)
        assert exp.flush() == 2
        assert exp.flush() == 0  # drained

        req = fake.requests[0]
        assert req["path"] == "/v1/traces"
        assert req["content_type"] == "application/json"
        rs = req["body"]["resourceSpans"][0]
        svc = rs["resource"]["attributes"][0]
        assert svc["key"] == "service.name"
        assert svc["value"]["stringValue"] == "risk"
        spans = rs["scopeSpans"][0]["spans"]
        assert {s["name"] for s in spans} == {"score.decode", "score.dispatch"}
        s0 = next(s for s in spans if s["name"] == "score.decode")
        assert len(s0["traceId"]) == 32 and len(s0["spanId"]) == 16
        assert int(s0["endTimeUnixNano"]) >= int(s0["startTimeUnixNano"])
        assert s0["attributes"] == [{"key": "batch", "value": {"intValue": "8192"}}]
    finally:
        fake.close()


def test_background_exporter_flushes_periodically():
    fake = _FakeCollector()
    collector = SpanCollector()
    exp = OtlpExporter(fake.url, "wallet", collector=collector, interval_s=0.05)
    exp.start()
    try:
        with span("rpc.Deposit", collector=collector):
            pass
        deadline = time.monotonic() + 60.0
        while exp.exported_total < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert exp.exported_total == 1
    finally:
        exp.stop()
        fake.close()


def test_export_failure_drops_batch_not_process():
    fake = _FakeCollector(status=503)
    collector = SpanCollector()
    try:
        with span("s", collector=collector):
            pass
        exp = OtlpExporter(fake.url, "risk", collector=collector)
        assert exp.flush() == 0
        assert exp.failed_batches == 1
        # Spans were dropped, not re-buffered.
        assert exp.flush() == 0 and len(fake.requests) == 1
    finally:
        fake.close()


def test_exporter_from_env(monkeypatch):
    monkeypatch.delenv("OTEL_EXPORTER_OTLP_ENDPOINT", raising=False)
    assert exporter_from_env("risk") is None
    fake = _FakeCollector()
    try:
        monkeypatch.setenv("OTEL_EXPORTER_OTLP_ENDPOINT", fake.url)
        exp = exporter_from_env("risk")
        assert exp is not None
        exp.stop()
    finally:
        fake.close()


def test_encode_attribute_types():
    from igaming_platform_tpu.obs.tracing import Span

    s = Span(name="x", start=1.0, end=2.0, trace_id="abc",
             attributes={"i": 3, "f": 1.5, "b": True, "s": "txt"})
    enc = encode_spans([s], "svc")["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
    by_key = {a["key"]: a["value"] for a in enc["attributes"]}
    assert by_key["i"] == {"intValue": "3"}
    assert by_key["f"] == {"doubleValue": 1.5}
    assert by_key["b"] == {"boolValue": True}
    assert by_key["s"] == {"stringValue": "txt"}
