"""Server reflection v1alpha over a real socket — the grpcurl discovery
path the reference enables (risk/cmd/main.go:150, wallet/cmd/main.go:154).
"""

from concurrent import futures

import grpc
import pytest

from igaming_platform_tpu.proto_gen.grpc.reflection.v1alpha import reflection_pb2
from igaming_platform_tpu.serve.reflection import SERVICE_NAME, reflection_handler

# Imported for their descriptor-pool registration side effect (the
# underscore alias marks a deliberate side-effect import for the analyzer).
from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2 as _risk_pb2  # noqa: F401
from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2 as _wallet_pb2  # noqa: F401


@pytest.fixture(scope="module")
def reflect():
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server.add_generic_rpc_handlers((
        reflection_handler(("risk.v1.RiskService", "grpc.health.v1.Health")),
    ))
    port = server.add_insecure_port("localhost:0")
    server.start()
    channel = grpc.insecure_channel(f"localhost:{port}")
    call = channel.stream_stream(
        f"/{SERVICE_NAME}/ServerReflectionInfo",
        request_serializer=reflection_pb2.ServerReflectionRequest.SerializeToString,
        response_deserializer=reflection_pb2.ServerReflectionResponse.FromString,
    )

    def ask(**kwargs):
        responses = list(call(iter([
            reflection_pb2.ServerReflectionRequest(host="h", **kwargs)])))
        assert len(responses) == 1
        return responses[0]

    yield ask
    channel.close()
    server.stop(0).wait()


def test_list_services(reflect):
    resp = reflect(list_services="")
    names = {s.name for s in resp.list_services_response.service}
    assert "risk.v1.RiskService" in names
    assert "grpc.health.v1.Health" in names
    assert SERVICE_NAME in names  # reflection lists itself, like grpc-go
    assert resp.original_request.list_services == ""


def test_file_containing_symbol_returns_dependency_closure(reflect):
    from google.protobuf import descriptor_pb2

    resp = reflect(file_containing_symbol="risk.v1.RiskService")
    blobs = resp.file_descriptor_response.file_descriptor_proto
    files = [descriptor_pb2.FileDescriptorProto.FromString(b) for b in blobs]
    names = {f.name for f in files}
    # risk.proto imports timestamp.proto — grpcurl needs BOTH to decode.
    assert "risk/v1/risk.proto" in names
    assert "google/protobuf/timestamp.proto" in names
    risk_fd = next(f for f in files if f.name == "risk/v1/risk.proto")
    assert any(s.name == "RiskService" for s in risk_fd.service)


def test_method_and_message_symbols_resolve(reflect):
    for symbol in ("risk.v1.RiskService.ScoreTransaction",
                   "wallet.v1.WalletService",
                   "risk.v1.ScoreTransactionRequest"):
        resp = reflect(file_containing_symbol=symbol)
        assert resp.WhichOneof("message_response") == "file_descriptor_response", symbol
        assert resp.file_descriptor_response.file_descriptor_proto


def test_file_by_filename(reflect):
    resp = reflect(file_by_filename="wallet/v1/wallet.proto")
    assert resp.WhichOneof("message_response") == "file_descriptor_response"


def test_unknown_symbol_is_not_found_not_an_rpc_error(reflect):
    resp = reflect(file_containing_symbol="no.such.Service")
    assert resp.WhichOneof("message_response") == "error_response"
    assert resp.error_response.error_code == 5  # NOT_FOUND


def test_empty_request_is_unimplemented(reflect):
    resp = reflect()
    assert resp.error_response.error_code == 12


def test_bogus_leaf_under_known_parent_is_not_found(reflect):
    """A nonexistent method/field under a real service/message must be
    NOT_FOUND — the parent walk-up may not vouch for children it doesn't
    have."""
    for symbol in ("risk.v1.RiskService.NoSuchMethod",
                   "risk.v1.ScoreTransactionRequest.no_such_field",
                   "risk.v1.NoSuchMessage.whatever"):
        resp = reflect(file_containing_symbol=symbol)
        assert resp.WhichOneof("message_response") == "error_response", symbol
        assert resp.error_response.error_code == 5  # NOT_FOUND


def test_enum_value_symbol_resolves(reflect):
    """Enum-value leaves (e.g. grpcurl describing risk.v1.Action.ACTION_ALLOW)
    must resolve via their enum parent."""
    resp = reflect(file_containing_symbol="risk.v1.Action.ACTION_APPROVE")
    assert resp.WhichOneof("message_response") == "file_descriptor_response"
    resp = reflect(file_containing_symbol="risk.v1.Action.NO_SUCH_VALUE")
    assert resp.WhichOneof("message_response") == "error_response"
