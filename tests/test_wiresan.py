"""wiresan: both wire directions validated at the rpc boundary, unknown
fields counted (never raised — the additive-compat stance), violations
deterministic, the version mask faithful, and the v1-masked skew fleet
completing a real gRPC job clean (graftlint v8's runtime twin)."""

import os
from concurrent import futures

import grpc
import pytest

from elasticdl_tpu.common import gauge, wiresan
from elasticdl_tpu.common.rpc import (
    JsonRpcClient,
    MessageSchema,
    make_generic_handler,
)

_STR = (str,)
_INT = (int,)
_BOOL = (bool,)

PING_REQ = {
    "Ping": MessageSchema(
        required={"worker_id": _STR}, optional={"lease": _INT},
        since={"lease": 9},
    ),
}
PING_RESP = {
    "Ping": MessageSchema(
        required={"ok": _BOOL}, optional={"eta": _INT}, since={"eta": 12},
    ),
}


@pytest.fixture(autouse=True)
def _isolated():
    wiresan.reset()
    yield
    wiresan.reset()


# ---- check(): the violation grammar ----

def test_missing_required_raises_deterministically():
    msg = {"lease": 2}
    with pytest.raises(wiresan.WireSanViolation) as e1:
        wiresan.check("Ping", msg, PING_REQ, "request")
    # Same message, same violation, same text — a schema bug must repro,
    # not flake.
    with pytest.raises(wiresan.WireSanViolation) as e2:
        wiresan.check("Ping", msg, PING_REQ, "request")
    assert str(e1.value) == str(e2.value)
    assert "request Ping" in str(e1.value)
    assert "worker_id" in str(e1.value)
    assert wiresan.stats()["violations"] == 2


def test_wrong_type_raises_and_bool_is_not_int():
    with pytest.raises(wiresan.WireSanViolation):
        wiresan.check(
            "Ping", {"worker_id": "w", "lease": "4"}, PING_REQ, "request"
        )
    # bool subclasses int; {"lease": True} must not read as lease 1.
    with pytest.raises(wiresan.WireSanViolation):
        wiresan.check(
            "Ping", {"worker_id": "w", "lease": True}, PING_REQ, "request"
        )


def test_unknown_fields_counted_never_raised():
    wiresan.check(
        "Ping", {"worker_id": "w", "new_field": 1, "newer": 2},
        PING_REQ, "request",
    )
    wiresan.check("Ping", {"worker_id": "w", "new_field": 3}, PING_REQ,
                  "request")
    stats = wiresan.stats()
    assert stats["unknown_fields"] == {"Ping": 3}
    assert stats["violations"] == 0


def test_undeclared_method_and_absent_table_pass_unjudged():
    # The PS tier's binary frames and schema-less services: no contract
    # declared, nothing enforced.
    wiresan.check("PullParams", {"anything": object()}, PING_REQ, "request")
    wiresan.check("Ping", {"anything": 1}, None, "request")
    assert wiresan.stats()["unknown_fields"] == {}


def test_gauge_collector_exports_unknown_counts():
    wiresan.check("Ping", {"worker_id": "w", "x": 1}, PING_REQ, "request")
    reg = gauge.Registry()
    collector = gauge.install_wire_collector(reg)
    try:
        fam = reg.snapshot()["edl_wire_unknown_fields_total"]
        by_method = {
            s["labels"]["method"]: s["value"] for s in fam["samples"]
        }
        assert by_method == {"Ping": 1.0}
    finally:
        reg.remove_collector(collector)


# ---- the version mask ----

def test_mask_strips_newer_fields_both_shapes():
    masked = wiresan.mask(
        "Ping", {"worker_id": "w", "lease": 4}, PING_REQ, rev=1
    )
    assert masked == {"worker_id": "w"}
    resp = wiresan.mask("Ping", {"ok": True, "eta": 9}, PING_RESP, rev=1)
    assert resp == {"ok": True}
    # At or past the field's revision nothing strips.
    assert wiresan.mask(
        "Ping", {"ok": True, "eta": 9}, PING_RESP, rev=12
    ) == {"ok": True, "eta": 9}


def test_mask_identity_when_nothing_strips():
    # No copy on the fast path: the SAME object comes back.
    msg = {"worker_id": "w"}
    assert wiresan.mask("Ping", msg, PING_REQ, rev=1) is msg
    assert wiresan.mask("NoSchema", msg, PING_REQ, rev=1) is msg


def test_mask_requires_armed_sanitizer(monkeypatch):
    monkeypatch.setenv("GRAFT_WIRESAN", "0")
    # A mask with the sanitizer off would strip nothing and "pass" by
    # testing the current protocol — fail loud instead.
    with pytest.raises(wiresan.WireSanError):
        wiresan.set_mask(1)
    monkeypatch.setenv("GRAFT_WIRESAN_MASK", "1")
    with pytest.raises(wiresan.WireSanError):
        wiresan.mask_rev()


def test_set_mask_overrides_env(monkeypatch):
    monkeypatch.setenv("GRAFT_WIRESAN_MASK", "9")
    wiresan.set_mask(1)
    assert wiresan.mask_rev() == 1
    wiresan.set_mask(None)
    assert wiresan.mask_rev() == 9


# ---- both ends over real gRPC ----

def _serve(methods, schemas=None, response_schemas=None):
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server.add_generic_rpc_handlers((
        make_generic_handler(
            "test.WireSvc", methods, schemas=schemas,
            response_schemas=response_schemas,
        ),
    ))
    port = server.add_insecure_port("localhost:0")
    server.start()
    return server, f"localhost:{port}"


def test_server_side_response_validation():
    # The handler returns a response missing its required field: the
    # violation must surface in the SERVER's frame (the client sees a
    # remote error, not a silent malformed dict).
    server, addr = _serve(
        {"Ping": lambda req: {}},
        schemas=PING_REQ, response_schemas=PING_RESP,
    )
    try:
        client = JsonRpcClient(
            addr, service_name="test.WireSvc",
            schemas=PING_REQ, response_schemas={},
        )
        client.wait_ready(10.0)
        with pytest.raises(grpc.RpcError):
            client.call("Ping", {"worker_id": "w"}, timeout_s=10.0)
        assert wiresan.stats()["violations"] >= 1
    finally:
        server.stop(grace=0)


def test_client_side_response_validation_and_clean_path():
    server, addr = _serve(
        {"Ping": lambda req: {"ok": True, "eta": 3}},
        schemas=PING_REQ, response_schemas=PING_RESP,
    )
    try:
        good = JsonRpcClient(
            addr, service_name="test.WireSvc",
            schemas=PING_REQ, response_schemas=PING_RESP,
        )
        good.wait_ready(10.0)
        assert good.call(
            "Ping", {"worker_id": "w"}, timeout_s=10.0
        ) == {"ok": True, "eta": 3}
        # A client whose schema demands a field this server never sends:
        # the violation lands in the CALLER's frame, field named.
        strict = JsonRpcClient(
            addr, service_name="test.WireSvc",
            schemas=PING_REQ,
            response_schemas={
                "Ping": MessageSchema(required={"bogus": _INT}),
            },
        )
        with pytest.raises(wiresan.WireSanViolation, match="bogus"):
            strict.call("Ping", {"worker_id": "w"}, timeout_s=10.0)
    finally:
        server.stop(grace=0)


def test_client_masks_request_and_response():
    seen = {}

    def ping(req):
        seen.update(req)
        return {"ok": True, "eta": 3}

    server, addr = _serve(
        {"Ping": ping}, schemas=PING_REQ, response_schemas=PING_RESP,
    )
    try:
        client = JsonRpcClient(
            addr, service_name="test.WireSvc",
            schemas=PING_REQ, response_schemas=PING_RESP,
        )
        client.wait_ready(10.0)
        wiresan.set_mask(1)
        try:
            resp = client.call(
                "Ping", {"worker_id": "w", "lease": 4}, timeout_s=10.0
            )
        finally:
            wiresan.set_mask(None)
        assert "lease" not in seen          # request masked on the way out
        assert resp == {"ok": True}         # response masked on the way in
    finally:
        server.stop(grace=0)


def test_disabled_mode_is_identity(monkeypatch):
    # GRAFT_WIRESAN off: no validation, no counting, no masking — the
    # call path must behave exactly as before r22.
    monkeypatch.delenv("GRAFT_WIRESAN", raising=False)
    server, addr = _serve(
        {"Ping": lambda req: {}},  # malformed response
        schemas=PING_REQ, response_schemas=PING_RESP,
    )
    try:
        client = JsonRpcClient(
            addr, service_name="test.WireSvc",
            schemas=PING_REQ, response_schemas=PING_RESP,
        )
        client.wait_ready(10.0)
        assert client.call("Ping", {"worker_id": "w"}, timeout_s=10.0) == {}
        assert wiresan.stats()["violations"] == 0
        assert wiresan.stats()["unknown_fields"] == {}
    finally:
        server.stop(grace=0)


def test_version_skew_roundtrip_real_grpc():
    # The additive-compat proof: a v1-masked worker (no lease batching,
    # no seq ledger, no envelopes) completes a real gRPC job against a
    # current master — zero violations, zero double-trains.  The driver
    # ``python tools/wire_skew.py`` runs.
    from tools.wire_skew import run_skew

    assert os.environ.get("GRAFT_WIRESAN") == "1"  # conftest arms it
    verdict = run_skew(4, log=lambda m: None)
    assert verdict["ok"], verdict["errors"]
    assert verdict["tasks_done"] == 4
    assert verdict["wire_violations"] == 0
    # A masked (older) peer drops fields, it never invents them: nothing
    # the current master receives is outside its schema.
    assert verdict["wiresan"]["unknown_fields"] == {}
    assert verdict["job_status"]["duplicate_done"] == 0
    assert verdict["job_status"]["stale_reports"] == 0
    assert verdict["job_status"]["finished"] is True
