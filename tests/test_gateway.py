"""Attestation gateway: admission, coalescing, transport, batch verify.

Covers the serving tier (repro/gateway/*) plus the API additions that
back it (``ProofService.attest_many``, ``api.verify_batch``, the
StreamingVerifier flood caps):

* admission-queue units — bounded depth, per-client limits, reasoned
  rejections, FIFO-prefix window formation (no crypto, fast);
* the acceptance bar — >=4 concurrent clients through the gateway, every
  attestation verifies AND is byte-identical to its serial
  ``ProofService.attest`` twin, on BOTH kernel paths;
* backpressure observable on the wire (a real REJ message);
* batch verify equivalence and flood hardening.

Crypto-bearing fixtures are module-scoped (one service, serial twins
proven once) to keep the proving budget bounded.
"""
import contextlib
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from repro import api
from repro.core import blocks as B
from repro.gateway import (REJECT_BAD_REQUEST, REJECT_CLIENT_LIMIT,
                           REJECT_QUEUE_FULL, REJECT_SHUTDOWN, AdmissionQueue,
                           AdmissionRejected, AttestationGateway, ClientQuota,
                           GatewayClient, GatewayConfig, GatewayError, Ticket)
from repro.gateway.transport import GatewayServer  # noqa: F401 (api check)

CFG = B.BlockCfg(family="gpt2", d=16, dff=32, heads=2, kv_heads=2, dh=8,
                 seq=8)
L = 2
QUERIES = 2
N_CLIENTS = 4


@contextlib.contextmanager
def kernel_path(path):
    old = os.environ.get("NANOZK_KERNEL_PATH")
    os.environ["NANOZK_KERNEL_PATH"] = path
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("NANOZK_KERNEL_PATH", None)
        else:
            os.environ["NANOZK_KERNEL_PATH"] = old


def _canonical_bytes(att):
    """v2 wire bytes with the telemetry float normalized out."""
    att.prove_seconds = 0.0
    att.__dict__.pop("_wire_cache", None)
    return att.to_bytes(2)


@pytest.fixture(scope="module")
def service():
    rng = np.random.default_rng(11)
    weights = [B.init_weights(CFG, rng) for _ in range(L)]
    with api.ProofService([CFG] * L, weights, default_queries=QUERIES,
                          workers=2, name="gw-model") as svc:
        yield svc


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(12)
    return [np.clip(np.round(rng.normal(0, 0.5,
                                        (CFG.d_pad, CFG.seq)) * 256),
                    -32768, 32767).astype(np.int64) for _ in range(2)]


@pytest.fixture(scope="module")
def policy():
    return api.VerifyPolicy(pcs_queries=QUERIES)


@pytest.fixture(scope="module")
def serial_twins(service, queries, policy):
    """{kernel path -> [canonical bytes per query]} from plain attest."""
    out = {}
    for path in ("ref", "fused"):
        with kernel_path(path):
            out[path] = [_canonical_bytes(service.attest(q, policy))
                         for q in queries]
    # parity guard: the twins themselves must agree across paths
    assert out["ref"] == out["fused"]
    return out


# ---------------------------------------------------------------------------
# Admission queue units (no crypto).
# ---------------------------------------------------------------------------
def _ticket(client="c", pcs=QUERIES):
    return Ticket(client_id=client, query=np.zeros((2, 2), np.int64),
                  policy=api.VerifyPolicy(pcs_queries=pcs))


class TestAdmission:
    def test_queue_full_is_reasoned(self):
        q = AdmissionQueue(max_depth=2,
                           quota=ClientQuota(max_inflight=8))
        q.submit(_ticket("a"))
        q.submit(_ticket("b"))
        with pytest.raises(AdmissionRejected) as ei:
            q.submit(_ticket("c"))
        assert ei.value.reason == REJECT_QUEUE_FULL
        assert "retry" in ei.value.detail

    def test_per_client_inflight_limit(self):
        q = AdmissionQueue(max_depth=16, quota=ClientQuota(max_inflight=2))
        t1, t2 = _ticket("a"), _ticket("a")
        q.submit(t1)
        q.submit(t2)
        with pytest.raises(AdmissionRejected) as ei:
            q.submit(_ticket("a"))
        assert ei.value.reason == REJECT_CLIENT_LIMIT
        q.submit(_ticket("b"))             # other clients unaffected
        q.task_done(t1)                    # slot released on completion
        q.submit(_ticket("a"))

    def test_quota_override_per_client(self):
        q = AdmissionQueue(max_depth=16, quota=ClientQuota(max_inflight=1),
                           quotas={"vip": ClientQuota(max_inflight=3)})
        q.submit(_ticket("vip"))
        q.submit(_ticket("vip"))
        q.submit(_ticket("anon"))
        with pytest.raises(AdmissionRejected):
            q.submit(_ticket("anon"))

    def test_pcs_queries_cap(self):
        q = AdmissionQueue(quota=ClientQuota(max_pcs_queries=8))
        with pytest.raises(AdmissionRejected) as ei:
            q.submit(_ticket(pcs=64))
        assert ei.value.reason == REJECT_BAD_REQUEST

    def test_closed_queue_rejects_shutdown(self):
        q = AdmissionQueue()
        q.close()
        with pytest.raises(AdmissionRejected) as ei:
            q.submit(_ticket())
        assert ei.value.reason == REJECT_SHUTDOWN

    def test_take_window_coalesces_fifo_prefix(self):
        q = AdmissionQueue(max_depth=16, quota=ClientQuota(max_inflight=16))
        a, b = _ticket("a", pcs=2), _ticket("a", pcs=2)
        odd = _ticket("a", pcs=4)          # incompatible PCS shape
        c = _ticket("a", pcs=2)            # compatible but behind `odd`
        for t in (a, b, odd, c):
            q.submit(t)
        w1 = q.take_window(max_batch=4, window_seconds=0.01)
        assert w1 == [a, b]                # stops at the first mismatch
        w2 = q.take_window(max_batch=4, window_seconds=0.01)
        assert w2 == [odd]                 # arrival order preserved
        w3 = q.take_window(max_batch=4, window_seconds=0.01)
        assert w3 == [c]

    def test_take_window_respects_max_batch(self):
        q = AdmissionQueue(max_depth=16, quota=ClientQuota(max_inflight=16))
        ts = [_ticket() for _ in range(3)]
        for t in ts:
            q.submit(t)
        assert q.take_window(max_batch=2, window_seconds=0.01) == ts[:2]
        assert q.take_window(max_batch=2, window_seconds=0.01) == ts[2:]

    def test_take_window_empty_times_out(self):
        q = AdmissionQueue()
        assert q.take_window(4, 0.01, poll_timeout=0.01) == []

    def test_ticket_result_timeout(self):
        with pytest.raises(GatewayError):
            _ticket().result(timeout=0.01)

    def test_rejection_str_carries_reason(self):
        assert str(AdmissionRejected("queue_full", "q at 32/32")) == \
            "[queue_full] q at 32/32"


# ---------------------------------------------------------------------------
# Gateway lifecycle (no crypto).
# ---------------------------------------------------------------------------
class TestGatewayLifecycle:
    def test_submit_after_close_rejected(self, service):
        gw = AttestationGateway(service)
        gw.start()
        gw.close()
        with pytest.raises(AdmissionRejected) as ei:
            gw.submit(np.zeros((CFG.d_pad, CFG.seq), np.int64))
        assert ei.value.reason == REJECT_SHUTDOWN

    def test_close_without_drain_rejects_queued(self, service, queries,
                                                policy):
        gw = AttestationGateway(service)   # dispatcher NOT started
        t1 = gw.submit(queries[0], policy)
        t2 = gw.submit(queries[1], policy)
        gw.close(drain=False)
        for t in (t1, t2):
            with pytest.raises(AdmissionRejected) as ei:
                t.result(timeout=1)
            assert ei.value.reason == REJECT_SHUTDOWN

    def test_metrics_snapshot_is_json(self, service):
        gw = AttestationGateway(service)
        with pytest.raises(AdmissionRejected):
            gw.submit(np.zeros((CFG.d_pad, CFG.seq), np.int64),
                      policy=api.VerifyPolicy(pcs_queries=10**6))
        snap = gw.metrics_snapshot()
        json.dumps(snap)                   # must be JSON-serializable
        assert snap["rejected"][REJECT_BAD_REQUEST] == 1
        assert snap["rejected_total"] == 1
        gw.close(drain=False)


# ---------------------------------------------------------------------------
# The acceptance bar: >=4 concurrent clients, byte-identical to serial,
# both kernel paths.  In-process gateway here; the socket path below.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path", ["ref", "fused"])
def test_gateway_concurrent_byte_identical(service, queries, policy,
                                           serial_twins, path):
    with kernel_path(path):
        cfgws = GatewayConfig(max_batch=N_CLIENTS, window_seconds=0.3)
        with AttestationGateway(service, cfgws) as gw:
            results = {}

            def client(i):
                att = gw.attest(queries[i % 2], policy,
                                client_id=f"c{i}", timeout=600)
                results[i] = _canonical_bytes(att)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(N_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = gw.metrics_snapshot()
    assert len(results) == N_CLIENTS
    for i, wire in results.items():
        assert wire == serial_twins[path][i % 2], \
            f"gateway attestation {i} ({path}) diverged from serial twin"
    assert snap["completed"] == N_CLIENTS
    # the window had every query available: commits were coalesced
    assert snap["coalesce"]["coalesced_queries"] >= 2


def test_gateway_socket_concurrent_clients(service, queries, policy,
                                           serial_twins):
    """>=4 concurrent clients over the REAL socket transport: each one
    stream-verifies its attestation as frames arrive, and the raw wire is
    byte-identical to the serial twin."""
    card = service.model_card
    with kernel_path("ref"):
        cfgws = GatewayConfig(max_batch=N_CLIENTS, window_seconds=0.3)
        with AttestationGateway(service, cfgws) as gw:
            srv = gw.serve(port=0)
            host, port = srv.address
            reports, wires, errors = {}, {}, []

            def client(i):
                try:
                    with GatewayClient(host, port,
                                       client_id=f"sock-{i}") as cli:
                        wires[i], info = cli.attest_bytes(queries[i % 2],
                                                          policy)
                        assert info["batch_size"] >= 1
                    with GatewayClient(host, port,
                                       client_id=f"sock-{i}") as cli:
                        reports[i] = cli.attest_verify(
                            queries[i % 2], card, policy)
                except BaseException as e:  # noqa: BLE001 — surface in main thread
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(N_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            snap = gw.metrics_snapshot()
        assert srv.connections_served >= 2 * N_CLIENTS
    for i in range(N_CLIENTS):
        assert reports[i].ok, reports[i].reason
        att = api.Attestation.from_bytes(wires[i])
        assert _canonical_bytes(att) == serial_twins["ref"][i % 2]
    assert snap["completed"] == 2 * N_CLIENTS
    json.dumps(snap)


def test_backpressure_on_the_wire(service, queries, policy):
    """A real REJ message with the queue_full reason code, while the
    queue is held at capacity by an in-flight + a queued proof."""
    cfgws = GatewayConfig(max_queue_depth=1, max_batch=1,
                          window_seconds=0.02)
    with AttestationGateway(service, cfgws) as gw:
        srv = gw.serve(port=0)
        host, port = srv.address
        with GatewayClient(host, port, client_id="g1") as c1, \
                GatewayClient(host, port, client_id="g2") as c2:
            c1._request(queries[0], policy, None)   # -> proving window
            _wait_for(lambda: len(gw.admission) == 0)
            c2._request(queries[1], policy, None)   # queued: depth 1/1
            _wait_for(lambda: len(gw.admission) == 1)
            with GatewayClient(host, port, client_id="late") as c3:
                with pytest.raises(AdmissionRejected) as ei:
                    c3.attest_bytes(queries[0], policy)
            assert ei.value.reason == REJECT_QUEUE_FULL
            c1._stream_response(lambda b: None)     # drain both proofs
            c2._stream_response(lambda b: None)
    snap = gw.metrics_snapshot()
    assert snap["rejected"][REJECT_QUEUE_FULL] == 1


def _wait_for(cond, timeout=10.0):
    import time
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.01)


def test_socket_rejects_malformed_request(service):
    with AttestationGateway(service) as gw:
        srv = gw.serve(port=0)
        host, port = srv.address
        import socket as socketlib

        from repro.gateway import transport as T
        with socketlib.create_connection((host, port), timeout=10) as s:
            T.send_msg(s, T.MSG_QUERY, b"\x00garbage")
            mtype, body = T.recv_msg(s, 1 << 20)
            assert mtype == T.MSG_REJECT
        with socketlib.create_connection((host, port), timeout=10) as s:
            T.send_msg(s, b"WAT?", b"")
            mtype, body = T.recv_msg(s, 1 << 20)
            assert mtype == T.MSG_REJECT
        # oversized request body: rejected BEFORE the body is read
        with socketlib.create_connection((host, port), timeout=10) as s:
            s.sendall(T.MSG_QUERY + (1 << 30).to_bytes(4, "big"))
            mtype, body = T.recv_msg(s, 1 << 20)
            assert mtype == T.MSG_REJECT
    snap = gw.metrics_snapshot()
    assert snap["completed"] == 0


# ---------------------------------------------------------------------------
# Concurrent direct ProofService use (no gateway in between).
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path", ["ref", "fused"])
def test_proofservice_concurrent_attest(service, queries, policy,
                                        serial_twins, path):
    """N threads attesting against the SHARED service/WeightCommitCache:
    every result byte-identical to its serial twin (the concurrent-prove
    hazards — round-batcher clobbering, pool double-init — stay fixed)."""
    with kernel_path(path):
        results, errors = {}, []

        def worker(i):
            try:
                att = service.attest(queries[i % 2], policy)
                results[i] = _canonical_bytes(att)
            except BaseException as e:  # noqa: BLE001 — surface in main thread
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors, errors
    for i, wire in results.items():
        assert wire == serial_twins[path][i % 2]


def test_attest_many_matches_serial(service, queries, policy, serial_twins):
    with kernel_path("ref"):
        atts = service.attest_many(queries, [policy, policy])
        report = service.last_report
    assert report.batch_size == 2
    assert report.commit_seconds >= 0     # the ONE shared commit pass
    for att, twin in zip(atts, serial_twins["ref"]):
        assert _canonical_bytes(att) == twin


def test_attest_many_rejects_mixed_pcs_shapes(service, queries):
    with pytest.raises(AssertionError):
        service.attest_many(queries, [api.VerifyPolicy(pcs_queries=2),
                                      api.VerifyPolicy(pcs_queries=4)])


# ---------------------------------------------------------------------------
# Batch verify.
# ---------------------------------------------------------------------------
def test_verify_batch_matches_individual(service, queries, policy,
                                         serial_twins):
    card = service.model_card
    wires = [serial_twins["ref"][0], serial_twins["ref"][1]]
    batch = api.verify_batch(wires, queries, card, policies=policy)
    assert all(r.ok for r in batch), [r.reason for r in batch]
    for wire, q, rep in zip(wires, queries, batch):
        solo = api.verify(wire, q, card, policy=policy)
        assert solo.ok == rep.ok
        assert solo.reason == rep.reason


def test_verify_batch_isolates_bad_items(service, queries, policy,
                                         serial_twins):
    card = service.model_card
    bad = bytearray(serial_twins["ref"][0])
    bad[-50] ^= 0x04
    batch = api.verify_batch([bytes(bad), serial_twins["ref"][1]],
                             queries, card, policies=policy)
    assert not batch[0].ok and batch[0].reason
    assert batch[1].ok, batch[1].reason


def test_verify_batch_bad_card_rejects_all(queries, serial_twins):
    batch = api.verify_batch(serial_twins["ref"], queries, b"not-a-card")
    assert len(batch) == 2
    assert all(not r.ok for r in batch)
    assert all("card" in r.reason for r in batch)


# ---------------------------------------------------------------------------
# StreamingVerifier flood hardening.
# ---------------------------------------------------------------------------
def test_streaming_rejects_zero_progress_flood(service, queries, policy,
                                               serial_twins):
    card = service.model_card
    sv = api.StreamingVerifier(queries[0], card, policy=policy,
                               max_stalled_feeds=4)
    sv.feed(serial_twins["ref"][0][:64])
    reports = []
    for _ in range(6):
        reports += sv.feed(b"")
        if reports:
            break
    assert reports and not reports[0].ok
    assert "zero-progress" in reports[0].reason


def test_streaming_rejects_buffered_bytes_flood(service, queries, policy,
                                                serial_twins):
    card = service.model_card
    wire = serial_twins["ref"][0]
    sv = api.StreamingVerifier(queries[0], card, policy=policy,
                               max_buffered_bytes=256)
    reports = []
    # drip the wire in; a frame larger than the cap must trip the limit
    for off in range(0, len(wire), 128):
        reports += sv.feed(wire[off:off + 128])
        if any(not r.ok for r in reports):
            break
    rej = [r for r in reports if not r.ok]
    assert rej, "buffered-bytes cap never tripped"
    assert "buffered" in rej[0].reason


def test_streaming_default_caps_accept_normal_stream(service, queries,
                                                     policy, serial_twins):
    card = service.model_card
    wire = serial_twins["ref"][0]
    sv = api.StreamingVerifier(queries[0], card, policy=policy)
    for off in range(0, len(wire), 1024):
        for rep in sv.feed(wire[off:off + 1024]):
            assert rep.ok, rep.reason
    assert sv.finish().ok


def test_chip_smoke_phases_at_toy_width(monkeypatch):
    """CPU rehearsal of ``chip_smoke.py``: its phases (gateway serving with
    stream verification, alongside it the kernel-path attestation, then
    the ``ref`` byte-identity check) at this file's toy width, on the
    kernel path a TPU defaults to; only the TPU device check is skipped."""
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setenv("NANOZK_KERNEL_PATH", "fused")
    lines = []
    out = smoke.run_phases(CFG, CFG, n_queries=2, pcs_queries=QUERIES,
                           log=lines.append)
    assert out["serve"] == {"kernel_path": "fused", "verified": 2}
    assert out["oracle"]["kernel_path"] == "fused"
    assert out["oracle"]["identical_bytes"] > 0
    assert any("byte-identical" in line for line in lines)
    assert os.environ["NANOZK_KERNEL_PATH"] == "fused"
