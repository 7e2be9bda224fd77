"""Hardening cells the individual suites don't pin:

- direct p2p upgrade over a TLS relay (TLS x direct matrix cell);
- an in-flight pipelined sweep invalidated by a hashgraph reset must not
  corrupt consensus or leak admission slots;
- the JSON-RPC socket proxy surviving garbage bytes and malformed
  requests from a client;
- the standalone signal-server CLI daemon serving a real RPC round trip.
"""

from __future__ import annotations

import json
import socket as socket_mod
import subprocess
import sys
import threading
import time

import pytest

from babble_tpu.crypto.keys import generate_key
from babble_tpu.net.rpc import SyncRequest, SyncResponse
from babble_tpu.net.signal import SignalServer, SignalTransport

from test_signal import _responder
from test_signal_direct import _wait_direct


def test_direct_upgrade_over_tls_relay(tmp_path):
    """Signaling over a TLS relay, then the upgrade: the direct link's own
    mutual auth is independent of the relay's TLS, so the combination
    must work and survive relay death."""
    import datetime
    import ipaddress

    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now)
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(
            x509.SubjectAlternativeName(
                [x509.IPAddress(ipaddress.ip_address("127.0.0.1"))]
            ),
            critical=False,
        )
        .sign(key, hashes.SHA256())
    )
    cert_file = str(tmp_path / "cert.pem")
    key_file = str(tmp_path / "key.pem")
    with open(cert_file, "wb") as f:
        f.write(cert.public_bytes(serialization.Encoding.PEM))
    with open(key_file, "wb") as f:
        f.write(
            key.private_bytes(
                serialization.Encoding.PEM,
                serialization.PrivateFormat.PKCS8,
                serialization.NoEncryption(),
            )
        )

    srv = SignalServer("127.0.0.1:0", cert_file=cert_file, key_file=key_file)
    srv.listen()
    ka, kb = generate_key(), generate_key()
    # 40 s RPC budget: under a full-suite run on this single core the TLS
    # handshakes + responder threads can stall for tens of seconds
    ta = SignalTransport(srv.addr(), ka, timeout=40.0, ca_file=cert_file,
                         direct_listen="127.0.0.1:0")
    tb = SignalTransport(srv.addr(), kb, timeout=40.0, ca_file=cert_file,
                         direct_listen="127.0.0.1:0")
    ta.listen()
    tb.listen()
    stop = threading.Event()
    _responder(tb, stop)
    try:
        resp = ta.sync(kb.public_key.hex(), SyncRequest(1, {}, 100))
        assert isinstance(resp, SyncResponse)
        # generous window: this single-core host can stall threads for
        # seconds when a bench or compile runs concurrently
        assert _wait_direct(ta, kb.public_key.hex(), timeout=30.0)
        srv.close()
        time.sleep(0.2)
        resp = ta.sync(kb.public_key.hex(), SyncRequest(2, {}, 100))
        assert isinstance(resp, SyncResponse)
    finally:
        stop.set()
        ta.close()
        tb.close()
        srv.close()


@pytest.mark.parametrize("batcher", [False, True])
def test_reset_invalidates_inflight_sweep_without_corruption(batcher):
    """A fast-sync style reset while a pipelined sweep is in flight: the
    stale sweep must be dropped (generation bump), its admission slot
    reclaimed (direct launch) or its ticket's result left unapplied (the
    batcher, the lane a chip resolves), and subsequent consensus must
    match the oracle exactly."""
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
    from babble_tpu.hashgraph.accel import TensorConsensus
    from test_accel import BUILDERS, _consensus_state, _ordered_events, \
        _replay

    h0, index, nodes, peer_set = BUILDERS["consensus"]()
    ordered = _ordered_events(h0)
    oracle = _replay(ordered, peer_set)

    h = Hashgraph(InmemStore(1000))
    h.init(peer_set)
    acc = TensorConsensus(sweep_events=10**9, async_compile=False,
                          min_window=0, pipeline=True, batcher=batcher)
    h.accel = acc
    half = len(ordered) // 2
    for ev in ordered[:half]:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    h.flush_consensus()  # launches a pipelined sweep (maybe in flight)
    gen_before = acc.generation
    acc.invalidate()  # what Reset()/fast-sync does mid-flight
    assert acc.generation == gen_before + 1
    for ev in ordered[half:]:
        e = Event(ev.body, ev.signature)
        e.prevalidate(True)
        h.insert_event_and_run_consensus(e, set_wire_info=True)
    # drain the pipeline to quiescence
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        h.flush_consensus()
        if not acc.busy() and not h.undetermined_events == []:
            # keep flushing until decisions stop arriving
            pass
        if h.store.last_block_index() >= oracle.store.last_block_index():
            break
        time.sleep(0.02)
    h.process_sig_pool()
    assert _consensus_state(h) == _consensus_state(oracle)
    # the invalidated sweep must not have wedged admission for later ones
    assert acc.sweeps > 0 or acc.small_windows > 0


def test_socket_proxy_survives_garbage_clients():
    """The Babble-side JSON-RPC server must survive raw garbage, a bad
    JSON body, and an unknown method — and still serve a real SubmitTx
    afterwards (reference posture: socket proxies never crash the node)."""
    from babble_tpu.proxy.socket_proxy import SocketAppProxy

    proxy = SocketAppProxy("127.0.0.1:27210", "127.0.0.1:27211")
    time.sleep(0.1)

    import struct

    def raw(data: bytes) -> bytes:
        """Send raw bytes; return one length-prefixed reply (or b'')."""
        s = socket_mod.create_connection(("127.0.0.1", 27210), timeout=5.0)
        try:
            s.sendall(data)
            s.settimeout(1.0)
            try:
                hdr = s.recv(4)
                if len(hdr) < 4:
                    return b""
                (length,) = struct.unpack(">I", hdr)
                buf = b""
                while len(buf) < length:
                    chunk = s.recv(length - len(buf))
                    if not chunk:
                        return b""
                    buf += chunk
                return buf
            except (socket_mod.timeout, ConnectionError):
                # an abrupt close on garbage is acceptable server behavior;
                # what matters is that the NEXT client still gets served
                return b""
        finally:
            s.close()

    def frame(obj) -> bytes:
        payload = json.dumps(obj).encode()
        return struct.pack(">I", len(payload)) + payload

    # raw garbage (bogus length prefix + junk)
    raw(b"\x00\xffnot json at all\n")
    # correct framing, undecodable JSON body
    raw(struct.pack(">I", 9) + b"not-json!")
    # correct framing, JSON but not an object
    raw(frame(42))
    # valid JSON object, unknown method -> typed error reply
    out = raw(frame({"method": "Nope.Nothing", "params": [], "id": 1}))
    assert out and b"no method" in out
    # malformed params for SubmitTx -> error reply, not a crash
    out2 = raw(frame({"method": "Babble.SubmitTx", "params": [1, 2, 3],
                      "id": 2}))
    assert out2 and json.loads(out2).get("error")
    # the server is still alive: a REAL SubmitTx round-trips
    import base64

    out3 = raw(frame({
        "method": "Babble.SubmitTx",
        "params": [base64.b64encode(b"tx after garbage").decode()],
        "id": 3,
    }))
    assert out3, "no response to a valid SubmitTx after garbage"
    resp = json.loads(out3)
    assert resp.get("error") is None and resp.get("result") is True
    proxy.close()


def test_signal_cli_daemon_round_trip(tmp_path):
    """`babble-tpu signal` (the cmd/signal analogue) as a real subprocess:
    clients register through it and complete an RPC round trip; SIGTERM
    shuts it down cleanly."""
    import re
    import signal as sig_mod

    proc = subprocess.Popen(
        [sys.executable, "-m", "babble_tpu.cli", "signal",
         "--listen", "127.0.0.1:0"],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd="/root/repo",
    )
    try:
        line = proc.stdout.readline()
        m = re.search(r"listening on ([0-9.]+:\d+)", line)
        assert m, f"no listen line: {line!r}"
        addr = m.group(1)
        ka, kb = generate_key(), generate_key()
        ta = SignalTransport(addr, ka, timeout=20.0)
        tb = SignalTransport(addr, kb, timeout=20.0)
        ta.listen()
        tb.listen()
        stop = threading.Event()
        _responder(tb, stop)
        try:
            resp = ta.sync(kb.public_key.hex(), SyncRequest(1, {}, 100))
            assert isinstance(resp, SyncResponse)
        finally:
            stop.set()
            ta.close()
            tb.close()
        proc.send_signal(sig_mod.SIGTERM)
        assert proc.wait(timeout=10.0) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
