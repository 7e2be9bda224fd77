"""Table-driven transport suite: every RPC pair over the inmem, TCP,
relay, and relay-with-direct-upgrade transports (reference:
/root/reference/src/net/transport_test.go:91-520), plus a full-node
gossip run over localhost TCP (node_test.go tier 4)."""

from __future__ import annotations

import threading
import time

import pytest

from babble_tpu.config.config import Config
from babble_tpu.crypto.keys import generate_key
from babble_tpu.dummy.state import State as DummyState
from babble_tpu.hashgraph.event import WireBody, WireEvent
from babble_tpu.hashgraph.internal_transaction import InternalTransaction
from babble_tpu.hashgraph.store import InmemStore
from babble_tpu.net.inmem import InmemNetwork
from babble_tpu.net.rpc import (
    EagerSyncRequest,
    EagerSyncResponse,
    FastForwardRequest,
    FastForwardResponse,
    JoinRequest,
    JoinResponse,
    SyncRequest,
    SyncResponse,
)
from babble_tpu.net.tcp import TCPTransport
from babble_tpu.net.transport import TransportError
from babble_tpu.node.node import Node
from babble_tpu.node.validator import Validator
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet
from babble_tpu.proxy.proxy import InmemProxy


def _wire_event() -> WireEvent:
    return WireEvent(
        body=WireBody(
            transactions=[b"t1", b"t2"],
            creator_id=7,
            other_parent_creator_id=3,
            index=4,
            self_parent_index=3,
            other_parent_index=2,
            timestamp=99,
        ),
        signature="abc|def",
    )


def _responder(trans, responses: dict, stop: threading.Event):
    """Serve canned responses keyed by request class name."""

    def run():
        while not stop.is_set():
            try:
                rpc = trans.consumer().get(timeout=0.1)
            except Exception:
                continue
            key = type(rpc.command).__name__
            resp = responses.get(key)
            if isinstance(resp, str):
                rpc.respond(None, resp)
            else:
                rpc.respond(resp, None)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _make_pair(kind):
    """Returns (client, server, cleanup)."""
    if kind == "inmem":
        net = InmemNetwork()
        a = net.new_transport("inmem://a")
        b = net.new_transport("inmem://b")
        return a, b, lambda: (a.close(), b.close())
    if kind in ("signal", "signal-direct"):
        # relay-routed pair: both sides dial OUT to a rendezvous server
        # and are addressed by public key (the WebRTC analogue).
        # "signal-direct" additionally enables the p2p upgrade, so after
        # the first RPC the suite's traffic rides the direct links.
        from babble_tpu.crypto.keys import generate_key
        from babble_tpu.net.signal import SignalServer, SignalTransport

        direct = "127.0.0.1:0" if kind == "signal-direct" else None
        relay = SignalServer("127.0.0.1:0")
        relay.listen()
        ka, kb = generate_key(), generate_key()
        a = SignalTransport(relay.addr(), ka, timeout=20.0,
                            direct_listen=direct)
        b = SignalTransport(relay.addr(), kb, timeout=20.0,
                            direct_listen=direct)
        a.listen()
        b.listen()
        return a, b, lambda: (a.close(), b.close(), relay.close())
    srv = TCPTransport("127.0.0.1:0")
    srv.listen()
    cli = TCPTransport("127.0.0.1:0")
    cli.listen()
    return cli, srv, lambda: (cli.close(), srv.close())


@pytest.fixture(params=["inmem", "tcp", "signal", "signal-direct"])
def pair(request):
    cli, srv, cleanup = _make_pair(request.param)
    stop = threading.Event()
    yield cli, srv, stop
    stop.set()
    cleanup()


def test_sync_rpc(pair):
    cli, srv, stop = pair
    want = SyncResponse(from_id=2, events=[_wire_event()], known={1: 5, 2: 9})
    _responder(srv, {"SyncRequest": want}, stop)
    got = cli.sync(
        srv.advertise_addr(), SyncRequest(from_id=1, known={1: 2}, sync_limit=500)
    )
    assert got.from_id == 2
    assert got.known == {1: 5, 2: 9}
    assert len(got.events) == 1
    assert got.events[0].body.transactions == [b"t1", b"t2"]
    assert got.events[0].signature == "abc|def"


def test_eager_sync_rpc(pair):
    cli, srv, stop = pair
    _responder(srv, {"EagerSyncRequest": EagerSyncResponse(2, True)}, stop)
    got = cli.eager_sync(
        srv.advertise_addr(),
        EagerSyncRequest(from_id=1, events=[_wire_event()]),
    )
    assert got.success is True


def test_fast_forward_rpc(pair):
    cli, srv, stop = pair
    want = FastForwardResponse(from_id=2, block=None, frame=None, snapshot=b"\x01\x02")
    _responder(srv, {"FastForwardRequest": want}, stop)
    got = cli.fast_forward(srv.advertise_addr(), FastForwardRequest(from_id=1))
    assert got.snapshot == b"\x01\x02"


def test_join_rpc(pair):
    cli, srv, stop = pair
    k = generate_key()
    peer = Peer("tcp://x", k.public_key.hex(), "joiner")
    itx = InternalTransaction.join(peer)
    itx.sign(k)
    want = JoinResponse(from_id=2, accepted=True, accepted_round=11, peers=[peer])
    _responder(srv, {"JoinRequest": want}, stop)
    got = cli.join(srv.advertise_addr(), JoinRequest(internal_transaction=itx))
    assert got.accepted is True
    assert got.accepted_round == 11
    assert got.peers[0].pub_key_hex == peer.pub_key_hex


def test_remote_error_propagates(pair):
    cli, srv, stop = pair
    _responder(srv, {"SyncRequest": "something broke"}, stop)
    with pytest.raises(TransportError):
        cli.sync(
            srv.advertise_addr(), SyncRequest(from_id=1, known={}, sync_limit=10)
        )


def test_remote_errors_are_typed():
    """Handler errors over inmem and TCP surface as RemoteError — the
    network worked, so retry loops (fast-forward) treat them as
    conclusive answers, not connectivity failures."""
    from babble_tpu.net.transport import RemoteError

    for kind in ("inmem", "tcp"):
        cli, srv, cleanup = _make_pair(kind)
        stop = threading.Event()
        _responder(srv, {"SyncRequest": "handler exploded"}, stop)
        try:
            with pytest.raises(RemoteError):
                cli.sync(
                    srv.advertise_addr(),
                    SyncRequest(from_id=1, known={}, sync_limit=10),
                )
        finally:
            stop.set()
            cleanup()


def test_dial_failure():
    cli = TCPTransport("127.0.0.1:0")
    with pytest.raises(TransportError):
        cli.sync(
            "127.0.0.1:1", SyncRequest(from_id=1, known={}, sync_limit=10)
        )
    cli.close()


def test_gossip_over_tcp():
    """3 full nodes over real localhost TCP sockets reach identical blocks
    (reference: node_test.go full-node tier with real TCP)."""
    n = 3
    keys = [generate_key() for _ in range(n)]
    transports = []
    for _ in range(n):
        t = TCPTransport("127.0.0.1:0")
        t.listen()
        transports.append(t)
    peers = PeerSet(
        [
            Peer(transports[i].advertise_addr(), k.public_key.hex(), f"n{i}")
            for i, k in enumerate(keys)
        ]
    )
    trans_of = {
        transports[i].advertise_addr(): transports[i] for i in range(n)
    }
    nodes, proxies, states = [], [], []
    for i, k in enumerate(keys):
        conf = Config(
            heartbeat_timeout=0.02,
            slow_heartbeat_timeout=0.2,
            moniker=f"n{i}",
            log_level="warning",
        )
        st = DummyState()
        pr = InmemProxy(st)
        addr = next(
            p.net_addr for p in peers.peers if p.pub_key_hex == k.public_key.hex()
        )
        node = Node(
            conf,
            Validator(k, f"n{i}"),
            peers,
            peers,
            InmemStore(conf.cache_size),
            trans_of[addr],
            pr,
        )
        node.init()
        nodes.append(node)
        proxies.append(pr)
        states.append(st)
    try:
        for nd in nodes:
            nd.run_async()
        deadline = time.monotonic() + 60
        i = 0
        while (
            min(nd.get_last_block_index() for nd in nodes) < 1
            and time.monotonic() < deadline
        ):
            proxies[i % n].submit_tx(f"tx {i}".encode())
            i += 1
            time.sleep(0.005)
        assert min(nd.get_last_block_index() for nd in nodes) >= 1
        b0 = [nodes[0].get_block(j).body.hash() for j in range(2)]
        for nd in nodes[1:]:
            assert [nd.get_block(j).body.hash() for j in range(2)] == b0
    finally:
        for nd in nodes:
            nd.shutdown()


def test_tcp_pooled_connections():
    """Concurrent RPCs to one target succeed and the connection pool never
    retains more than max_pool sockets (reference:
    net_transport_test.go:13 TestNetworkTransport_PooledConn,
    tcp_transport_test.go:30)."""
    srv = TCPTransport("127.0.0.1:0")
    srv.listen()
    cli = TCPTransport("127.0.0.1:0", max_pool=2)
    cli.listen()
    stop = threading.Event()
    _responder(
        srv, {"SyncRequest": SyncResponse(from_id=2, events=[], known={})},
        stop,
    )
    try:
        results = []
        errs = []

        def one(k):
            try:
                got = cli.sync(
                    srv.advertise_addr(),
                    SyncRequest(from_id=k, known={}, sync_limit=10),
                )
                results.append(got.from_id)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=one, args=(k,)) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        assert not errs, errs
        assert results == [2] * 8
        with cli._pool_lock:
            pooled = sum(len(v) for v in cli._pool.values())
        assert pooled <= 2, f"pool retained {pooled} > max_pool sockets"
        # pooled connections are REUSED: sequential calls must check the
        # SAME socket objects back out, not dial fresh ones
        with cli._pool_lock:
            pooled_ids = {id(c) for v in cli._pool.values() for c in v}
        assert pooled_ids, "nothing pooled to reuse"
        for k in range(4):
            cli.sync(srv.advertise_addr(),
                     SyncRequest(from_id=k, known={}, sync_limit=10))
        with cli._pool_lock:
            after_ids = {id(c) for v in cli._pool.values() for c in v}
        assert after_ids & pooled_ids, (
            "sequential calls dialed fresh sockets instead of reusing "
            "the pool"
        )
    finally:
        stop.set()
        cli.close()
        srv.close()


def _one_shot_server(responses: dict):
    """A raw framed-protocol server that serves exactly ONE RPC per
    connection then closes it — manufacturing the stale-pooled-socket
    condition (peer closed the connection between RPCs)."""
    import socket
    import struct

    from babble_tpu.crypto.canonical import canonical_dumps
    from babble_tpu.net.tcp import _recv_exact, _send_frame
    from babble_tpu.net.rpc import REQUEST_TYPES

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    stop = threading.Event()
    served = []

    def run():
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                type_byte = _recv_exact(conn, 1)[0]
                (length,) = struct.unpack(">I", _recv_exact(conn, 4))
                _recv_exact(conn, length)
                resp = responses[REQUEST_TYPES[type_byte].__name__]
                _send_frame(
                    conn, None,
                    canonical_dumps(
                        {"error": None, "payload": resp.to_dict()}
                    ),
                )
                served.append(1)
            except Exception:
                pass
            finally:
                try:
                    conn.close()  # one RPC per connection, then hang up
                except OSError:
                    pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    addr = "127.0.0.1:%d" % srv.getsockname()[1]
    return srv, addr, stop, served


def test_tcp_stale_pooled_socket_retries_on_fresh_dial():
    """A pooled socket the peer has since closed must not fail the RPC:
    the pool is evicted and the RPC retried once on a fresh dial
    (ISSUE-3 satellite: TCP pool hardening)."""
    import select
    import socket

    srv, addr, stop, served = _one_shot_server(
        {"SyncRequest": SyncResponse(from_id=5, events=[], known={})}
    )
    cli = TCPTransport("127.0.0.1:0")
    try:
        req = SyncRequest(from_id=1, known={}, sync_limit=10)
        assert cli.sync(addr, req).from_id == 5
        # the socket went back to the pool, but the server closed its end
        with cli._pool_lock:
            (pooled,) = [c for v in cli._pool.values() for c in v]
        # wait until the server-side FIN has landed: readable, and empty
        deadline = time.monotonic() + 5.0
        while True:
            readable, _, _ = select.select([pooled], [], [], 0.05)
            if readable and pooled.recv(1, socket.MSG_PEEK) == b"":
                break
            assert time.monotonic() < deadline, "the server never hung up"
        assert cli.sync(addr, req).from_id == 5  # salvaged by the retry
        assert cli.retries == 1
        assert cli.pool_evictions >= 1
        # the server thread counts an RPC after it has answered it
        while len(served) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(served) == 2
    finally:
        stop.set()
        srv.close()
        cli.close()


def test_tcp_timeout_on_pooled_socket_is_not_retried():
    """An RPC timeout means the peer is slow/gone, not that the pooled
    socket was stale — it must surface after ONE timeout period, never
    trigger the fresh-dial retry (which would double latency and deliver
    the request twice to a slow-but-alive peer)."""
    import socket as _socket
    import struct as _struct

    from babble_tpu.crypto.canonical import canonical_dumps
    from babble_tpu.net.rpc import REQUEST_TYPES
    from babble_tpu.net.tcp import _recv_exact, _send_frame

    srv = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    srv.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    stop = threading.Event()
    served = []

    def run():
        # per connection: answer the FIRST RPC, then go silent (slow peer)
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                while True:
                    type_byte = _recv_exact(conn, 1)[0]
                    (ln,) = _struct.unpack(">I", _recv_exact(conn, 4))
                    _recv_exact(conn, ln)
                    if served:
                        stop.wait(5.0)  # stall well past the RPC timeout
                        break
                    resp = SyncResponse(from_id=3, events=[], known={})
                    _send_frame(
                        conn, None,
                        canonical_dumps(
                            {"error": None, "payload": resp.to_dict()}
                        ),
                    )
                    served.append(REQUEST_TYPES[type_byte].__name__)
            except Exception:
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    threading.Thread(target=run, daemon=True).start()
    addr = "127.0.0.1:%d" % srv.getsockname()[1]
    cli = TCPTransport("127.0.0.1:0", timeout=0.5)
    try:
        req = SyncRequest(from_id=1, known={}, sync_limit=10)
        assert cli.sync(addr, req).from_id == 3  # pooled afterwards
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            cli.sync(addr, req)  # pooled socket, server stalls
        elapsed = time.monotonic() - t0
        assert cli.retries == 0, "timeout must not trigger a retry"
        assert elapsed < 1.5, f"timeout surfaced after {elapsed:.1f}s (retried?)"
    finally:
        stop.set()
        srv.close()
        cli.close()


def test_tcp_remote_error_is_not_retried():
    """A remote handler error means the peer processed the request — it
    must surface immediately, never trigger the fresh-dial retry."""
    srv = TCPTransport("127.0.0.1:0")
    srv.listen()
    cli = TCPTransport("127.0.0.1:0")
    stop = threading.Event()
    _responder(srv, {"SyncRequest": "handler exploded"}, stop)
    try:
        req = SyncRequest(from_id=1, known={}, sync_limit=10)
        for _ in range(2):  # second call uses the pooled socket
            with pytest.raises(TransportError, match="remote error"):
                cli.sync(srv.advertise_addr(), req)
        assert cli.retries == 0
    finally:
        stop.set()
        cli.close()
        srv.close()


def test_tcp_dial_timeout_is_explicit():
    """The connect deadline is the dial timeout, not the (much longer)
    RPC timeout."""
    cli = TCPTransport("127.0.0.1:0", timeout=30.0, dial_timeout=0.5)
    try:
        assert cli._dial_timeout == 0.5
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            cli.sync(
                "127.0.0.1:1", SyncRequest(from_id=1, known={}, sync_limit=1)
            )
        # refused or timed out — either way far below the RPC timeout
        assert time.monotonic() - t0 < 5.0
    finally:
        cli.close()


def test_tcp_bad_addr():
    """An unbindable address fails loudly at listen (reference:
    tcp_transport_test.go:13 TestTCPTransport_BadAddr)."""
    # unresolvable host: fails in getaddrinfo regardless of sysctls like
    # ip_nonlocal_bind (which can make binding a foreign unicast IP succeed)
    t = TCPTransport("256.256.256.256:0")
    try:
        with pytest.raises(OSError):
            t.listen()
    finally:
        t.close()


def test_tcp_with_advertise():
    """advertise_addr is what peers are told; the bind address still
    serves (reference: tcp_transport_test.go:20 WithAdvertise)."""
    srv = TCPTransport("127.0.0.1:0", advertise_addr="node77.example:9000")
    srv.listen()
    try:
        assert srv.advertise_addr() == "node77.example:9000"
        assert srv.local_addr() != srv.advertise_addr()
        # the real bound address still answers RPCs
        stop = threading.Event()
        _responder(
            srv,
            {"SyncRequest": SyncResponse(from_id=9, events=[], known={})},
            stop,
        )
        cli = TCPTransport("127.0.0.1:0")
        cli.listen()
        try:
            got = cli.sync(
                srv.local_addr(),
                SyncRequest(from_id=1, known={}, sync_limit=5),
            )
            assert got.from_id == 9
        finally:
            stop.set()
            cli.close()
    finally:
        srv.close()
