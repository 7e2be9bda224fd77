"""One validator, built as ``engine.py`` builds it."""

from __future__ import annotations


def build_node(env, conf: dict, key, moniker: str, peers, bind_addr: str,
               app, tcp: bool):
    """A ``Node`` with ``Config(accelerator=True)`` and otherwise upstream
    defaults (heartbeat 10 ms / 1 s, SyncLimit 1000, CacheSize 10000,
    SuspendLimit 100), ``InmemStore(cache_size)`` and ``InmemProxy(app)``,
    taken through ``Node.init()`` with the prewarm thread joined (as
    ``chip_smoke.py`` does, no ``BABBLE_PREWARM_BLOCK``) and not started.
    ``tcp`` picks a loopback ``TCPTransport`` or an in-memory one that
    nothing is connected to. Returns (node, proxy)."""
    from babble_tpu.config.config import Config
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.net.tcp import TCPTransport
    from babble_tpu.node.node import Node
    from babble_tpu.node.validator import Validator
    from babble_tpu.proxy.proxy import InmemProxy

    node_conf = Config(
        bind_addr=bind_addr, moniker=moniker, log_level="error",
        no_service=True, accelerator=True,
    )
    if tcp:
        trans = TCPTransport(
            bind_addr, max_pool=node_conf.max_pool,
            timeout=node_conf.tcp_timeout,
            join_timeout=node_conf.join_timeout,
        )
    else:
        trans = InmemNetwork().new_transport(bind_addr)
    proxy = InmemProxy(app)
    node = Node(node_conf, Validator(key, moniker), peers, peers,
                InmemStore(node_conf.cache_size), trans, proxy)
    env.scale_gate(node, conf)
    node.init()
    warm = getattr(node, "_prewarm_thread", None)
    if warm is not None:
        warm.join()
    return node, proxy
