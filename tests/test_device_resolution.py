"""Device resolution in the process that uses the device
(babble_tpu/ops/device.py), the compile-cache helper, the content-keyed
native library build, and chip_smoke.py's refusal to pass without a chip.

The suite runs under conftest's explicit cpu pin, so these tests exercise
the pin being honoured and — by lifting it for one call — the error a
``--accelerator`` node gets on a machine with no TPU.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

from babble_tpu.ops import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Fake:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


def test_is_tpu_device_classifier():
    """One platform name decides; the kind string is never sniffed."""
    assert device._is_tpu_device(_Fake("tpu", "TPU v5 lite"))
    assert not device._is_tpu_device(_Fake("cpu", "TPU-ish"))
    assert not device._is_tpu_device(_Fake("gpu", "NVIDIA"))


@pytest.fixture
def no_pin(monkeypatch):
    """Lift conftest's cpu pin for one test (the backend stays the cpu one
    it already is — only the *stated choice* goes)."""
    import jax

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    jax.config.update("jax_platforms", "")
    yield
    jax.config.update("jax_platforms", "cpu")


def test_explicit_cpu_pin_is_honoured():
    assert device.cpu_pinned()
    assert device.require_accelerator() == "cpu"
    assert device.ensure_device() == "cpu"
    assert not device.on_accelerator() and not device.on_tpu()
    d = device.describe()
    assert d["platform"] == "cpu" and d["capture_class"] == "cpu-xla"
    assert d["count"] >= 1 and d["device"]


def test_tpu_first_platform_list_is_not_a_pin(no_pin, monkeypatch):
    import jax

    jax.config.update("jax_platforms", "tpu,cpu")
    assert not device.cpu_pinned()
    jax.config.update("jax_platforms", "cpu,tpu")
    assert device.cpu_pinned()
    jax.config.update("jax_platforms", "")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.cpu_pinned()


def test_accelerator_without_tpu_or_pin_raises_at_node_init(no_pin):
    """No TPU, no pin: Node.init refuses — it does not move to host XLA."""
    from babble_tpu.net.inmem import InmemNetwork
    from tests.test_node import make_cluster

    assert not device.cpu_pinned()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        make_cluster(2, InmemNetwork(), accelerator=True)


def test_accelerator_mesh_that_cannot_be_built_raises():
    """A mesh request the devices cannot serve is an error at Node.init,
    never a quiet single-device run."""
    from babble_tpu.config.config import Config
    from babble_tpu.crypto.keys import generate_key
    from babble_tpu.hashgraph.store import InmemStore
    from babble_tpu.net.inmem import InmemNetwork
    from babble_tpu.node.node import Node
    from babble_tpu.node.validator import Validator
    from babble_tpu.peers.peer import Peer
    from babble_tpu.peers.peer_set import PeerSet
    from babble_tpu.proxy.proxy import InmemProxy
    from babble_tpu.dummy.state import State

    key = generate_key()
    peers = PeerSet([Peer("inmem://m0", key.public_key.hex(), "m0")])

    def node(mesh):
        conf = Config(log_level="error", moniker="m0", accelerator=True,
                      accelerator_mesh=mesh)
        return Node(conf, Validator(key, "m0"), peers, peers,
                    InmemStore(conf.cache_size),
                    InmemNetwork().new_transport("inmem://m0"),
                    InmemProxy(State()))

    with pytest.raises(ValueError, match="power of two"):
        node(6).init()
    with pytest.raises(Exception):
        node(64).init()  # conftest provides 8 virtual devices


def test_babble_pallas_off_tpu_raises(monkeypatch):
    import jax.numpy as jnp

    from babble_tpu.ops import dag, voting

    monkeypatch.delenv("BABBLE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("BABBLE_PALLAS", "1")
    with pytest.raises(RuntimeError, match="BABBLE_PALLAS=1 needs a TPU"):
        voting.pallas_mode()
    la = jnp.zeros((8, 4), jnp.int32)
    with pytest.raises(RuntimeError, match="BABBLE_PALLAS=1 needs a TPU"):
        dag.strongly_see_matrix(la, la, 3)
    # the interpreter is a separate, explicit switch
    monkeypatch.setenv("BABBLE_PALLAS_INTERPRET", "1")
    assert voting.pallas_mode() == "interpret"
    monkeypatch.delenv("BABBLE_PALLAS")
    monkeypatch.delenv("BABBLE_PALLAS_INTERPRET")
    assert voting.pallas_mode() is None


def test_compile_cache_helper_obeys_env_else_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.compile_cache_dir() == os.path.join(REPO, ".jax_cache")

    class _Config:
        def __init__(self):
            self.updates = {}

        def update(self, k, v):
            self.updates[k] = v

    class _Jax:
        config = _Config()

    device.setup_compile_cache(_Jax)
    assert _Jax.config.updates == {
        "jax_compilation_cache_dir": os.path.join(REPO, ".jax_cache")
    }
    # placed from outside: no directory is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert device.compile_cache_dir() is None
    _Jax.config = _Config()
    device.setup_compile_cache(_Jax)
    assert _Jax.config.updates == {}


def test_native_lib_build_keyed_on_source_hash(tmp_path, monkeypatch):
    """The library's name carries the source's content hash: new content
    builds a new library and retires the old; mtimes play no part."""
    import shutil

    from babble_tpu import native_crypto as nc

    if shutil.which("g++") is None:
        pytest.skip("no g++ here")
    src = tmp_path / "secp256k1.cc"
    shutil.copy(nc._SRC, src)
    monkeypatch.setattr(nc, "_SRC", str(src))
    monkeypatch.setattr(nc, "_lib", None)
    monkeypatch.setattr(nc, "_tried", False)

    first = tmp_path / nc.so_name(str(src))
    assert first.name == nc.so_name(nc._SRC_CANDIDATES[0])  # same content
    assert nc.available() and first.exists()
    assert nc.sha256_batch([b"a" * 8])[0] == hashlib.sha256(b"a" * 8).digest()

    # an OLDER mtime on changed content must still rebuild
    src.write_bytes(src.read_bytes() + b"\n// changed\n")
    os.utime(src, (1, 1))
    second = tmp_path / nc.so_name(str(src))
    assert second != first
    monkeypatch.setattr(nc, "_lib", None)
    monkeypatch.setattr(nc, "_tried", False)
    assert nc.available() and second.exists() and not first.exists()


def _run_smoke(*args):
    env = {k: v for k, v in os.environ.items() if not k.startswith("BABBLE_")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    return proc, proc.stdout.strip().splitlines()


def test_chip_smoke_without_a_chip_fails():
    proc, lines = _run_smoke()
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_chip_smoke_cpu_rehearsal_passes_and_says_cpu():
    proc, lines = _run_smoke("--cpu-rehearsal")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"],
        "count": last["device"]["count"]}}
    assert last["device"]["platform"] == "cpu"  # can never read as a chip pass
    out = proc.stdout
    for phase in ("programs", "replay", "live"):
        assert f"phase {phase}: PASS" in out
