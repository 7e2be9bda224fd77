#!/usr/bin/env python
"""Spin up an N-node localhost testnet (the demo/ makefile analogue,
reference: /root/reference/demo/makefile + demo/scripts/*.sh, minus docker).

Each node is a separate OS process running `babble_tpu run` with a socket
app proxy; a dummy chat-app client process attaches to each. Ports:

  node i:  gossip 127.0.0.1:12000+i   service   127.0.0.1:8000+i
           proxy  127.0.0.1:13000+i   app       127.0.0.1:14000+i
           subscriptions (docs/clients.md) 127.0.0.1:15000+i

Usage:  python demo/testnet.py [n_nodes] [--signal] [--accelerator]
                               [--async] [--gateway]
With --accelerator every node process runs device consensus sweeps on a
chip of its OWN (a chip belongs to one process at a time): node i is pinned
to chip i, and more node processes than chips is refused — many validators
on one chip is the in-process path (chip_smoke.py). With --async every
node runs the event-driven gossip engine + binary codec (docs/gossip.md)
instead of the threaded JSON transport — mixed testnets work too. With
--gateway a sharded light-client gateway (babble_tpu.client.gateway)
rides on top: submit at 127.0.0.1:16000, subscribe at 127.0.0.1:16001,
proofs at http://127.0.0.1:16002. Stop with Ctrl-C (nodes leave politely
on SIGTERM).

Cleanup is hardened (stray nodes from an aborted run squat the demo
ports and starve whatever runs next): children run in their own process
group, a SIGTERM/SIGHUP handler and an atexit hook both tear the group down, and
every child PID is recorded in <testnet dir>/pids plus the well-known
/tmp/babble_tpu_testnet.pids so `make killtestnet` can reap survivors
of even a SIGKILLed driver.
"""

from __future__ import annotations

import atexit
import contextlib
import fcntl
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from babble_tpu.crypto.keyfile import SimpleKeyfile  # noqa: E402
from babble_tpu.crypto.keys import generate_key  # noqa: E402

PIDS_WELL_KNOWN = os.path.join(tempfile.gettempdir(), "babble_tpu_testnet.pids")

_procs: list = []
_pid_files: list = []
_done = False


@contextlib.contextmanager
def _pidfile_lock():
    """Serialize every touch of the SHARED well-known pidfile across
    concurrently running drivers (append vs. the cleanup's
    read-modify-write would otherwise lose another driver's records)."""
    lock_path = PIDS_WELL_KNOWN + ".lock"
    try:
        fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o666)
    except OSError:
        yield
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)


def _record_pid(pid: int) -> None:
    with _pidfile_lock():
        for path in _pid_files:
            try:
                with open(path, "a", encoding="utf-8") as f:
                    f.write(f"{pid}\n")
            except OSError:
                pass


def _local_chips() -> int:
    """TPU chips on this host, counted from their device nodes — WITHOUT
    touching jax: a driver that initialized the backend would hold the
    chips its children need."""
    import glob

    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def _spawn(cmd: list, env: dict | None = None) -> subprocess.Popen:
    # own process group: one killpg reaps a node AND anything it forked
    p = subprocess.Popen(cmd, start_new_session=True, env=env)
    _procs.append(p)
    _record_pid(p.pid)
    return p


def _cleanup() -> None:
    """Idempotent teardown: polite SIGTERM to every child's process
    group, then SIGKILL what survives the grace window."""
    global _done
    if _done:
        return
    _done = True
    for p in _procs:
        try:
            os.killpg(p.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError, OSError):
            pass
    deadline = time.time() + 3.0
    for p in _procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError, OSError):
                pass
    own = {str(p.pid) for p in _procs}
    with _pidfile_lock():
        for path in _pid_files:
            try:
                if path == PIDS_WELL_KNOWN:
                    # the well-known file is SHARED with any concurrently
                    # running driver: remove only OUR pids (under the
                    # pidfile lock — an unlocked read-modify-write could
                    # drop a racing driver's append), unlinking only when
                    # nothing else is recorded, so another driver's
                    # survivors stay reachable via `make killtestnet`
                    with open(path, encoding="utf-8") as f:
                        others = [
                            ln for ln in f.read().splitlines()
                            if ln.strip() and ln.strip() not in own
                        ]
                    if others:
                        with open(path, "w", encoding="utf-8") as f:
                            f.write("\n".join(others) + "\n")
                    else:
                        os.unlink(path)
                else:
                    os.unlink(path)
            except OSError:
                pass


def _on_signal(signum, frame):
    # raise through the signal.pause() below so the finally/atexit path
    # runs exactly once, whatever interrupted us
    raise SystemExit(128 + signum)


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 4
    use_signal = "--signal" in sys.argv
    accelerator = "--accelerator" in sys.argv
    use_async = "--async" in sys.argv
    use_gateway = "--gateway" in sys.argv
    # an explicit cpu pin (JAX_PLATFORMS=cpu) runs the device kernels on
    # host XLA on purpose: no chip is needed or pinned then
    on_chips = accelerator and (
        os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu"
    )
    if on_chips and n > _local_chips():
        print(
            f"--accelerator: {n} node processes but {_local_chips()} TPU "
            "chip(s) on this host. A chip belongs to one process at a "
            "time, so each node process needs its own. To run many "
            "validators on one chip use the in-process cluster: "
            "python chip_smoke.py.",
            file=sys.stderr,
        )
        return 2
    base = tempfile.mkdtemp(prefix="babble_tpu_testnet_")
    print(f"testnet dir: {base}")
    _pid_files.extend([os.path.join(base, "pids"), PIDS_WELL_KNOWN])

    atexit.register(_cleanup)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGHUP, _on_signal)

    keys = [generate_key() for _ in range(n)]
    peers = [
        {
            "NetAddr": (
                k.public_key.hex() if use_signal else f"127.0.0.1:{12000 + i}"
            ),
            "PubKeyHex": k.public_key.hex(),
            "Moniker": f"node{i}",
        }
        for i, k in enumerate(keys)
    ]

    try:
        if use_signal:
            _spawn(
                [sys.executable, "-m", "babble_tpu.cli", "signal",
                 "--listen", "127.0.0.1:2443"]
            )
            time.sleep(0.5)

        for i, k in enumerate(keys):
            dd = os.path.join(base, f"node{i}")
            os.makedirs(dd)
            SimpleKeyfile(os.path.join(dd, "priv_key")).write_key(k)
            for fn in ("peers.json", "peers.genesis.json"):
                with open(os.path.join(dd, fn), "w") as f:
                    json.dump(peers, f, indent=2)
            cmd = [
                sys.executable, "-m", "babble_tpu.cli", "run",
                "--datadir", dd,
                "--listen", f"127.0.0.1:{12000 + i}",
                "--service-listen", f"127.0.0.1:{8000 + i}",
                "--proxy-listen", f"127.0.0.1:{13000 + i}",
                "--client-connect", f"127.0.0.1:{14000 + i}",
                "--client-listen", f"127.0.0.1:{15000 + i}",
                "--heartbeat", "0.02", "--slow-heartbeat", "0.5",
                "--moniker", f"node{i}", "--log", "info",
            ]
            if use_signal:
                cmd += ["--signal", "--signal-addr", "127.0.0.1:2443"]
            if use_async and not use_signal:
                cmd += ["--transport", "async"]
            env = None
            if accelerator:
                cmd.append("--accelerator")
            if on_chips:
                # one chip per node process: node i sees only chip i
                env = {
                    **os.environ,
                    "TPU_VISIBLE_CHIPS": str(i),
                    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                    "TPU_PROCESS_BOUNDS": "1,1,1",
                }
            _spawn(cmd, env)
            # dummy chat-app client on the other side of the socket pair
            _spawn(
                [sys.executable, "-m", "babble_tpu.cli", "dummy",
                 "--listen", f"127.0.0.1:{14000 + i}",
                 "--connect", f"127.0.0.1:{13000 + i}",
                 "--no-repl"]
            )

        if use_gateway:
            _spawn(
                [sys.executable, "-m", "babble_tpu.client.gateway",
                 "--forward",
                 ",".join(f"127.0.0.1:{13000 + i}" for i in range(n)),
                 "--upstream", "127.0.0.1:15000",
                 "--peers", os.path.join(base, "node0", "peers.json"),
                 "--listen", "127.0.0.1:16000",
                 "--sub-listen", "127.0.0.1:16001",
                 "--http", "127.0.0.1:16002",
                 "--processes"]
            )

        print(f"{n} nodes up. Stats:     curl 127.0.0.1:800N/stats")
        print("          Load:      python demo/bombard.py")
        print("          Graph:     curl 127.0.0.1:8000/graph")
        print("          Subscribe: python demo/bombard.py --subscribers=100"
              " --sub-addr=127.0.0.1:15000")
        print("          Proofs:    curl 127.0.0.1:8000/proof/<txid>")
        if use_gateway:
            print("          Gateway:   submit 127.0.0.1:16000, subscribe "
                  "127.0.0.1:16001, proofs http://127.0.0.1:16002")
        print("          Cleanup:   make killtestnet  (reaps stray nodes)")
        signal.pause()
    except KeyboardInterrupt:
        pass
    finally:
        _cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
