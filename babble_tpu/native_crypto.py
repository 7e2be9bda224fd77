"""ctypes loader for the native batch crypto library (native/secp256k1.cc).

The shared object is built with g++ on first use, next to the source, under
a name that carries the source's content hash
(``libbabble_crypto.<sha256[:16]>.so``): a copied or checked-out tree has
no meaningful mtimes, and a library built from other source can never be
loaded for this one. The ``.so`` is never committed. Consumers degrade to
the OpenSSL / pure-Python paths in babble_tpu.crypto.keys when no compiler
is available — ~100x slower on the path every event takes, so a failed
build is logged as a warning, and chip_smoke.py refuses to run without
the library. The batch entry points exist so the gossip sync path can
verify a whole sync's worth of event signatures in ONE foreign call
(reference hot loop: src/hashgraph/hashgraph.go:672-687 verifying per
event).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_PKG_DIR)

# Source search order:
# 1. repo layout (native/ next to the package — development checkouts);
# 2. installed package data (babble_tpu/_native/, shipped in the wheel;
#    the wheel build pre-compiles the .so there when a compiler exists).
_SRC_CANDIDATES = [
    os.path.join(_REPO_ROOT, "native", "secp256k1.cc"),
    os.path.join(_PKG_DIR, "_native", "secp256k1.cc"),
]
_SRC = next((p for p in _SRC_CANDIDATES if os.path.exists(p)),
            _SRC_CANDIDATES[0])

_lib: Optional[ctypes.CDLL] = None
_tried = False
_lock = threading.Lock()


def so_name(src_path: str) -> str:
    """The library's file name for a given source file: keyed on the
    source's CONTENT (setup.py's wheel build uses the same rule)."""
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"libbabble_crypto.{digest}.so"


def _build_at(so_path: str) -> bool:
    # Compile to a temp path and rename into place: os.rename is atomic on
    # POSIX, so concurrent node processes never dlopen a half-written .so.
    tmp = f"{so_path}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so_path)
    except (OSError, subprocess.SubprocessError) as err:
        logger.warning(
            "native crypto build failed at %s (%s): signature checks fall "
            "back to OpenSSL / pure Python, ~100x slower", so_path, err,
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    # libraries built from older source are dead weight now
    for old in glob.glob(
        os.path.join(os.path.dirname(so_path), "libbabble_crypto.*.so")
    ):
        if old != so_path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SRC):
            logger.warning("native crypto source missing at %s", _SRC)
            return None
        so = os.path.join(os.path.dirname(_SRC), so_name(_SRC))
        if not os.path.exists(so) and not _build_at(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as err:
            logger.warning("native crypto load failed: %s", err)
            return None
        lib.bt_has_native.restype = ctypes.c_int
        lib.bt_verify_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        lib.bt_sign.restype = ctypes.c_int
        lib.bt_sign.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
        lib.bt_pubkey.restype = ctypes.c_int
        lib.bt_pubkey.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.bt_sha256_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def verify_batch(
    pubs: Sequence[bytes], msgs: Sequence[bytes], rs: Sequence[Tuple[int, int]]
) -> Optional[List[bool]]:
    """Verify n signatures in one native call.

    pubs: 64-byte x||y each; msgs: 32-byte hashes; rs: (r, s) ints.
    Returns None when the native library is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    n = len(pubs)
    if not (n == len(msgs) == len(rs)):
        raise ValueError("batch length mismatch")
    if n == 0:
        return []
    # Attacker-controlled signatures can decode to negative or >256-bit
    # ints (base-36 is unbounded); those are invalid, never an exception.
    results = [False] * n
    idx: List[int] = []
    chunks: List[bytes] = []
    for i, (r, s) in enumerate(rs):
        if 0 < r < (1 << 256) and 0 < s < (1 << 256):
            idx.append(i)
            chunks.append(r.to_bytes(32, "big") + s.to_bytes(32, "big"))
    if not idx:
        return results
    pub_buf = b"".join(pubs[i] for i in idx)
    msg_buf = b"".join(msgs[i] for i in idx)
    rs_buf = b"".join(chunks)
    out = ctypes.create_string_buffer(len(idx))
    lib.bt_verify_batch(pub_buf, msg_buf, rs_buf, len(idx), out)
    for i, b in zip(idx, out.raw):
        results[i] = b == 1
    return results


def verify_one(pub64: bytes, msg32: bytes, r: int, s: int) -> Optional[bool]:
    res = verify_batch([pub64], [msg32], [(r, s)])
    return None if res is None else res[0]


def sign(priv32: bytes, msg32: bytes) -> Optional[Tuple[int, int]]:
    """Deterministic RFC 6979 ECDSA sign; (r, s) or None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(64)
    rc = lib.bt_sign(priv32, msg32, out)
    if rc != 0:
        raise ValueError(f"native sign failed (rc={rc})")
    raw = out.raw
    return int.from_bytes(raw[:32], "big"), int.from_bytes(raw[32:], "big")


def pubkey(priv32: bytes) -> Optional[Tuple[int, int]]:
    lib = _load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(64)
    rc = lib.bt_pubkey(priv32, out)
    if rc != 0:
        raise ValueError(f"native pubkey failed (rc={rc})")
    raw = out.raw
    return int.from_bytes(raw[:32], "big"), int.from_bytes(raw[32:], "big")


def sha256_batch(msgs: Sequence[bytes]) -> Optional[List[bytes]]:
    """Hash n equal-length messages in one native call (None if n=0 ok)."""
    lib = _load()
    if lib is None or not msgs:
        return None if lib is None else []
    stride = len(msgs[0])
    if any(len(m) != stride for m in msgs):
        raise ValueError("sha256_batch requires equal-length messages")
    out = ctypes.create_string_buffer(32 * len(msgs))
    lib.bt_sha256_batch(b"".join(msgs), stride, len(msgs), out)
    raw = out.raw
    return [raw[32 * i : 32 * i + 32] for i in range(len(msgs))]
