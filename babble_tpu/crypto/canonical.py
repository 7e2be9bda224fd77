"""Canonical, byte-stable serialization for consensus objects.

The reference hashes canonical JSON of event/round/frame bodies (ugorji codec
with Canonical=true, reference: roundInfo.go:127-149, event.go:57-64). We use
our own deterministic convention — sorted keys, no whitespace, bytes as
base64 — which is stable across nodes (what consensus actually requires), not
wire-compatible with Go.
"""

from __future__ import annotations

import base64
import json
from typing import Any


class CacheStats:
    """Hit/miss tally for a serialization memo. Process-wide (co-located
    nodes share it); increments race benignly under the GIL — a stats
    counter may drop an update, never corrupt."""

    __slots__ = ("hits", "misses")

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}


#: memo_normalized() effectiveness — how often an event body / wire event
#: re-serialization was avoided (gossip replies, frame re-encodes).
NORM_CACHE = CacheStats()


class PreNormalized:
    """Wrapper marking a value as ALREADY normalized (b64 applied, plain
    str/int/dict/list all the way down). _normalize passes it through
    untouched — the hook that lets hot senders (event push paths) memoize
    an object's normalized form instead of re-walking it per send."""

    __slots__ = ("value",)

    def __init__(self, value: Any):
        self.value = value


def memo_normalized(holder: Any, build) -> Any:
    """Shared memo for normalized() encoders (wire events, event bodies):
    compute _normalize(build()) once and cache it on ``holder._norm``.
    Callers must invalidate by setting ``holder._norm = None`` when the
    underlying object mutates."""
    n = getattr(holder, "_norm", None)
    if n is None:
        NORM_CACHE.misses += 1
        n = _normalize(build())
        holder._norm = n
    else:
        NORM_CACHE.hits += 1
    return n


def _normalize(obj: Any) -> Any:
    # exact-type fast path ordered by frequency (leaves dominate): this
    # walk runs for every event hash on the insert hot path. Subclasses
    # (IntEnum, OrderedDict, namedtuple, ...) miss the fast path and fall
    # through to the original isinstance chain below, keeping their old
    # semantics.
    t = type(obj)
    if t is str or t is int:
        return obj
    if t is PreNormalized:
        return obj.value
    if t is bytes or t is bytearray:
        return base64.b64encode(bytes(obj)).decode("ascii")
    if t is dict:
        return {str(k): _normalize(v) for k, v in obj.items()}
    if t is list or t is tuple:
        return [_normalize(v) for v in obj]
    if t is bool or obj is None:
        return obj
    if isinstance(obj, (bytes, bytearray)):
        return base64.b64encode(bytes(obj)).decode("ascii")
    if isinstance(obj, dict):
        return {str(k): _normalize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_normalize(v) for v in obj]
    if isinstance(obj, (str, int, bool)):
        return obj
    raise TypeError(f"non-canonical type {type(obj)!r} in consensus object")


def canonical_dumps(obj: Any) -> bytes:
    """Deterministic JSON bytes: sorted keys, compact separators, base64 bytes.

    Floats are rejected (consensus must not contain floats — SURVEY.md §7
    hard part 4)."""
    return json.dumps(
        _normalize(obj), sort_keys=True, separators=(",", ":"), allow_nan=False
    ).encode("utf-8")


def canonical_scalar(v: Any) -> bytes:
    """canonical_dumps(v) for a value that is as a rule a plain int or
    bool (a round, a timestamp, a flag spliced into a larger encoding);
    whatever else it turns out to be goes the plain way."""
    t = type(v)
    if t is int:
        return b"%d" % v
    if t is bool:
        return b"true" if v else b"false"
    return canonical_dumps(v)


def canonical_loads(data: bytes) -> Any:
    return json.loads(data.decode("utf-8"))


def jsonable(obj: Any) -> Any:
    """Canonical-normalize (bytes → b64, sorted keys) into plain JSON
    types — the one helper behind every HTTP payload and evidence record
    that must round-trip through json.dumps."""
    return json.loads(canonical_dumps(obj))


def b64(data: bytes) -> str:
    return base64.b64encode(data).decode("ascii")


def unb64(s: str) -> bytes:
    return base64.b64decode(s)
