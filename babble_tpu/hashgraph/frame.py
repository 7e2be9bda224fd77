"""Frame and Root — consensus checkpoints (reference: src/hashgraph/frame.go,
root.go). A Frame is a self-contained restart point: the peer-set history,
per-participant Roots (last ROOT_DEPTH consensus events), and the events
received at one round."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from babble_tpu.crypto.canonical import canonical_dumps, canonical_scalar
from babble_tpu.crypto.hashing import sha256
from babble_tpu.hashgraph.event import FrameEvent, sort_frame_events
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet


@dataclass
class Root:
    """Base on top of which a participant's events can be inserted,
    sorted by Lamport timestamp (reference: root.go:13-28)."""

    events: List[FrameEvent] = field(default_factory=list)

    def insert(self, fe: FrameEvent) -> None:
        self.events.append(fe)

    def to_dict(self) -> dict:
        return {"Events": [fe.to_dict() for fe in self.events]}

    def canonical_bytes(self) -> bytes:
        """canonical_dumps(self.to_dict()), joined from the events' texts."""
        return b'{"Events":[%b]}' % b",".join(
            [fe.canonical_text() for fe in self.events]
        )

    @staticmethod
    def from_dict(d: dict) -> "Root":
        return Root(events=[FrameEvent.from_dict(e) for e in d.get("Events") or []])


@dataclass
class Frame:
    """reference: frame.go:13-20."""

    round: int  # round received
    peers: PeerSet  # authoritative peer-set at this round
    roots: Dict[str, Root]  # participant pubkey hex => Root
    events: List[FrameEvent]  # events with round_received == round
    peer_sets: Dict[int, List[Peer]]  # full peer-set history: round => peers
    timestamp: int  # BFT median of famous-witness timestamps

    def sorted_frame_events(self) -> List[FrameEvent]:
        """All events incl. roots', in consensus order (reference: frame.go:24-32)."""
        out: List[FrameEvent] = []
        for r in self.roots.values():
            out.extend(r.events)
        out.extend(self.events)
        return sort_frame_events(out)

    def to_dict(self) -> dict:
        return {
            "Round": self.round,
            "Peers": [p.to_dict() for p in self.peers.peers],
            "Roots": {k: r.to_dict() for k, r in self.roots.items()},
            "Events": [fe.to_dict() for fe in self.events],
            "PeerSets": {
                str(rnd): [p.to_dict() for p in ps]
                for rnd, ps in self.peer_sets.items()
            },
            "Timestamp": self.timestamp,
        }

    def canonical_bytes(self) -> bytes:
        """The canonical encoding, byte for byte canonical_dumps(
        self.to_dict()) — sorted keys at every level, compact separators —
        but assembled: every event of the Frame and of its Roots brings
        the text its frame form holds (FrameEvent.canonical_text), and
        only the peers and the scalars are encoded here. to_dict() stays
        the wire and persistence form, and what this is tested against."""
        return (
            b'{"Events":[%b],"PeerSets":%b,"Peers":%b,"Roots":{%b},'
            b'"Round":%b,"Timestamp":%b}'
        ) % (
            b",".join([fe.canonical_text() for fe in self.events]),
            canonical_dumps({
                str(rnd): [p.to_dict() for p in ps]
                for rnd, ps in self.peer_sets.items()
            }),
            canonical_dumps([p.to_dict() for p in self.peers.peers]),
            b",".join([
                b"%b:%b" % (canonical_dumps(k), root.canonical_bytes())
                for k, root in sorted(self.roots.items())
            ]),
            canonical_scalar(self.round),
            canonical_scalar(self.timestamp),
        )

    def hash(self) -> bytes:
        """SHA256 of the canonical encoding (reference: frame.go:63-69):
        the one way a Frame is hashed, whoever built it."""
        return sha256(self.canonical_bytes())

    @staticmethod
    def from_dict(d: dict) -> "Frame":
        return Frame(
            round=d["Round"],
            peers=PeerSet([Peer.from_dict(p) for p in d.get("Peers") or []]),
            roots={k: Root.from_dict(r) for k, r in (d.get("Roots") or {}).items()},
            events=[FrameEvent.from_dict(e) for e in d.get("Events") or []],
            peer_sets={
                int(rnd): [Peer.from_dict(p) for p in ps]
                for rnd, ps in (d.get("PeerSets") or {}).items()
            },
            timestamp=d["Timestamp"],
        )
