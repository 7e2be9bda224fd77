"""RoundInfo — per-round record of created/received events and fame state
(reference: src/hashgraph/roundInfo.go:11-154)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from babble_tpu.common.trilean import Trilean
from babble_tpu.peers.peer_set import PeerSet


@dataclass
class RoundEvent:
    """Witness/fame state of one event (reference: roundInfo.go:17-20)."""

    witness: bool = False
    famous: Trilean = Trilean.UNDEFINED


class RoundInfo:
    """reference: roundInfo.go:23-30. ``decided`` is sticky: once a round is
    decided it stays decided even if new witnesses appear later
    (roundInfo.go:73-96)."""

    # What a PersistentStore last encoded this round's row from (its
    # ``_RoundRow``); written by the store's encoder alone, never by the
    # mutators below, so a round no durable store writes carries none.
    store_row = None

    def __init__(self) -> None:
        self.created_events: Dict[str, RoundEvent] = {}
        self.received_events: List[str] = []
        self.decided: bool = False

    def add_created_event(self, x: str, witness: bool) -> None:
        """First write wins (reference: roundInfo.go:41-48)."""
        if x not in self.created_events:
            self.created_events[x] = RoundEvent(witness=witness)

    def add_received_event(self, x: str) -> None:
        self.received_events.append(x)

    def set_fame(self, x: str, famous: bool) -> None:
        """reference: roundInfo.go:56-71."""
        e = self.created_events.get(x)
        if e is None:
            e = RoundEvent(witness=True)
            self.created_events[x] = e
        e.famous = Trilean.TRUE if famous else Trilean.FALSE

    def witnesses_decided(self, peer_set: PeerSet) -> bool:
        """True when a super-majority of witnesses are decided and none are
        undecided (reference: roundInfo.go:78-96)."""
        if self.decided:
            return True
        c = 0
        for e in self.created_events.values():
            if e.witness and e.famous != Trilean.UNDEFINED:
                c += 1
            elif e.witness and e.famous == Trilean.UNDEFINED:
                return False
        self.decided = c >= peer_set.super_majority()
        return self.decided

    def witnesses(self) -> List[str]:
        return [x for x, e in self.created_events.items() if e.witness]

    def famous_witnesses(self) -> List[str]:
        return [
            x
            for x, e in self.created_events.items()
            if e.witness and e.famous == Trilean.TRUE
        ]

    def is_decided(self, witness: str) -> bool:
        e = self.created_events.get(witness)
        return e is not None and e.witness and e.famous != Trilean.UNDEFINED

    def to_dict(self) -> dict:
        return {
            "CreatedEvents": {
                x: {"Witness": e.witness, "Famous": int(e.famous)}
                for x, e in self.created_events.items()
            },
            "ReceivedEvents": list(self.received_events),
        }

    @staticmethod
    def from_dict(d: dict) -> "RoundInfo":
        r = RoundInfo()
        for x, e in (d.get("CreatedEvents") or {}).items():
            r.created_events[x] = RoundEvent(
                witness=e["Witness"], famous=Trilean(e["Famous"])
            )
        r.received_events = list(d.get("ReceivedEvents") or [])
        return r
