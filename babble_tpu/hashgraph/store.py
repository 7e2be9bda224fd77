"""Store — the persistence boundary of the consensus engine
(reference: src/hashgraph/store.go:6-73, inmem_store.go:14-321).

The engine only ever touches state through this interface, which is what
lets the TPU kernels swap in dense tensor snapshots behind the same
boundary (SURVEY.md §7)."""

from __future__ import annotations

from typing import Dict, List, Optional, Protocol

from babble_tpu.common.errors import StoreError, StoreErrorKind, is_store_err
from babble_tpu.common.lru import LRU
from babble_tpu.common.rolling_index import RollingIndex
from babble_tpu.hashgraph.block import Block
from babble_tpu.hashgraph.caches import ParticipantEventsCache, PeerSetCache
from babble_tpu.hashgraph.event import Event
from babble_tpu.hashgraph.frame import Frame, Root
from babble_tpu.hashgraph.round_info import RoundInfo
from babble_tpu.peers.peer import Peer
from babble_tpu.peers.peer_set import PeerSet


class Store(Protocol):
    """reference: store.go:6-73."""

    def cache_size(self) -> int: ...
    def get_peer_set(self, round: int) -> PeerSet: ...
    def set_peer_set(self, round: int, peer_set: PeerSet) -> None: ...
    def get_all_peer_sets(self) -> Dict[int, List[Peer]]: ...
    def first_round(self, participant_id: int) -> tuple[int, bool]: ...
    def repertoire_by_pub_key(self) -> Dict[str, Peer]: ...
    def repertoire_by_id(self) -> Dict[int, Peer]: ...
    def get_event(self, hash_: str) -> Event: ...
    def set_event(self, event: Event) -> None: ...
    # ``fn(event)`` for every event the cache evicts from then on (one
    # listener, the hashgraph on this store: what it holds by (creator,
    # index) for the first-descendant walk follows the cache's window).
    def on_event_evicted(self, fn) -> None: ...
    def participant_events(self, participant: str, skip: int) -> List[str]: ...
    def participant_event(self, participant: str, index: int) -> str: ...
    def last_event_from(self, participant: str) -> str: ...
    def last_consensus_event_from(self, participant: str) -> str: ...
    def known_events(self) -> Dict[int, int]: ...
    def consensus_events(self) -> List[str]: ...
    def consensus_events_count(self) -> int: ...
    def add_consensus_event(self, event: Event) -> None: ...
    def get_round(self, round_index: int) -> RoundInfo: ...
    def set_round(self, round_index: int, round_info: RoundInfo) -> None: ...
    def last_round(self) -> int: ...
    def round_witnesses(self, round_index: int) -> List[str]: ...
    def round_events(self, round_index: int) -> int: ...
    def get_root(self, participant: str) -> Root: ...
    def get_block(self, index: int) -> Block: ...
    def set_block(self, block: Block) -> None: ...
    def last_block_index(self) -> int: ...
    def get_frame(self, round_received: int) -> Frame: ...
    def set_frame(self, frame: Frame) -> None: ...
    def reset(self, frame: Frame) -> None: ...
    def close(self) -> None: ...
    def store_path(self) -> str: ...
    # Compaction (lifecycle tier — babble_tpu/lifecycle): the hashgraph
    # computes WHAT is safe to drop (Hashgraph.prune_below); the store
    # only deletes it and reports its footprint.
    def prune_below(
        self,
        floor_round: int,
        drop_events: List[str],
        drop_rounds: List[int],
        participant_floors: Dict[str, int],
    ) -> None: ...
    def size_stats(self) -> Dict[str, int]: ...
    # Misbehavior evidence (equivocation proofs — node/sentry.py): a flat
    # key -> jsonable-dict ledger, durable on persistent stores.
    def set_evidence(self, key: str, data: dict) -> None: ...
    def all_evidence(self) -> Dict[str, dict]: ...


class InmemStore:
    """All-LRU store; evicts old items, so not suitable for joiners that
    need full history (reference: inmem_store.go:14-48)."""

    def __init__(self, cache_size: int = 10000):
        self._cache_size = cache_size
        self._event_evicted = None
        self._event_cache = LRU(cache_size, self._evicted)
        self._round_cache = LRU(cache_size)
        self._block_cache = LRU(cache_size)
        self._frame_cache = LRU(cache_size)
        self._consensus_cache = RollingIndex("ConsensusCache", cache_size)
        self._tot_consensus_events = 0
        self._peer_set_cache = PeerSetCache()
        self._participant_events_cache = ParticipantEventsCache(cache_size)
        self._roots: Dict[str, Root] = {}
        self._last_round = -1
        self._last_consensus_events: Dict[str, str] = {}
        self._last_block = -1
        # Equivocation evidence (node/sentry.py) — in-memory only here;
        # deliberately NOT an LRU: proofs are tiny, rare, and must never
        # be evicted while the process lives.
        self._evidence: Dict[str, dict] = {}

    def cache_size(self) -> int:
        return self._cache_size

    # -- peer sets ---------------------------------------------------------

    def get_peer_set(self, round: int) -> PeerSet:
        return self._peer_set_cache.get(round)

    def set_peer_set(self, round: int, peer_set: PeerSet) -> None:
        """reference: inmem_store.go:63-89 — also registers participants and
        creates their Roots."""
        self._peer_set_cache.set(round, peer_set)
        for p in peer_set.peers:
            self._add_participant(p)

    def _add_participant(self, p: Peer) -> None:
        if p.id not in self._participant_events_cache.participants.by_id:
            self._participant_events_cache.add_peer(p)
        if p.pub_key_hex not in self._roots:
            self._roots[p.pub_key_hex] = Root()

    def get_all_peer_sets(self) -> Dict[int, List[Peer]]:
        return self._peer_set_cache.get_all()

    def first_round(self, participant_id: int) -> tuple[int, bool]:
        return self._peer_set_cache.first_round(participant_id)

    def repertoire_by_pub_key(self) -> Dict[str, Peer]:
        return self._peer_set_cache.repertoire_by_pub_key

    def repertoire_by_id(self) -> Dict[int, Peer]:
        return self._peer_set_cache.repertoire_by_id

    # -- events ------------------------------------------------------------

    def get_event(self, hash_: str) -> Event:
        ev, ok = self._event_cache.get(hash_)
        if not ok:
            raise StoreError("EventCache", StoreErrorKind.KEY_NOT_FOUND, hash_)
        return ev

    def set_event(self, event: Event) -> None:
        """First insert also appends to the creator's participant index
        (reference: inmem_store.go:122-135)."""
        key = event.hex()
        if key not in self._event_cache:
            self._participant_events_cache.set(event.creator(), key, event.index())
        self._event_cache.add(key, event)

    def on_event_evicted(self, fn) -> None:
        self._event_evicted = fn

    def _evicted(self, _key: str, event: Event) -> None:
        if self._event_evicted is not None:
            self._event_evicted(event)

    def participant_events(self, participant: str, skip: int) -> List[str]:
        return self._participant_events_cache.get(participant, skip)

    def participant_event(self, participant: str, index: int) -> str:
        return self._participant_events_cache.get_item(participant, index)

    def last_event_from(self, participant: str) -> str:
        return self._participant_events_cache.get_last(participant)

    def last_consensus_event_from(self, participant: str) -> str:
        """Returns '' when the participant has no consensus events yet
        (reference: inmem_store.go:154-157 — the Go version swallows the
        missing-key case the same way)."""
        return self._last_consensus_events.get(participant, "")

    def known_events(self) -> Dict[int, int]:
        return self._participant_events_cache.known()

    def consensus_events(self) -> List[str]:
        # get_last_window already returns a fresh copy
        window, _ = self._consensus_cache.get_last_window()
        return window

    def consensus_events_count(self) -> int:
        return self._tot_consensus_events

    def add_consensus_event(self, event: Event) -> None:
        self._consensus_cache.set(event.hex(), self._tot_consensus_events)
        self._tot_consensus_events += 1
        self._last_consensus_events[event.creator()] = event.hex()

    # -- rounds ------------------------------------------------------------

    def get_round(self, round_index: int) -> RoundInfo:
        ri, ok = self._round_cache.get(round_index)
        if not ok:
            raise StoreError(
                "RoundCache", StoreErrorKind.KEY_NOT_FOUND, str(round_index)
            )
        return ri

    def set_round(self, round_index: int, round_info: RoundInfo) -> None:
        self._round_cache.add(round_index, round_info)
        if round_index > self._last_round:
            self._last_round = round_index

    def last_round(self) -> int:
        return self._last_round

    def round_witnesses(self, round_index: int) -> List[str]:
        try:
            return self.get_round(round_index).witnesses()
        except StoreError:
            return []

    def round_events(self, round_index: int) -> int:
        try:
            return len(self.get_round(round_index).created_events)
        except StoreError:
            return 0

    # -- roots -------------------------------------------------------------

    def get_root(self, participant: str) -> Root:
        root = self._roots.get(participant)
        if root is None:
            raise StoreError("RootCache", StoreErrorKind.KEY_NOT_FOUND, participant)
        return root

    # -- blocks ------------------------------------------------------------

    def get_block(self, index: int) -> Block:
        b, ok = self._block_cache.get(index)
        if not ok:
            raise StoreError("BlockCache", StoreErrorKind.KEY_NOT_FOUND, str(index))
        return b

    def set_block(self, block: Block) -> None:
        self._block_cache.add(block.index(), block)
        if block.index() > self._last_block:
            self._last_block = block.index()

    def last_block_index(self) -> int:
        return self._last_block

    # -- frames ------------------------------------------------------------

    def get_frame(self, round_received: int) -> Frame:
        f, ok = self._frame_cache.get(round_received)
        if not ok:
            raise StoreError(
                "FrameCache", StoreErrorKind.KEY_NOT_FOUND, str(round_received)
            )
        return f

    def set_frame(self, frame: Frame) -> None:
        self._frame_cache.add(frame.round, frame)

    # -- lifecycle ---------------------------------------------------------

    def reset(self, frame: Frame) -> None:
        """Clear everything, then rebuild roots/peer-sets from the frame
        (reference: inmem_store.go:286-311)."""
        cs = self._cache_size
        self._peer_set_cache = PeerSetCache()
        self._event_cache = LRU(cs, self._evicted)
        self._round_cache = LRU(cs)
        self._block_cache = LRU(cs)
        self._frame_cache = LRU(cs)
        self._participant_events_cache = ParticipantEventsCache(cs)
        self._last_round = -1
        self._last_block = -1
        self._consensus_cache = RollingIndex("ConsensusCache", cs)
        self._last_consensus_events = {}
        # NOTE: _tot_consensus_events deliberately survives the reset — the
        # reference keeps counting across resets (inmem_store.go:286-311 never
        # touches totConsensusEvents) so consensus indexes stay monotonic.

        self._roots = dict(frame.roots)
        for round, ps in frame.peer_sets.items():
            self.set_peer_set(round, PeerSet(ps))
        self.set_frame(frame)
        # evidence survives resets: a fast-forward must not amnesty an
        # equivocator

    # -- compaction --------------------------------------------------------

    def prune_below(
        self,
        floor_round: int,
        drop_events: List[str],
        drop_rounds: List[int],
        participant_floors: Dict[str, int],
    ) -> None:
        """Drop compacted history (lifecycle tier). Blocks, peer-sets,
        roots, evidence and the consensus counters always survive — only
        the listed events/rounds and frames below the floor go. The
        participant index is already a bounded rolling window, so
        ``participant_floors`` only matters to durable stores."""
        for h in drop_events:
            self._event_cache.remove(h)
        for r in drop_rounds:
            self._round_cache.remove(r)
        for fr in [k for k in self._frame_cache.keys() if k < floor_round]:
            self._frame_cache.remove(fr)

    def size_stats(self) -> Dict[str, int]:
        """Retained-object counts + byte footprint (0 for a pure in-memory
        store) — the lifecycle_* gauges and healthview columns read this."""
        return {
            "events": len(self._event_cache),
            "rounds": len(self._round_cache),
            "blocks": len(self._block_cache),
            "frames": len(self._frame_cache),
            "store_bytes": 0,
            "free_bytes": 0,
        }

    # -- evidence ----------------------------------------------------------

    def set_evidence(self, key: str, data: dict) -> None:
        self._evidence[key] = data

    def all_evidence(self) -> Dict[str, dict]:
        return dict(self._evidence)

    def close(self) -> None:
        pass

    def store_path(self) -> str:
        return ""
