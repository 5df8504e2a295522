"""Last-known positions of tracked entities, by entity id.

The TPU controller's ``_last_positions``: one mapping with two stores
behind it. An entity written through the ordinary update path
(``notify``, ``observe_entity``, ``track_entity``: wire entities,
channel-backed agents) is a dict entry holding the ``SpatialInfo`` that
was written. A simulated agent's position as the last census fetched it
is a ROW: the census's ``float32[capacity, 3]`` positions, indexed by
the engine's slot, beside the entity id each row was fetched for. A
census then costs two array writes however many agents it covers, and
an agent's ``SpatialInfo`` is built when somebody reads it
(``_build_crossing``, follow interests, failover's re-hosting,
``on_agents_adopted``).

An id lives in one store at a time and the last writer wins, as in the
dict this replaces: item assignment drops the id's row, a census drops
the dict entries of the agents it covers. A row is the id's only while
the engine still has the id in that slot, so a freed or re-bound slot
reads as absent whatever its row holds.

Threading (doc/concurrency.md): the GLOBAL tick loop, like the
controller that owns it.
"""

from __future__ import annotations

from collections.abc import MutableMapping
from typing import Iterator, Optional

import numpy as np

from .controller import SpatialInfo

_NO_ID = -1


class LastPositions(MutableMapping):
    def __init__(self):
        self._infos: dict[int, SpatialInfo] = {}
        self._slot_of = None  # the engine's entity id -> slot lookup
        self._rows: Optional[np.ndarray] = None    # float32[capacity, 3]
        self._row_id: Optional[np.ndarray] = None  # int64[capacity]

    def bind(self, engine) -> None:
        """The engine whose slots index the rows (controller load)."""
        self._slot_of = engine.slot_of_entity
        self._rows = np.zeros((engine.entity_capacity, 3), np.float32)
        self._row_id = np.full(engine.entity_capacity, _NO_ID, np.int64)

    def absorb_census(self, slots: np.ndarray, ids: np.ndarray,
                      positions: np.ndarray) -> None:
        """One census: ``ids`` and ``positions`` are the entity ids and
        the rows of ``slots``, an index array. Afterwards exactly these
        agents read from their rows; a slot the census left out (it
        changed owner in flight, ``StepChurn``) has no row, and its
        owner keeps whatever the ordinary path wrote."""
        self._rows[slots] = positions
        self._row_id.fill(_NO_ID)
        self._row_id[slots] = ids
        if self._infos:
            # Python work over the ordinary path's entries alone (wire
            # entities, channel-backed agents), never over the census.
            for eid in [e for e in self._infos if self._row_of(e) is not None]:
                del self._infos[eid]

    def _row_of(self, entity_id: int) -> Optional[int]:
        if self._slot_of is None:
            return None
        slot = self._slot_of(entity_id)
        if slot is not None and self._row_id[slot] == entity_id:
            return slot
        return None

    def get(self, entity_id: int, default=None):
        info = self._infos.get(entity_id)
        if info is not None:
            return info
        slot = self._row_of(entity_id)
        if slot is None:
            return default
        return SpatialInfo(*self._rows[slot].tolist())

    def __getitem__(self, entity_id: int) -> SpatialInfo:
        info = self.get(entity_id)
        if info is None:
            raise KeyError(entity_id)
        return info

    def __contains__(self, entity_id) -> bool:
        return entity_id in self._infos or self._row_of(entity_id) is not None

    def __setitem__(self, entity_id: int, info: SpatialInfo) -> None:
        slot = self._row_of(entity_id)
        if slot is not None:
            self._row_id[slot] = _NO_ID
        self._infos[entity_id] = info

    def __delitem__(self, entity_id: int) -> None:
        slot = self._row_of(entity_id)
        if slot is None:
            del self._infos[entity_id]
        else:
            self._row_id[slot] = _NO_ID

    def __iter__(self) -> Iterator[int]:
        yield from list(self._infos)
        if self._row_id is not None:
            for eid in self._row_id[self._row_id != _NO_ID].tolist():
                if self._row_of(eid) is not None:
                    yield eid

    def __len__(self) -> int:
        return sum(1 for _ in self)
