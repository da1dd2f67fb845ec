"""The script caches' bounded map (reference: crypto/txscript/src/caches.rs:14-55).

Two maps, both owned by the ``TransactionValidator``:

- ``SigCache``, the signature cache, keyed by (kind, sig, msg, pubkey) ->
  bool, shared across the validator so repeated relay/mempool/block
  validations of the same signature skip the device round-trip;
- ``VerdictMemo``, the script-verdict memo over it (txscript/batch.py
  ``_memo_key``), keyed by a transaction with its signature scripts over the
  outputs it spends -> the number of signature checks its all-valid verdict
  stood for, so a transaction collected again is not classified, parsed or
  hashed again.

``SigCache`` is bounded with random eviction, exactly like the reference's
IndexMap+swap_remove scheme (the reference wraps it in a RwLock; here a plain
Lock — the parallel VM fallback lane and the RPC handlers read and write it
from their own threads, and the multi-step eviction must stay atomic).  A
stored value is never None: None is ``get``'s "not held".

``VerdictMemo`` is the same map at the same bound, with one exception: the
entries the consensus block path inserted or hit since the last virtual
resolution ended (the running *epoch*) are never evicted while it runs;
eviction takes an older entry at random.  A virtual resolution qualifies
tip chain after tip chain, and each chain's mergesets re-ask nearly every
transaction the ones before it asked: on a wide DAG more than the bound, so
random eviction at the bound dropped a third of them between two asks.  The
resolution's end, also one that raised, closes the epoch and trims the map
back to its bound, earlier epochs first.  Only consensus marks a checker as
the block path (``BatchScriptChecker``'s ``block_path``): mempool admission,
RPC and the ingest waves insert at the bound as the signature cache does.
So the bound is exceeded only by what one resolution's blocks asked.
"""

from __future__ import annotations

import random
import threading

from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.utils.sync import ranked_lock

_OVER_BOUND = REGISTRY.counter(
    "txscript_tx_memo_over_bound",
    help="entries the script-verdict memo held above its bound when a virtual resolution closed its epoch: "
    "what random eviction at the bound would have put at risk while the resolution ran",
)


class SigCache:
    def __init__(self, size: int = 10_000, seed: int | None = None):
        assert size > 0
        self.size = size
        self._map: dict[tuple, int] = {}  # a verdict (bool) or a count, never None
        self._keys: list[tuple] = []
        self._rng = random.Random(seed)
        self._lock = ranked_lock("txscript.cache")
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple):
        with self._lock:
            v = self._map.get(key)
            if v is None:
                self.misses += 1
            else:
                self.hits += 1
            return v

    def insert(self, key: tuple, value: int) -> None:
        with self._lock:
            if key in self._map:
                self._map[key] = value
                return
            if len(self._keys) == self.size:
                # random eviction with swap-remove (caches.rs:46-55)
                i = self._rng.randrange(self.size)
                old = self._keys[i]
                del self._map[old]
                self._keys[i] = self._keys[-1]
                self._keys.pop()
            self._keys.append(key)
            self._map[key] = value


class VerdictMemo(SigCache):
    """The script-verdict memo: ``SigCache``'s map, whose running epoch is
    evicted last.  ``get`` is the cache's own (so a hit moves nothing);
    the block path hands its hits to ``protect`` once a dispatch round.

    ``_keys[:_n_old]`` are the entries of earlier epochs and those a traffic
    class inserted, ``_keys[_n_old:]`` the running epoch's; ``_pos`` holds
    each key's index, so that moving an entry across the boundary, or
    evicting one, is a swap or two and a pop."""

    def __init__(self, size: int = 10_000, seed: int | None = None):
        super().__init__(size, seed)
        self._pos: dict[tuple, int] = {}
        self._n_old = 0

    def insert(self, key: tuple, value: int, protect: bool = False) -> None:
        """``protect``: the block path's insert, held until the epoch closes."""
        with self._lock:
            i = self._pos.get(key)
            if i is not None:
                self._map[key] = value
                if protect and i < self._n_old:
                    self._promote(i)
                return
            if len(self._keys) >= self.size and self._n_old:
                self._evict_old()
            self._pos[key] = len(self._keys)
            self._keys.append(key)
            self._map[key] = value
            if not protect:
                self._swap(len(self._keys) - 1, self._n_old)
                self._n_old += 1

    def protect(self, keys) -> None:
        """Move the held ones of ``keys`` (the block path's hits) into the running epoch."""
        with self._lock:
            for key in keys:
                i = self._pos.get(key)
                if i is not None and i < self._n_old:
                    self._promote(i)

    def close_epoch(self) -> int:
        """End the running epoch: trim to the bound, entries of earlier
        epochs first, then the closed epoch's at random.  Returns (and
        counts) what the memo held above its bound before the trim."""
        with self._lock:
            over = len(self._keys) - self.size
            while len(self._keys) > self.size and self._n_old:
                self._evict_old()
            self._n_old = len(self._keys)
            while len(self._keys) > self.size:
                self._evict_old()
        over = max(over, 0)
        _OVER_BOUND.inc(over)
        return over

    def _swap(self, i: int, j: int) -> None:
        keys = self._keys
        keys[i], keys[j] = keys[j], keys[i]
        self._pos[keys[i]] = i
        self._pos[keys[j]] = j

    def _promote(self, i: int) -> None:
        self._n_old -= 1
        self._swap(i, self._n_old)

    def _evict_old(self) -> None:
        # a random old entry to the boundary, the boundary out of the old
        # part, then swap-remove it from the end (caches.rs:46-55, per part)
        self._n_old -= 1
        self._swap(self._rng.randrange(self._n_old + 1), self._n_old)
        self._swap(self._n_old, len(self._keys) - 1)
        key = self._keys.pop()
        del self._pos[key], self._map[key]
