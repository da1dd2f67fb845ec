"""The script caches' bounded map (reference: crypto/txscript/src/caches.rs:14-55).

One class, two uses, both owned by the ``TransactionValidator``:

- the signature cache, keyed by (kind, sig, msg, pubkey) -> bool, shared
  across the validator so repeated relay/mempool/block validations of the same
  signature skip the device round-trip;
- the script-verdict memo over it (txscript/batch.py ``_memo_key``), keyed by
  a transaction with its signature scripts over the outputs it spends -> the
  number of signature checks its all-valid verdict stood for, so a
  transaction collected again is not classified, parsed or hashed again.

Bounded with random eviction, exactly like the reference's
IndexMap+swap_remove scheme (the reference wraps it in a RwLock; here a plain
Lock — the parallel VM fallback lane and the RPC handlers read and write it
from their own threads, and the multi-step eviction must stay atomic).  A
stored value is never None: None is ``get``'s "not held".
"""

from __future__ import annotations

import random
import threading

from kaspa_tpu.utils.sync import ranked_lock


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
