"""In-memory MVCC ordered KV store.

Role of the reference's `mem` backend (reference: core/src/kvs/mem/mod.rs) but
designed differently: a dict of key -> version chain gives true snapshot
isolation (each transaction reads as-of its begin version) plus versioned
reads (`scan_all_versions` analog), with optimistic first-committer-wins
conflict detection at commit — the semantics SurrealDB gets from surrealkv.
Ordering for range scans comes from a SortedList of keys maintained alongside
the dict: large commit batches merge into it wholesale (SortedList.update's
bulk path) instead of paying one insort per key — the difference between
~7µs and ~0.5µs per key during bulk ingest. Single-process; commits are
applied atomically (no awaits inside).
"""

from __future__ import annotations

from surrealdb_tpu.utils import locks as _locks
from typing import Dict, List, Optional, Tuple

from sortedcontainers import SortedList

from surrealdb_tpu.err import TxConflictError
from .api import KV, BackendDatastore, BackendTransaction


class MemDatastore(BackendDatastore):
    def __init__(self):
        # key -> list[(version, value|None)] ascending by version; None = tombstone
        self.data: Dict[bytes, list] = {}
        self.sorted_keys: SortedList = SortedList()
        self.version: int = 0
        self.lock = _locks.RLock("kvs.mem")
        self.active: Dict[int, int] = {}  # snapshot version -> refcount

    # -- snapshots ---------------------------------------------------------
    def _acquire_snapshot(self) -> int:
        with self.lock:
            v = self.version
            self.active[v] = self.active.get(v, 0) + 1
            return v

    def _release_snapshot(self, v: int) -> None:
        with self.lock:
            n = self.active.get(v, 0) - 1
            if n <= 0:
                self.active.pop(v, None)
            else:
                self.active[v] = n

    def transaction(self, write: bool) -> "MemTransaction":
        return MemTransaction(self, write)

    # -- version-chain helpers --------------------------------------------
    def _read_at(self, key: bytes, snapshot: int) -> Optional[bytes]:
        with self.lock:  # gc() truncates chains in place
            chain = self.data.get(key)
            if not chain:
                return None
            # chains are short; linear scan from the end
            for ver, val in reversed(chain):
                if ver <= snapshot:
                    return val
            return None

    def _latest_version(self, key: bytes) -> int:
        with self.lock:
            chain = self.data.get(key)
            return chain[-1][0] if chain else 0

    def gc(self) -> None:
        """Drop version-chain entries older than the oldest active snapshot."""
        with self.lock:
            horizon = min(self.active) if self.active else self.version
            dead = []
            for key, chain in self.data.items():
                if len(chain) > 1:
                    keep_from = 0
                    for i in range(len(chain) - 1, -1, -1):
                        if chain[i][0] <= horizon:
                            keep_from = i
                            break
                    if keep_from > 0:
                        del chain[:keep_from]
                if len(chain) == 1 and chain[0][1] is None and chain[0][0] <= horizon:
                    dead.append(key)
            for key in dead:
                del self.data[key]
                self.sorted_keys.remove(key)


_ABSENT = object()  # "key had no local write" marker in the undo log


class MemTransaction(BackendTransaction):
    def __init__(self, store: MemDatastore, write: bool):
        super().__init__(write)
        self.store = store
        self.snapshot = store._acquire_snapshot()
        self.writes: Dict[bytes, Optional[bytes]] = {}
        # savepoint undo log: (key, previous write-buffer state) per
        # mutation while recording; None = not recording (zero overhead)
        self.undo: Optional[List[tuple]] = None

    # -- lifecycle ---------------------------------------------------------
    def commit(self) -> None:
        self._check_open(self.write and bool(self.writes))
        store = self.store
        with store.lock:
            # first-committer-wins: conflict iff any written key changed
            # after our snapshot. Nothing at all committed since our snapshot
            # (store.version unchanged) ⇒ no key can have — skip the scan;
            # bulk ingest commits hundreds of thousands of keys per txn.
            data = store.data
            if store.version != self.snapshot:
                for key in self.writes:
                    chain = data.get(key)
                    if chain is not None and chain[-1][0] > self.snapshot:
                        self._finish()
                        raise TxConflictError()
            if self.writes:
                store.version += 1
                ver = store.version
                # the MVCC version this commit's writes landed at: the
                # column-mirror delta feed uses it as the served snapshot
                # floor, the changefeed batch reader as its expansion point
                self.commit_version = ver
                new_keys = []
                for key, val in self.writes.items():
                    chain = data.get(key)
                    if chain is None:
                        data[key] = [(ver, val)]
                        new_keys.append(key)
                    else:
                        chain.append((ver, val))
                if new_keys:
                    # bulk merge: SortedList.update sorts the batch and
                    # merges wholesale when it is large relative to the list
                    store.sorted_keys.update(new_keys)
        self._finish()

    def version_of(self, key: bytes) -> Optional[int]:
        """MVCC version of the newest committed chain entry for `key`
        (None when absent) — the changefeed reader resolves a bulk entry's
        expansion point from the entry key's own commit version."""
        with self.store.lock:
            chain = self.store.data.get(key)
            return chain[-1][0] if chain else None

    def oldest_retained(self, key: bytes) -> Optional[bytes]:
        """Oldest committed value still in `key`'s chain (gc() compacts
        chains from the front) — the changefeed bulk-entry expansion
        fallback when its pinned version predates the GC horizon."""
        with self.store.lock:
            chain = self.store.data.get(key)
            return chain[0][1] if chain else None

    def cancel(self) -> None:
        if not self.done:
            self._finish()

    def _finish(self) -> None:
        self.done = True
        self.store._release_snapshot(self.snapshot)
        self.writes = {}

    # -- point ops ---------------------------------------------------------
    def get(self, key: bytes, version: Optional[int] = None) -> Optional[bytes]:
        self._check_open()
        if version is not None:
            return self.store._read_at(key, version)
        if key in self.writes:
            return self.writes[key]
        return self.store._read_at(key, self.snapshot)

    def set(self, key: bytes, val: bytes) -> None:
        self._check_open(True)
        if self.undo is not None:
            self.undo.append((key, self.writes.get(key, _ABSENT)))
        self.writes[key] = val

    def delete(self, key: bytes) -> None:
        self._check_open(True)
        if self.undo is not None:
            self.undo.append((key, self.writes.get(key, _ABSENT)))
        self.writes[key] = None

    # -- range ops ---------------------------------------------------------
    _RANGE_CHUNK = 4096

    def _merged_range(self, beg: bytes, end: bytes, want: int = -1):
        """Iterate live (key, value) pairs in [beg, end) merging local writes.

        Committed keys are pulled from the SortedList in chunks (of `want`
        keys where the caller wants few: a probe with a limit of 1 pays for
        1) rather than materialized whole: `batch()` walks multi-million-key ranges
        (mirror builds, exports) by repeated scans with an advancing cursor,
        and materializing the full remaining range per scan made that
        quadratic — ~10^9 list appends over a 12M-posting range. Chunked
        irange keeps every scan O(limit).
        """
        from itertools import islice

        store = self.store
        local = sorted(k for k in self.writes if beg <= k < end)
        li = 0
        n_local = len(local)
        cursor = beg
        exhausted = False
        step = self._RANGE_CHUNK if want < 0 else max(1, min(want, self._RANGE_CHUNK))
        while not exhausted:
            with store.lock:
                committed = list(
                    islice(store.sorted_keys.irange(cursor, end, inclusive=(True, False)), step)
                )
                # the chunk's values at this snapshot, under the one
                # acquisition that listed its keys (a mirror build walks
                # millions of keys: a lock and a call a key was most of it);
                # what a snapshot sees never changes, so reading here or at
                # the yield is the same
                data, snap = store.data, self.snapshot
                visible = []
                for k in committed:
                    v = None
                    for ver, val in reversed(data.get(k) or ()):
                        if ver <= snap:
                            v = val
                            break
                    visible.append(v)
            if len(committed) < step:
                exhausted = True
            for k, seen in zip(committed, visible):
                while li < n_local and local[li] < k:
                    lk = local[li]
                    li += 1
                    v = self.writes[lk]
                    if v is not None:
                        yield lk, v
                if li < n_local and local[li] == k:
                    li += 1
                    v = self.writes[k]
                else:
                    v = seen
                if v is not None:
                    yield k, v
            if committed:
                cursor = committed[-1] + b"\x00"
        while li < n_local:
            lk = local[li]
            li += 1
            v = self.writes[lk]
            if v is not None:
                yield lk, v

    def keys(self, beg: bytes, end: bytes, limit: int = -1) -> List[bytes]:
        self._check_open()
        out = []
        for k, _ in self._merged_range(beg, end, limit):
            out.append(k)
            if limit >= 0 and len(out) >= limit:
                break
        return out

    def scan(self, beg: bytes, end: bytes, limit: int = -1) -> List[KV]:
        self._check_open()
        out = []
        for kv in self._merged_range(beg, end, limit):
            out.append(kv)
            if limit >= 0 and len(out) >= limit:
                break
        return out
