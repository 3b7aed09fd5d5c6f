"""A cache of derived device arrays held under a byte budget."""

from collections import OrderedDict

# device bytes of derived arrays one cache holds: a filtered count's end
# weights a (pair, predicate binding) (idx/graph_csr.py), a filtered kNN's
# slot mask a (predicate binding, vector snapshot) (idx/knn.py)
DEVICE_CACHE_BYTES = 256 << 20


class ByteBudgetCache:
    """Entries in order of last use, each with the bytes it holds, dropped
    oldest first once their sum passes `budget` (the newest always stays).
    What makes an entry good (the generation or the objects it was made
    from) is the caller's to check on what `get` returns; a `put` under the
    same key replaces. No lock of its own: the caller holds its own."""

    def __init__(self, budget: int = DEVICE_CACHE_BYTES):
        self.budget = budget
        self._d: "OrderedDict[tuple, tuple]" = OrderedDict()  # key -> (entry, bytes)

    def get(self, key):
        got = self._d.get(key)
        if got is None:
            return None
        self._d.move_to_end(key)
        return got[0]

    def put(self, key, entry, nbytes: int) -> None:
        self._d[key] = (entry, int(nbytes))
        self._d.move_to_end(key)
        held = sum(b for _, b in self._d.values())
        while held > self.budget and len(self._d) > 1:
            held -= self._d.popitem(last=False)[1][1]

    def forget(self, stale) -> None:
        """Drop every entry that `stale(key, entry)` selects."""
        for k in [k for k, (e, _) in self._d.items() if stale(k, e)]:
            del self._d[k]
