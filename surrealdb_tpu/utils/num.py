"""Shared numeric helpers for device-shape padding."""


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>=1). Mirror/kernel static dims round
    through this so steady writes never change compiled shapes; the slot
    count of a destination-sorted path array follows path_slots instead."""
    return 1 << max(int(x) - 1, 0).bit_length()


def tile_slices(n: int, tile: int):
    """Yield (lo, hi) covering [0, n) in fixed-size tiles (last may be short);
    pair with pad_tail so every kernel call keeps one static shape."""
    for lo in range(0, n, tile):
        yield lo, min(lo + tile, n)


def pad_tail(arr, tile: int):
    """Zero-pad the leading dim of a host array up to `tile` rows, so a tail
    chunk reuses the same compiled kernel shape as full chunks."""
    import numpy as np

    n = arr.shape[0]
    if n == tile:
        return arr
    pad = np.zeros((tile - n,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def dispatch_tile(nq: int, cap: int = None) -> int:
    """Query-batch tile size with a SMALL shape vocabulary {1, 8, cap}: a
    coalesced batch can arrive at any size, and every distinct padded shape
    is a separate XLA compile (seconds each) — three shapes
    keep the compile cache tiny while bounding padding waste at 8x only for
    2..7-query batches whose kernels are small anyway. `cap` defaults to the
    dispatcher's width cap (cnf.DISPATCH_MAX_WIDTH), so the widest batch the
    coalescer can hand a runner is exactly the largest pre-warmed tile."""
    if cap is None:
        from surrealdb_tpu import cnf

        cap = cnf.DISPATCH_MAX_WIDTH
    if nq <= 1:
        return 1
    t = 8 if nq <= 8 else cap
    return max(1, min(t, cap))


def warm_tile_sizes(cap: int = None):
    """The tile vocabulary background shape-warming should pre-compile:
    every size dispatch_tile can return for the current width cap."""
    if cap is None:
        from surrealdb_tpu import cnf

        cap = cnf.DISPATCH_MAX_WIDTH
    return (1, 8, cap) if cap > 8 else ((1, cap) if cap > 1 else (1,))


# An int32 tile is 8 sublanes and a batched count's frontier is
# [lanes, n + 1] int32 with the lanes on the second-minor axis: below 8
# nothing is saved (the sparse kernel alone at SNB SF3's composed shapes on
# a v5e: 6.51 ms at 8 lanes, 6.71 at 4, 6.68 at 2, 17.98 at 1, and 7.70 at
# 16; PERF.md section 6, PR 37), and the shape set stays at four.
COUNT_LANES_MIN = 8


def count_lanes(riders: int) -> int:
    """Lane count of a batched graph count: the riders of the batch rounded
    up to a power of two, no fewer than COUNT_LANES_MIN. The kNN path's
    {1, 8, cap} is dispatch_tile's; this is the one rule of the graph count
    runners, their warm-up and their audit shapes."""
    return max(next_pow2(riders), COUNT_LANES_MIN)


def path_slots(paths: int) -> int:
    """Slot count of a destination-sorted path array (`csrc`, what the
    sparse count kernel's row gathers, prefix sums and transposes are as
    long as): the paths rounded up to a sixteenth of the power of two above
    them, so eight shapes an octave and at most an eighth of the slots hold
    the sentinel where a power of two left up to half (SNB SF3: 1,130,494
    paths in 1,179,648 slots, not 2,097,152; PERF.md section 6, PR 34).
    Up to 1,024 paths the sixteenth is under a row of 128 lanes and the
    power of two stays. The price is a new compiled shape every 6-12% of
    growth in paths where there was one a doubling."""
    top = next_pow2(paths)
    q = top // 16
    return top if q < 128 else -(-paths // q) * q


def count_lane_set(cap: int = None):
    """Every lane count count_lanes can return for batches of up to `cap`
    riders (default: the dispatcher's width cap, cnf.DISPATCH_MAX_WIDTH):
    (8, 16, 32, 64) at a cap of 64. What warm-up compiles, no more."""
    if cap is None:
        from surrealdb_tpu import cnf

        cap = cnf.DISPATCH_MAX_WIDTH
    return tuple(sorted({count_lanes(r) for r in range(1, max(cap, 1) + 1)}))
