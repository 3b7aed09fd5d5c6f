"""Grouped integer aggregates over a table's columns held on the device.

Role of the reference's per-record GROUP BY collector (reference:
core/src/dbs/group.rs: every record's aggregate arguments evaluated and
pushed into a per-group accumulator) re-designed TPU-first: the columns a
statement names stand in HBM as int32 planes (idx/column_mirror.py: the
values themselves, or dense codes of the sorted distinct values), and ONE
sweep of them answers every rider of a launch: per rider a mask from its
own constants, per row a group id from the key columns' codes, and per
(rider, group) the count and the exact sum of each integer expression.

Exactness on a chip whose integers are 32 bits wide and with
`jax_enable_x64` off:

- A summed expression (`+`, `-`, `*` of planes and integer constants) is
  a polynomial of the planes, and its sum the constants' combination of
  the sums of its MONOMIALS (products of planes). The host expands it
  (ops/pipeline.py) and the kernel sums monomials only, so no constant of
  an expression reaches the program and riders whose constants differ
  share one sweep. A monomial is evaluated a row in two's complement
  modulo 2**64, as a (low, high) pair of uint32 words; the high word of a
  product comes from 16-bit halves.
- Each value is cut into 8 limbs of 8 bits. A limb is exact in bfloat16,
  and so is the 0/1 of the (rider, group) one-hot, so the MXU's product of
  the two with float32 accumulation is the exact limb sum while it stays
  under 2**24: over a block of at most 65,536 rows (255 x 65,536 < 2**24).
- Block sums are added as int32 over at most 127 blocks (a superblock:
  127 x 2**24 < 2**31) and the host adds the superblocks in Python ints,
  reassembles `sum_j limb_j << 8j` modulo 2**64, combines the monomials'
  sums under the rider's coefficients modulo 2**64 and reads the result
  as signed. The route rule (ops/pipeline.py) admits a statement only
  where the true sum is provably inside int64, so the answer is the exact
  integer whatever the monomials' own sums wrapped to.

The count is the first limb sum of one more word, the constant 1; the
position of a group's first row (the host route numbers groups by first appearance) is a masked
minimum over the same one-hot.

Compiled shapes come from the padded row count, the lane count of the
launch (utils/num.count_lanes), the group slots (the power of two above the
product of the key columns' distinct counts) and the statement's shape:
which planes the predicate compares with which operators, which planes key
the groups, the monomials. Every constant the program sees is an operand:
a rider's predicate constants a row of `consts`, the key strides `strides`.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK_ROWS = 65_536  # 255 x 65,536 < 2**24: a block's limb sum is exact in float32
SUPER_BLOCKS = 127  # 127 x 2**24 < 2**31: a superblock's limb sum is exact in int32
LIMBS = 8  # 8-bit limbs of a 64-bit value
GROUPS_MAX = 256  # group slots a launch may carry a rider
CHUNK_ELEMS = 1 << 25  # elements of a scan step's (rider, group) x row one-hot: 8 blocks at 8 lanes x 8 groups
_INT32_MAX = np.iinfo(np.int32).max
_OPS = {"<": jnp.less, "<=": jnp.less_equal, "=": jnp.equal, ">=": jnp.greater_equal, ">": jnp.greater}


def blocking(slots: int, hot_rows: int = 1) -> Tuple[int, int, int]:
    """(superblocks, blocks a superblock, rows a block) of a plane of
    `slots` padded rows (utils/num.path_slots: a power of two up to 1,024,
    else a multiple of a sixteenth of the power of two above the rows)
    swept against a one-hot of `hot_rows` (lanes x group slots) rows. A
    superblock holds at most 127 x 65,536 rows, which is what keeps its
    limb sums inside int32; a block is at most 65,536 rows, and is halved
    (the superblock keeping its rows) until one block's one-hot is under
    CHUNK_ELEMS elements: 16 lanes x 256 groups sweep blocks of 8,192."""
    block = min(BLOCK_ROWS, slots)
    while slots % block:
        block //= 2
    blocks = slots // block
    per = next(d for d in range(min(blocks, SUPER_BLOCKS), 0, -1) if blocks % d == 0)
    supers = blocks // per
    while hot_rows * block > CHUNK_ELEMS and block % 2 == 0:
        block, per = block // 2, per * 2
    return supers, per, block


def _mulhi(a, b):
    """High 32 bits of the 64-bit product of two uint32 arrays."""
    a0, a1, b0, b1 = a & 0xFFFF, a >> 16, b & 0xFFFF, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    return p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)


def _product(mono, cols):
    """(low, high) uint32 words of the product of the planes `mono` names,
    each sign-extended to 64 bits, modulo 2**64."""
    lo = hi = None
    for plane in mono:
        p = cols[plane]
        blo, bhi = lax.bitcast_convert_type(p, jnp.uint32), jnp.where(p < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        if lo is None:
            lo, hi = blo, bhi
        else:
            lo, hi = lo * blo, _mulhi(lo, blo) + lo * bhi + hi * blo
    return lo, hi


def _limbs(words):
    """The 8-bit limbs of uint32 `words` ([chunk, block] each), least
    first, as one bfloat16 [4 x words, chunk, block] operand: a row a limb,
    stacked (one shift of the stacked words by a broadcast [1, 4, 1, 1] is
    the same values and 1.90 ms where this is 1.34: XLA then writes the
    broadcast words out; PERF.md section 6, PR 40)."""
    return jnp.stack([((w >> (8 * j)) & 0xFF).astype(jnp.int32).astype(jnp.bfloat16) for w in words for j in range(4)])


def chunking(per: int, block: int, hot_rows: int) -> int:
    """Blocks a scan step sweeps side by side: the largest divisor of the
    superblock's `per` blocks that keeps the step's one-hot under
    CHUNK_ELEMS elements (`blocking` has cut the block so that one fits: a
    step's one-hot and its compare stay under ~100 MB however many lanes
    and groups ride). At Q1's 64 one-hot rows over 48 blocks: 1
    block a step 3.49 ms, 8 blocks 1.34, all 48 at once 1.45 (PERF.md
    section 6, PR 40)."""
    cap = max(CHUNK_ELEMS // (hot_rows * block), 1)
    return next(d for d in range(min(per, cap), 0, -1) if per % d == 0)


@functools.partial(jax.jit, static_argnames=("pred", "keys", "exprs", "groups"))
def grouped_aggregate(planes, n_rows, consts, strides, *, pred, keys, exprs, groups):
    """One sweep of `planes` (int32 [slots] each, rows past `n_rows` pad)
    for the riders of `consts` (int32 [lanes, max(terms, 1)]).

    Static: `pred` ((plane, operator), ...) the conjunction every rider
    holds its own constants to; `keys` the planes whose codes times
    `strides` (int32 values) add up to a row's group id; `exprs` the
    monomials summed, each the planes whose product it is; `groups` the
    group slots.

    Returns int32 [superblocks, lanes x groups, 4 + 8 x len(exprs) + 1]:
    the limb sums of the word 1 (so the first is the count and three are
    0), each monomial's 8 limb sums, and last the least row index of the
    (rider, group) in that superblock (int32 max where none)."""
    slots = planes[0].shape[0]
    lanes = consts.shape[0]
    supers, per, block = blocking(slots, lanes * groups)
    chunk = chunking(per, block, lanes * groups)
    cols4 = tuple(p.reshape(supers, per // chunk, chunk, block) for p in planes)
    rows4 = jnp.arange(slots, dtype=jnp.int32).reshape(supers, per // chunk, chunk, block)
    slot_ids = jnp.arange(groups, dtype=jnp.int32)
    width = 4 * (1 + 2 * len(exprs))

    def sweep(carry, xs):
        acc, first = carry
        cols, idx = xs  # [chunk, block] each
        mask = jnp.broadcast_to(idx < n_rows, (lanes, chunk, block))
        for t, (plane, op) in enumerate(pred):
            mask = mask & _OPS[op](cols[plane][None], consts[:, t][:, None, None])
        if keys:
            gid = sum(cols[plane] * strides[k] for k, plane in enumerate(keys))
            key = jnp.where(mask, gid[None], groups)
            hot = (key[:, None] == slot_ids[None, :, None, None]).reshape(lanes * groups, chunk, block)
        else:
            hot = mask
        words = [jnp.ones((chunk, block), jnp.uint32)]
        for e in exprs:
            words += _product(e, cols)
        part = jnp.einsum(
            "kcb,wcb->ckw", hot.astype(jnp.bfloat16), _limbs(words),
            preferred_element_type=jnp.float32,
        )  # a block's limb sum: under 2**24, exact in float32
        at = jnp.min(jnp.where(hot, idx[None], _INT32_MAX), axis=(1, 2))
        return (acc + part.astype(jnp.int32).sum(axis=0, dtype=jnp.int32), jnp.minimum(first, at)), None

    def superblock(xs):
        zero = (jnp.zeros((lanes * groups, width), jnp.int32), jnp.full((lanes * groups,), _INT32_MAX, jnp.int32))
        (acc, first), _ = lax.scan(sweep, zero, xs)
        return jnp.concatenate([acc, first[:, None]], axis=1)

    return lax.map(superblock, (cols4, rows4))


def unpack_results(out: np.ndarray, riders: int, groups: int, exprs: int) -> list:
    """The device output as, a rider, (counts [groups], first rows [groups],
    sums: a list of Python ints [groups] a monomial, each its sum modulo
    2**64 read as signed)."""
    total = out[:, :, :-1].astype(np.int64).sum(axis=0)  # superblocks: each limb sum under 2**31
    first = out[:, :, -1].min(axis=0)
    # sum_j limb_j << 8j in uint64 wraps modulo 2**64 by itself; read as signed
    limbs = total[:, 4:].astype(np.uint64).reshape(len(total), exprs, LIMBS)
    sums = (limbs << (8 * np.arange(LIMBS, dtype=np.uint64))).sum(axis=2, dtype=np.uint64).view(np.int64)
    res = []
    for r in range(riders):
        mine = slice(r * groups, (r + 1) * groups)
        res.append((total[mine, 0], first[mine], [sums[mine, e].tolist() for e in range(exprs)]))
    return res


def graftcheck_sites():
    """Audit contract of the grouped aggregate (compile_log subsystem
    `column_agg`, launched by ops/pipeline.py): TPC-H Q1's shape (one
    comparison, two key planes, the six monomials of its five expressions,
    8 group slots) and Q6's (five comparisons, no key, one product, one
    slot) at 8 lanes, and a keyed count at 16 lanes and 256 group slots
    over planes of several superblocks (its blocks cut to 8,192 rows)."""
    q1 = ((0,), (1,), (1, 2), (1, 3), (1, 2, 3), (2,))  # quantity, price, price x discount, x tax, x both, discount

    def build(shape):
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        args = (
            tuple(i32(shape["slots"]) for _ in range(shape["planes"])), i32(), i32(shape["lanes"], max(len(shape["pred"]), 1)),
            i32(max(len(shape["keys"]), 1)),
        )
        fn = functools.partial(grouped_aggregate, pred=shape["pred"], keys=shape["keys"], exprs=shape["exprs"],
                               groups=shape["groups"])
        return fn, args

    shapes = [
        {"label": "q1_r8_g8", "slots": 196_608, "planes": 7, "lanes": 8, "groups": 8,
         "pred": ((4, "<="),), "keys": (5, 6), "exprs": q1},
        {"label": "q6_r8_g1", "slots": 196_608, "planes": 4, "lanes": 8, "groups": 1,
         "pred": ((3, ">="), (3, "<"), (2, ">="), (2, "<="), (0, "<")), "keys": (), "exprs": ((1, 2),)},
        {"label": "count_r16_g256", "slots": 1 << 24, "planes": 2, "lanes": 16, "groups": 256,
         "pred": ((0, "="),), "keys": (1,), "exprs": ()},
    ]
    return [
        {
            "subsystem": "column_agg",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            # one packed output: the count, the limb sums, the first row
            "out_dtypes": ("int32",),
            "shapes": shapes,
            "build": build,
        }
    ]
