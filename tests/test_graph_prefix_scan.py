"""The sparse count's prefix sum takes no reduce-window over a slot-sized
operand (ISSUE 37): `idx/graph_csr.py::prefix_sum_rows` scans the in-row
positions of `[lanes, slots]` as slabs, a running sum that starts from the row
totals' prefix; a row is 128 slots, shorter under 16,384 of them. The scan
alone against a wrapping `np.cumsum` over the whole int32 range at every
lane count the runners use; the whole kernel, weighted and bare, composed and
record-level, against the dense form and an int64 walk on a graph whose
counts pass 2**31; and the program as it is lowered for a TPU at SNB SF3's
shapes, which holds no `reduce_window` over an operand as long as the slots,
so the cliff of PR 34 cannot come back by a refactoring."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surrealdb_tpu.idx import graph_csr
from surrealdb_tpu.utils.num import count_lane_set, next_pow2, path_slots
from test_graph_dense_exact import as_int32, near_complete

SF3_SLOTS = 1_179_648  # path_slots(1,130,494): SNB SF3's composed person->person operand
OCTAVE = sorted({path_slots(p) for p in range(1025, 2049)})  # the eight shapes between 1,024 and 2,048


def kernels() -> dict:
    graph_csr._kernels()
    return graph_csr._JITTED


# ------------------------------------------------------------------ the scan alone
def test_the_octave_has_its_eight_slot_counts():
    assert OCTAVE == [1152, 1280, 1408, 1536, 1664, 1792, 1920, 2048]


@pytest.mark.parametrize("lanes", count_lane_set())
@pytest.mark.parametrize("slots", [1, 2, 3, 17, 96, 128, 1024] + OCTAVE + [4097, 16_384, SF3_SLOTS])
def test_the_scan_is_a_wrapping_cumsum_over_the_whole_int32_range(slots, lanes):
    rng = np.random.default_rng(slots * 64 + lanes)
    vals = rng.integers(-(2**31), 2**31, size=(lanes, slots), dtype=np.int64).astype(np.int32)
    want = np.cumsum(vals, axis=1, dtype=np.int32)  # int32 addition wraps, as the device's does
    if slots > 1:
        assert (np.cumsum(vals.astype(np.int64), axis=1) != want).any()  # and it did wrap
    got = np.asarray(kernels()["prefix_sum_rows"](jnp.asarray(vals)))
    assert got.dtype == np.int32 and got.shape == (lanes, slots)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("slots, row", [(1, 1), (2, 1), (16, 4), (96, 8), (1024, 32), (1152, 32), (16_383, 64),
                                        (16_384, 128), (65_536, 128), (SF3_SLOTS, 128), (4_194_304, 128)])
def test_a_row_is_128_slots_and_shorter_only_where_that_would_be_more_slabs_than_rows(slots, row):
    text = str(jax.make_jaxpr(kernels()["prefix_sum_rows"])(jax.ShapeDtypeStruct((8, slots), jnp.int32)))
    assert [int(n) for n in re.findall(r"\bscan\[.*?length=(\d+)", text, flags=re.S)] == [row]  # one pass, `row` steps
    assert -(-slots // row) >= row


# ------------------------------------------------------------------ the whole kernel
N, N_PAD = 120, 128
EDGES = near_complete(N)  # 119**3 x 5,301 seeds' weight: past 2**31 at three pairs
SEEDS = [{3: 5000, 4: 1, 77: 300}, {0: 1}, {5: 70_001, 6: 257}]
PASSES = (np.arange(N) * 7919 % 97 < 30)  # the persons a final predicate lets through, about a third


def walk(seeds: dict, pairs: int, ends=None) -> int:
    """`pairs` knows records from the weighted seeds in int64; with `ends`,
    only walks whose last person passes."""
    x = np.zeros(N, dtype=np.int64)
    for s, w in seeds.items():
        x[s] += w
    for p in range(pairs):
        y = np.zeros(N, dtype=np.int64)
        np.add.at(y, EDGES[:, 1], x[EDGES[:, 0]])
        x = y
    return int(x.sum() if ends is None else x[ends].sum())


def lanes_of(seed_sets, sentinel: int, fsz: int = 8):
    lanes = max(next_pow2(len(seed_sets)), min(count_lane_set()))
    fr = np.full((lanes, fsz), sentinel, dtype=np.int32)
    cw = np.zeros((lanes, fsz), dtype=np.int32)
    for i, seeds in enumerate(seed_sets):
        fr[i, : len(seeds)] = list(seeds)
        cw[i, : len(seeds)] = list(seeds.values())
    return jnp.asarray(fr), jnp.asarray(cw)


def csr_ptr(src: np.ndarray, cap: int) -> np.ndarray:
    ptr = np.zeros(cap + 1, dtype=np.int32)
    np.cumsum(np.bincount(src, minlength=cap), out=ptr[1:])
    return ptr


def end_weights(space: int) -> np.ndarray:
    """w[v] = the last pair's paths from v to a person that passes."""
    w = np.zeros(space, dtype=np.int32)
    np.add.at(w, EDGES[:, 0], PASSES[EDGES[:, 1]].astype(np.int32))
    return w


def composed_count(pairs: int, weighted: bool) -> list:
    """The node->node operand in the table's compact ids: a hop a pair."""
    cptr, csrc = graph_csr._csc_arrays(EDGES[:, 0].astype(np.int32), EDGES[:, 1].astype(np.int32), N_PAD)
    assert csrc.shape == (path_slots(len(EDGES)),)
    hop = ((jnp.asarray(cptr), jnp.asarray(csrc)),)
    fr, cw = lanes_of(SEEDS, N_PAD)
    ends = (jnp.asarray(end_weights(N_PAD)),) * fr.shape[0] if weighted else None
    last = () if weighted else ((jnp.asarray(csr_ptr(EDGES[:, 0], N_PAD)),),)
    out = kernels()["chain_count_batch"]((hop,) * (pairs - 1), last, fr, cw, n_cap=N_PAD, end_weights=ends)
    return np.asarray(out)[: len(SEEDS)].tolist()


def records_count(pairs: int, weighted: bool) -> list:
    """The record-level operands in the id space persons and records share:
    a hop a spec, person -> record -> person; weighted, the last pair is
    folded into the end weights over the persons' ids."""
    records = N + np.arange(len(EDGES))
    cap = next_pow2(N + len(EDGES))
    near = graph_csr._csc_arrays(EDGES[:, 0].astype(np.int32), records.astype(np.int32), cap)
    far = graph_csr._csc_arrays(records.astype(np.int32), EDGES[:, 1].astype(np.int32), cap)
    near, far = tuple(map(jnp.asarray, near)), tuple(map(jnp.asarray, far))
    specs = 2 * pairs - (2 if weighted else 1)  # the bare count's last spec is a degree, the weighted one's last pair the ends
    hops = tuple(((near,) if i % 2 == 0 else (far,)) for i in range(specs))
    fr, cw = lanes_of(SEEDS, cap)
    ends = (jnp.asarray(end_weights(cap)),) * fr.shape[0] if weighted else None
    last = () if weighted else ((jnp.asarray(csr_ptr(records, cap)),),)
    out = kernels()["chain_count_batch"](hops, last, fr, cw, n_cap=cap, end_weights=ends)
    return np.asarray(out)[: len(SEEDS)].tolist()


def dense_count(pairs: int, weighted: bool) -> list:
    A = np.zeros((N_PAD, N_PAD), dtype=np.float32)
    np.add.at(A, (EDGES[:, 0], EDGES[:, 1]), 1)
    A = jnp.asarray(A, dtype=jnp.bfloat16)
    fr, cw = lanes_of(SEEDS, N_PAD)
    ends = (jnp.asarray(end_weights(N_PAD)),) * fr.shape[0] if weighted else None
    outdeg = None if weighted else jnp.asarray(np.diff(csr_ptr(EDGES[:, 0], N_PAD)))
    out = kernels()["chain_count_batch_dense"]((A,) * (pairs - 1), outdeg, fr, cw, n0=N_PAD, end_weights=ends)
    return np.asarray(out)[: len(SEEDS)].tolist()


@pytest.mark.parametrize("pairs", [2, 3])
@pytest.mark.parametrize("ending", ["bare", "weighted"])
@pytest.mark.parametrize("operand", ["composed", "records"])
def test_the_kernel_the_dense_form_and_the_walk_agree_where_the_counts_wrap(operand, ending, pairs):
    weighted = ending == "weighted"
    exact = [walk(s, pairs, PASSES if weighted else None) for s in SEEDS]
    if pairs == 3:
        assert max(exact) > 2**31 > min(exact)  # one rider wraps, one does not
    want = [as_int32(v) for v in exact]
    sparse = composed_count(pairs, weighted) if operand == "composed" else records_count(pairs, weighted)
    assert sparse == want
    assert dense_count(pairs, weighted) == want


# ------------------------------------------------------------------ the program lowered for a TPU
def window_operands(module_text: str) -> list:
    """The first operand's type of every `reduce_window` of a StableHLO module."""
    return re.findall(r"stablehlo\.reduce_window.*?\((tensor<[^>]*>)", module_text, flags=re.S)


def sf3_shapes(lanes: int, fsz: int = 64):
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    hop = ((i32(32769), i32(SF3_SLOTS)),)
    return (hop, hop), ((i32(32769),),), i32(lanes, fsz), i32(lanes, fsz)


@pytest.mark.parametrize("ending", ["bare", "weighted"])
@pytest.mark.parametrize("lanes", [8, 16])
def test_the_tpu_lowering_at_sf3_holds_no_reduce_window_over_the_slots(lanes, ending):
    from jax import export

    hops, last, fr, cw = sf3_shapes(lanes)
    ends = (jax.ShapeDtypeStruct((32768,), jnp.int32),) * lanes if ending == "weighted" else None
    kernel = kernels()["chain_count_batch"]
    try:
        exported = export.export(kernel, platforms=("tpu",))(
            hops, () if ends else last, fr, cw, n_cap=32768, end_weights=ends)
    except Exception as e:  # a JAX that cannot lower for a platform it does not run on
        pytest.skip(f"no TPU lowering without a chip here: {e!r}"[:200])
    text = exported.mlir_module()
    assert "gather" in text  # it is the kernel: the hops' gathers are there
    dims = [[int(d) for d in re.findall(r"(\d+)x", t)] for t in window_operands(text)]
    # the row totals' prefix may be one (9,216 a lane); nothing as long as the slots may
    assert all(max(d) <= SF3_SLOTS // 128 for d in dims), dims
    # and the spelling it replaced is caught: jnp.cumsum over the same rows lowers to one
    old = export.export(jax.jit(lambda v: jnp.cumsum(v, axis=1)), platforms=("tpu",))(
        jax.ShapeDtypeStruct((lanes, SF3_SLOTS), jnp.int32)).mlir_module()
    found = window_operands(old)
    assert found and any(str(SF3_SLOTS) in t for t in found), found
