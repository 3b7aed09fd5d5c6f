"""Operations and bytes of the kernels on hand-worked shapes, and the roofline rule."""

import pytest

from harness import manifest as mf, roofline

KERNELS = mf.load_modules(mf.BENCH_DIR, "kernels", None)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_ivf_at_1024_lists_nprobe_6():
    # 1M rows in 1,024 lists: a query probing 6 lists of 977 rows reads 5,862 rows
    shapes = {"dim": 768, "nlists": 1024, "nprobe": 6, "rows_probed_mean": 5862.0,
              "corpus_elem_bytes": 2, "centroid_elem_bytes": 4}
    one = KERNELS["ivf"].need(shapes, statements=1, dispatches=1)
    assert one["bytes"] == 1024 * 768 * 4 + 5862 * 768 * 2 == 12_149_760
    assert one["flops"] == 2 * 768 * (1024 + 5862) == 10_576_896
    # three statements riding one dispatch read the centroid table once
    three = KERNELS["ivf"].need(shapes, statements=3, dispatches=1)
    assert three["bytes"] == 1024 * 768 * 4 + 3 * 5862 * 768 * 2
    least, bound = roofline.least_seconds(one, PEAKS)
    assert bound == "memory" and least == pytest.approx(12_149_760 / 819e9)


GRAPH = {"nodes": 9892, "edges": 361_246, "hops": 3}


def test_graph_csc_at_snb_sf1():
    one = KERNELS["graph_csc"].need(GRAPH, statements=1, dispatches=1)
    # two sparse products read the adjacency (361,246 indices + 9,893 pointers, int32) each,
    # the last hop the 9,892 out-degrees; the statement's frontier is read and written a product
    assert one["bytes"] == 2 * 4 * (361_246 + 9893) + 4 * 9892 + 2 * 2 * 4 * 9892 == 3_166_952
    assert one["flops"] == 2 * (2 * 361_246 + 9892)
    # four statements in one dispatch: the adjacency is read once, the frontiers four times
    four = KERNELS["graph_csc"].need(GRAPH, statements=4, dispatches=1)
    assert four["bytes"] == one["bytes"] + 3 * 2 * 2 * 4 * 9892 and four["flops"] == 4 * one["flops"]
    least, bound = roofline.least_seconds(one, PEAKS)
    assert bound == "memory" and least == pytest.approx(3_166_952 / 819e9)


def test_roofline_share_of_a_slice():
    ctx = {
        "kernel": {"name": "graph_csc", "need": KERNELS["graph_csc"].need, "shapes": GRAPH},
        "slice": {"reduced": {"kernel_s": 0.2, "kernel_launches": 100},
                  "dispatch": {"submitted": 250, "dispatches": 100}},
        "peaks": PEAKS,
    }
    out = roofline.share(ctx, "graph_csc")
    assert out["bound"] == "memory"
    need = 100 * (2 * 4 * (361_246 + 9893) + 4 * 9892) + 250 * 2 * 2 * 4 * 9892
    assert out["pct"] == pytest.approx(100 * (need / 819e9) / 0.2)
    assert roofline.share(ctx, "ivf") is None  # this cell does not run that kernel
    assert roofline.share({**ctx, "slice": None}, "graph_csc") is None
