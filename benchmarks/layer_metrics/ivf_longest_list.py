"""Rows in the longest list of the trained IVF quantizer. Today's kernel pads
every probed list to the power of two above it, so a reading that crosses a
power of two changes the work of every search about twofold: a change in
training, or in the data, that does so shows here and not only in the rate."""

NAME, UNIT, LAYER, MOVES, SOURCE = "ivf.longest_list", "rows", "mirrors", "p50_ms", "program_counter"


def read(ctx):
    return ctx["kernel"]["shapes"].get("longest_list")
