"""The server's codec for one tagged request, both ways: `ws_decode` (unmask,
unpack, fingerprint) plus `ws_encode` (the reply packed and framed)."""

from harness import spans
from harness.stats import median

NAME, UNIT, LAYER, MOVES, SOURCE = "wire.codec_ms", "ms", "wire", "p50_ms", "program_span"


def read(ctx):
    xs = []
    for t in ctx["tagged"]:
        dec, enc = spans.durations_ms(t["doc"], "ws_decode"), spans.durations_ms(t["doc"], "ws_encode")
        if dec and enc:
            xs.append(sum(dec) + sum(enc))
    return median(xs) if xs else None
