"""Model FLOP/s utilization of the slice, %: the operations every token
processed in the slice needs (prompt tokens of each prefill chunk and one
token per live decode row; two per matmul weight plus attention over the
live context, whatever the tier), over the slice's length times the
chip's bf16 peak."""
from chipbench.counts import token_flops


def read(sl):
    flops = 0
    for c in sl.calls:
        if c.kind == "chunk":
            start, end = c.ctx
            flops += sum(token_flops(sl.spec, p + 1) for p in range(start, end))
        elif c.kind == "decode":
            flops += sum(token_flops(sl.spec, p + 1) for p in c.ctx)
    if not flops:
        return None
    return 100.0 * flops / (sl.window_s * sl.peaks["bf16_flops"])
