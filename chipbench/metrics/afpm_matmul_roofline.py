"""Share of the roofline reached by the segmented-matmul Pallas kernel in
the slice, %: the least time its calls could take on this chip (each
call's max(operations / peak FLOP/s, bytes / HBM bandwidth), from
``chipbench/counts.py``) over the device time of the ``tpu_custom_call``
operations.  The number of kernel runs in the trace must equal what the
lanes' decode and prefill calls in the slice imply."""
from chipbench.counts import forward_kernel_calls, kernels_per_forward
from chipbench.trace import TraceError, is_kernel


def read(sl):
    runs = [e for e in sl.trace.within(sl.trace.ops) if is_kernel(e)]
    seg = [c for c in sl.calls if c.kind in ("decode", "chunk")
           and sl.passes[c.tier] > 0]
    if not runs or not seg:
        return None
    want = kernels_per_forward(sl.spec) * len(seg)
    if len(runs) != want:
        raise TraceError(f"{len(runs)} tpu_custom_call runs in the slice, "
                         f"but its {len(seg)} segmented calls imply {want}")
    slots = sl.mix["engine"]["slots"]
    best = 0.0
    for c in seg:
        rows, tokens = (slots, 1) if c.kind == "decode" else (1, c.ctx[1] - c.ctx[0])
        for ops, byt in forward_kernel_calls(sl.spec, rows, tokens,
                                             sl.passes[c.tier]):
            best += max(ops / sl.peaks["bf16_flops"],
                        byt / sl.peaks["hbm_bytes_per_s"])
    return 100.0 * best / (1e-9 * sum(e.dur for e in runs))
