"""dequant_matmul_roofline: the least time the chip needs for the
`vp_dequant_matmul` launches of the decode calls the trace holds, over
the device time of those launches: packed weight words at their storage
bits, their scales, and bf16 activations in and out, per launch the
larger of its compute and its bytes bound (`bench/work.py`)."""
from bench import trace, work

KERNEL = trace.kernel_matcher(["vp_dequant_matmul"])


def read(r, peaks):
    pairs = r.traced("decode")
    if not pairs:
        return None
    calls = []
    for span, _ in pairs:
        n = span.attrs["rows"]
        calls += work.dequant_matmul_calls(r.lm, n, n)
    need, _, _ = work.roofline_seconds(calls, peaks.bf16_flops,
                                       peaks.hbm_bytes_s)
    took = r.trace.op_seconds(KERNEL, [t for _, t in pairs])
    return 100.0 * need / took if took > 0 else None
