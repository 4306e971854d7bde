"""decode_attention_roofline: the least time the chip needs for the
`vp_decode_attention` launches of the decode calls the trace holds, over
their device time: K and V words and scales over the live lengths, not
the cache capacity (`bench/work.py`)."""
from bench import trace, work

KERNEL = trace.kernel_matcher(["decode_attention"])


def read(r, peaks):
    pairs = r.traced("decode")
    if not pairs:
        return None
    calls = []
    for span, _ in pairs:
        calls += work.decode_attention_calls(r.lm, span.attrs["live"])
    need, _, _ = work.roofline_seconds(calls, peaks.bf16_flops,
                                       peaks.hbm_bytes_s)
    took = r.trace.op_seconds(KERNEL, [t for _, t in pairs])
    return 100.0 * need / took if took > 0 else None
