"""serve_mfu: model FLOPs of every prompt and output token processed in
the traced part of the window, over its seconds, as a share of the
chip's bf16 peak (`bench/work.py` counts the FLOPs)."""
from bench import work


def read(r, peaks):
    if r.window is None:
        return None
    t0, t1 = r.window
    flops = 0.0
    for s in r.spans_in_window("prefill"):
        flops += work.model_flops(r.lm, 0, s.attrs["tokens"], 1)
    for s in r.spans_in_window("decode"):
        flops += sum(work.model_flops(r.lm, n - 1, 1, 1)
                     for n in s.attrs["live"])
    if not flops:
        return None
    return 100.0 * flops / (t1 - t0) / peaks.bf16_flops
