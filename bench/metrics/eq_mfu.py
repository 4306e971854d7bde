"""eq_mfu: 8 U B FLOPs per (subcarrier, symbol) of every slot equalized
in the traced part of the window, over its seconds, as a share of the
chip's bf16 peak."""


def read(r, peaks):
    if r.window is None:
        return None
    t0, t1 = r.window
    n = len(r.spans_in_window("slot"))
    if not n:
        return None
    return 100.0 * n * r.slot_work[0] / (t1 - t0) / peaks.bf16_flops
