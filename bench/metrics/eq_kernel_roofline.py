"""eq_kernel_roofline: the least time the chip needs for a slot's
equalization (each W once per subcarrier, each y and estimate once per
(subcarrier, symbol), float32; `bench/work.py`) times the slots the
trace holds, over the device time of the batched VP kernel in them."""
from bench import trace, work

KERNEL = trace.kernel_matcher(["vp_quant_matmul_batched"])


def read(r, peaks):
    pairs = r.traced("slot")
    if not pairs:
        return None
    need, _, _ = work.roofline_seconds([r.slot_work] * len(pairs),
                                       peaks.bf16_flops, peaks.hbm_bytes_s)
    took = r.trace.op_seconds(KERNEL, [t for _, t in pairs])
    return 100.0 * need / took if took > 0 else None
