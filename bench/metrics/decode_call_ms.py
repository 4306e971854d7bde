"""decode_call_ms: host milliseconds per `runner.decode_batch` call, the
logits' copy to the host included: total time in the calls over their
number, in the traced part of the window."""


def read(r, peaks):
    if r.window is None:
        return None
    calls = r.spans_in_window("decode")
    if not calls:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in calls) / len(calls)
