"""decode_rows: mean active slots per `runner.decode_batch` call, from
the harness's span around each call in the traced part of the window."""


def read(r, peaks):
    if r.window is None:
        return None
    calls = r.spans_in_window("decode")
    if not calls:
        return None
    return sum(s.attrs["rows"] for s in calls) / len(calls)
