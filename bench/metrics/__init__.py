"""One reader per per-layer metric, found by the metric's name:
`<name>.py`, else `<base name>.py` for every cell that reports the same
quantity (`device_idle.eq` is read by `device_idle.py`).

A reader is `read(readings, peaks) -> float | None`.  It returns None
where the run holds nothing for it to read, and the metric is then left
out of the result line; it never returns 0 for a share of a peak.
"""
