"""decode_dispatch_ms: host milliseconds per decode call from the
runner's entry until its jitted program returns: the program's own
`runner.decode.dispatch` span, mean over the calls it recorded while
the profiler ran (the traced part of the window)."""
from bench import program_trace


def read(r, peaks):
    return program_trace.mean_ms(program_trace.recorded(),
                                 "runner.decode.dispatch")
