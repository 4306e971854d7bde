"""decode_fetch_ms: host milliseconds per decode call spent bringing its
tokens and logits to the host and unpacking them per slot: the program's
own `runner.decode.fetch` span, mean over the calls it recorded while
the profiler ran (the traced part of the window)."""
from bench import program_trace


def read(r, peaks):
    return program_trace.mean_ms(program_trace.recorded(),
                                 "runner.decode.fetch")
