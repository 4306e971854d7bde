"""engine_host_ms: host milliseconds per engine step outside the
runner's calls (admission, screening, retirement, bookkeeping): the
program's own `engine.step` span less its `runner.decode` and
`runner.prefill` spans, mean over the steps it recorded while the
profiler ran (the traced part of the window)."""
from bench import program_trace


def read(r, peaks):
    return program_trace.engine_host_ms(program_trace.recorded())
