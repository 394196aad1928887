"""``compile_time_s`` of the warm-up call: trace, lower and compile, or
load from the persistent cache (Engine, host clock)."""


def read(run):
    return run.compile_s
