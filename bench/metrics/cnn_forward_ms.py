"""Device time per call of the CNN server's batched forward program (jit
module ``jit__fwd``), milliseconds, from the profiler trace."""

PROGRAM = "jit__fwd"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_times(PROGRAM)
    return 1e3 * sum(t) / len(t) if t else None
