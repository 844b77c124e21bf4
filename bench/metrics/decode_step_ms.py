"""Device time per decode step: time per call of the scan-fused decode
program (jit module ``jit__decode_scan``) over its ``decode_block``
steps, milliseconds, from the profiler trace."""

PROGRAM = "jit__decode_scan"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_times(PROGRAM)
    return 1e3 * sum(t) / len(t) / run.decode_block if t else None
