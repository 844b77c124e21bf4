"""Share of the decode program's roofline: the least time the chip needs
for the traced decode calls' algorithmic work (weights read once at
container width, the live KV read once; 2 ops per weight per active row
at the int8 peak, attention at the bf16 peak), over their device time,
in %.  Work comes from ``bench/work.py``; device time from the trace
(``jit__decode_scan``)."""
from bench import work

PROGRAM = "jit__decode_scan"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    dev = run.trace.module_times(PROGRAM)
    ticks = run.traced_ticks()
    if not dev or not ticks:
        return None
    pk = run.peaks
    least = []
    for x in ticks:
        rows, live = x["rows"], x["live"]
        if rows == 0:
            continue
        lin = 2.0 * (work.lm_linear_params(run.m)
                     + work.lm_head_params(run.m)) * rows
        ops, nbytes = work.decode_step_work(run.m, rows, int(live))
        attn = ops - lin
        least.append(run.decode_block * max(
            lin / pk["int8_ops"] + attn / pk["bf16_flops"],
            nbytes / pk["hbm_bytes_per_s"]))
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (sum(dev) / len(dev))
