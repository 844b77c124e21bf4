"""The whole LM step's share of the chip's int8 peak: useful work of the
ticks that ran inside the traced slice (prefill of the real prompt
tokens and every token the ticks delivered, 2 ops per weight plus
attention on the real lengths, ``bench/work.py``) over the span of those
ticks (first start to last end) times 393 TOP/s, in %."""
from bench import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ticks = run.traced_ticks()
    if not ticks:
        return None
    ops = sum(work.lm_useful_ops(run.m, x["prompt_tokens"], x["tokens"],
                                 x["prompt_pairs"] + x["gen_pairs"])
              for x in ticks)
    span = ticks[-1]["t1"] - ticks[0]["t0"]
    return 100.0 * ops / (span * run.peaks["int8_ops"])
