"""90th percentile over completed requests of the time per output token
after the first: (last token time - first token time) / (tokens - 1),
in milliseconds.  Host clock."""
from bench.harness import percentile


def read(run):
    if run.kind != "lm":
        return None
    v = [1e3 * (r["t_last"] - r["t_first"]) / (r["n"] - 1)
         for r in run.requests if r["done"] and r["n"] > 1]
    return percentile(v, 90) if v else None
