"""Output tokens delivered by scheduler ticks that returned inside the
window, per second of window.  Host clock."""


def read(run):
    if run.kind != "lm":
        return None
    return run.tokens_in_window / run.window_s
