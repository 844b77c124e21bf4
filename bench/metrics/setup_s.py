"""Set-up time: process start to the opening of the measured window
(weights from the seed, engine construction, warm-up and, in a cold
checkout, compilation).  Host clock."""


def read(run):
    return run.setup_s
