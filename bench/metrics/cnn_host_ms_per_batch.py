"""Host time per batch on the CNN serving path: the wall time of each
traced ``serve()`` call (benchmark span ``serve``) minus the time the
device was busy inside it, averaged, in milliseconds."""


def read(run):
    if run.trace is None:
        return None
    v = [(e - s) - run.trace.busy_within(s, e)
         for name, s, e in run.trace.spans if name == "serve"]
    return 1e3 * sum(v) / len(v) if v else None
