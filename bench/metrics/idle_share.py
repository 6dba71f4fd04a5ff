"""Device: the share of the traced window in which no operation ran,
averaged over the chips in use."""


def read(name, run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
