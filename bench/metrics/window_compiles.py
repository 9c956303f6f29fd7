"""Backend compiles inside the measured window (JAX monitoring events):
every executable should have been compiled or loaded during set-up."""


def read(run):
    return run["compiles"]
