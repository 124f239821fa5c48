"""Process start until the window opens: JAX start-up, traffic, the
reference's segmentation, loading or compiling the cell's programs, and
the warm-up stretch of traffic."""


def read(run):
    return run.setup_s
