"""mfu.saturate: see ``bench.readers.mfu``."""
from bench import readers


def read(run):
    return readers.mfu(run)
