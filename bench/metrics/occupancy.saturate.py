"""occupancy.saturate: see ``bench.readers.occupancy``."""
from bench import readers


def read(run):
    return readers.occupancy(run)
