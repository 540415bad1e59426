"""submit_ms.chat: see ``bench.readers.submit_ms``."""
from bench import readers


def read(run):
    return readers.submit_ms(run)
