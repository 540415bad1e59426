"""step_ms.chat: see ``bench.readers.step_ms``."""
from bench import readers


def read(run):
    return readers.step_ms(run)
