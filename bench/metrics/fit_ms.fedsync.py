"""fit_ms.fedsync: see ``bench.spans.fit_ms``."""
from bench import spans


def read(run):
    return spans.fit_ms(run)
