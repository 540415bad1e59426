"""stack_ms.fedsync: see ``bench.spans.stack_ms``."""
from bench import spans


def read(run):
    return spans.stack_ms(run)
