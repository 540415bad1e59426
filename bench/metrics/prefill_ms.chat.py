"""prefill_ms.chat: see ``bench.spans.prefill_ms``."""
from bench import spans


def read(run):
    return spans.prefill_ms(run)
