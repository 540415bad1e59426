"""step_host_ms.saturate: see ``bench.spans.step_host_ms``."""
from bench import spans


def read(run):
    return spans.step_host_ms(run)
