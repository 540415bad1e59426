"""route_ms.chat: see ``bench.spans.route_ms``."""
from bench import spans


def read(run):
    return spans.route_ms(run)
