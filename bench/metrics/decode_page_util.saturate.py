"""decode_page_util.saturate: see ``bench.spans.decode_page_util``."""
from bench import spans


def read(run):
    return spans.decode_page_util(run)
