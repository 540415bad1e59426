"""decode_pages_read_share.chat: see ``bench.spans.decode_pages_read_share``."""
from bench import spans


def read(run):
    return spans.decode_pages_read_share(run)
