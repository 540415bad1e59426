"""paged_decode_attention_roofline.chat: see ``bench.readers.paged_attn_roofline``."""
from bench import readers


def read(run):
    return readers.paged_attn_roofline(run)
