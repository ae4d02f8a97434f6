"""The decode program's share of its roofline: the least time a traced
step needs (the larger of its needed FLOPs over peak and its needed bytes
over HBM bandwidth, flops.dense_decode_bytes: weights once, live KV
positions only), averaged over the traced steps, over the decode
program's mean device time per run."""
from chipbench import view


def read(run):
    roof, dev = view.decode_roofline_ms(run), view.decode_ms(run)
    return None if roof is None or not dev else roof / dev * 100.0
