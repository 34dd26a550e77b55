"""Share of the HBM-bandwidth bound that the window's device time reaches
for the stream's decode + format work: the bytes that work must move
(``roofline.decode_bytes``) over the chip's peak bytes/s, divided by the
device busy time. Bytes, not operations, bound it: the decode is integer
work, for which the peaks table has no peak."""

import roofline


def read(m):
    t = m["trace"]
    fetches = len(t.spans.get("bench.fetch", ())) if t else 0
    if not fetches:
        return None
    moved = roofline.decode_bytes(fetches * m["blocks_per_fetch"],
                                  m["resident_bytes_per_block"], m["output_shapes"])
    share = roofline.roofline_share(moved, t.busy_s, m["peaks"]["hbm_bytes_per_s"])
    return None if share is None else 100.0 * share
