"""Share of its bytes roofline that the BM25 lane reaches: the least time
the chip could take for the client requests served in the traced slice
(one read of every resident document's postings and length per request:
a request is the batch the client sent, however the program splits it)
over the device time of the lane's programs in the slice."""
from benchmarks import rooflines
from benchmarks.layer_common import lane_seconds, requests_in_slice


def read(ctx):
    sec = lane_seconds(ctx)
    n = requests_in_slice(ctx) if sec else 0.0
    if not sec or n <= 0:
        return None
    st = ctx["corpus_stats"]
    least = rooflines.bm25_batch_min_seconds(
        st["postings"], st["docs"], rooflines.peaks_for(ctx["dev"]["kind"]))
    return 100.0 * n * least / sec
