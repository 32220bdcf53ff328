"""Share of its bytes roofline that the collective plane's program
reaches on a mesh of several chips: the least time the chips could take
together for the client requests served in the traced slice (one read of
every posting and document length of the whole sharded index per request,
the shards side by side at ``chips`` x one chip's HBM bandwidth) over the
device time of the plane's program in the slice, the mean over the device
planes (``trace_reduce`` divides a module's seconds by their number)."""
from benchmarks import plane_rooflines, rooflines
from benchmarks.layer_common import lane_seconds, requests_in_slice


def read(ctx):
    sec = lane_seconds(ctx)
    n = requests_in_slice(ctx) if sec else 0.0
    if not sec or n <= 0:
        return None
    st = ctx["corpus_stats"]
    least = plane_rooflines.plane_batch_min_seconds(
        st["postings"], st["docs"], ctx["cell"].chips,
        rooflines.peaks_for(ctx["dev"]["kind"]))
    return 100.0 * n * least / sec
