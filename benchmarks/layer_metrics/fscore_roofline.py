"""Share of its roofline that the function_score program reaches: the
least time the chip could take for the requests served in the traced
slice over the device time of the lane's programs in it. With at most C
requests outstanding (the closed loop's connections) one read of the
columns serves at most C of them, so the least time is ``requests / C``
times ``fscore_rooflines.fscore_batch_min_seconds`` over the traffic's
operation mix. It passes 100% only if a program answers in fewer bytes
than every document's values of the fields its function reads."""
from benchmarks import fscore_rooflines, rooflines
from benchmarks.layer_common import lane_seconds, requests_in_slice


def read(ctx):
    st = ctx["corpus_stats"]
    if "places" not in st:
        return None
    streams = [s for s in ctx["cell"].traffic["streams"]
               if s["arrivals"]["process"] == "closed"
               and s.get("measured", True) and "ops" in s["queries"]]
    if not streams:
        return None
    sec = lane_seconds(ctx)
    n = requests_in_slice(ctx) if sec else 0.0
    if not sec or n <= 0:
        return None
    clients = sum(int(s["arrivals"]["processes"])
                  * int(s["arrivals"]["connections"]) for s in streams)
    least = fscore_rooflines.fscore_batch_min_seconds(
        st["places"], streams[0]["queries"]["ops"],
        rooflines.peaks_for(ctx["dev"]["kind"]))
    return 100.0 * (n / clients) * least / sec
