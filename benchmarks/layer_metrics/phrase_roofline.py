"""Share of its roofline that the phrase-boost program reaches: the least
time the chip could take for the requests served in the traced slice
over the device time of the lane's programs in it. With at most C
requests outstanding (the closed loop's connections) one read of the
postings, positions and document lengths serves at most C of them, so
the least time is ``requests / C`` times
``phrase_rooflines.phrase_batch_min_seconds``. It passes 100% only if a
program answers in fewer bytes than every posting and position once."""
from benchmarks import phrase_rooflines, rooflines
from benchmarks.layer_common import lane_seconds, requests_in_slice


def read(ctx):
    st = ctx["corpus_stats"]
    if "positions" not in st:
        return None
    sec = lane_seconds(ctx)
    n = requests_in_slice(ctx) if sec else 0.0
    if not sec or n <= 0:
        return None
    clients = sum(int(s["arrivals"]["processes"])
                  * int(s["arrivals"]["connections"])
                  for s in ctx["cell"].traffic["streams"]
                  if s["arrivals"]["process"] == "closed"
                  and s.get("measured", True))
    if clients <= 0:
        return None
    least = phrase_rooflines.phrase_batch_min_seconds(
        st["postings"], st["positions"], st["docs"],
        rooflines.peaks_for(ctx["dev"]["kind"]))
    return 100.0 * (n / clients) * least / sec
