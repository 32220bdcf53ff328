"""Share of its roofline that the knn lane reaches: the least time the chip
could take for the queries answered in the traced slice over the device
time of the lane's programs in it. With at most C queries outstanding (the
closed loop's connections) one read of the resident float32 vectors serves
at most C of them, so the least time is ``queries / C`` times
``rooflines.knn_batch_min_seconds`` of a batch of C: the read of the
vectors (bytes), or 2·C·N·D operations at the bfloat16 peak over the MXU
passes the configuration's stated precision costs (HIGHEST: six),
whichever is larger — whatever program does the work. It passes 100% only
if the program scores in fewer bytes than the configuration states."""
from benchmarks import rooflines
from benchmarks.layer_common import lane_seconds, requests_in_slice

PASSES = {"HIGHEST": 6, "HIGH": 3, "DEFAULT": 1}


def read(ctx):
    st = ctx["corpus_stats"]
    if "dims" not in st:
        return None
    sec = lane_seconds(ctx)
    n = requests_in_slice(ctx) if sec else 0.0
    if not sec or n <= 0:
        return None
    cell = ctx["cell"]
    clients = sum(int(s["arrivals"]["processes"])
                  * int(s["arrivals"]["connections"])
                  for s in cell.traffic["streams"]
                  if s["arrivals"]["process"] == "closed"
                  and s.get("measured", True))
    if clients <= 0:
        return None
    passes = PASSES[cell.config["precision"].split()[-1]]
    peaks = rooflines.peaks_for(ctx["dev"]["kind"])
    least, _bound = rooflines.knn_batch_min_seconds(
        st["docs"], st["dims"], clients,
        peaks["bf16_flops_per_s"] / passes, peaks)
    return 100.0 * (n / clients) * least / sec
