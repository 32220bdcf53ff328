"""Share of the window's function_score items that fell to the
query-by-query path: ``fscore_items_serial`` ÷ (``fscore_items_batched``
+ ``fscore_items_serial``) of ``_nodes/stats`` ``indices.jit``, the
difference of the window's two ends. A program without those counters
gives nothing to read."""


def read(ctx):
    b = ctx.get("before", {}).get("jit", {})
    a = ctx.get("after", {}).get("jit", {})
    if "fscore_items_serial" not in a or "fscore_items_serial" not in b:
        return None
    serial = a["fscore_items_serial"] - b["fscore_items_serial"]
    batched = a["fscore_items_batched"] - b["fscore_items_batched"]
    if serial + batched <= 0:
        return None
    return 100.0 * serial / (serial + batched)
