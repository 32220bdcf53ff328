"""Share of the exact-phrase terms the lane's dispatches compared in the
window that were pads (a phrase's term list filled to the batch's term
bucket): ``phrase_terms_padded`` ÷ (``phrase_terms_real`` +
``phrase_terms_padded``) of ``_nodes/stats`` ``indices.jit``, the
difference of the window's two ends. A program without those counters
gives nothing to read."""


def read(ctx):
    b = ctx.get("before", {}).get("jit", {})
    a = ctx.get("after", {}).get("jit", {})
    if "phrase_terms_padded" not in a or "phrase_terms_padded" not in b:
        return None
    pad = a["phrase_terms_padded"] - b["phrase_terms_padded"]
    real = a["phrase_terms_real"] - b["phrase_terms_real"]
    if pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
