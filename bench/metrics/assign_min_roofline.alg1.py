"""``assign_min``'s share of its roofline in the traced stretch: the least
time the chip could take for every call the trace shows (the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, from each call's
shapes), over the time those calls took.  Its f32 tiles take six bf16 MXU
passes, so the share sits below about 1/6 when compute bound."""


def read(ctx):
    km = ctx["kernels"]("assign_min")
    least = took = 0.0
    for name, secs in ctx["trace"]["op_seconds"].items():
        if km.matches(name):
            t, _ = km.least_seconds(name, ctx["peaks"])
            least += t * ctx["trace"]["op_calls"][name]
            took += secs
    return 100.0 * least / took if took > 0 else None
