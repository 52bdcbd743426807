"""The grouped expert FFN's share of its roofline in the traced stretch: the
least time the chip could take for every grouped-matmul call the trace shows
(the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, for
the routed pairs a call computes, from the program's counters), over the
time those calls took.  Forward and backward calls both count."""


def read(ctx):
    km = ctx["kernels"]("expert_ffn")
    pairs = km.pairs_per_call(ctx["counters"]["config"])
    if pairs is None:
        return None
    least = took = 0.0
    for name, secs in ctx["trace"]["op_seconds"].items():
        if km.matches(name):
            least += km.least_seconds(name, pairs, ctx["peaks"]) * ctx["trace"]["op_calls"][name]
            took += secs
    return 100.0 * least / took if took > 0 else None
