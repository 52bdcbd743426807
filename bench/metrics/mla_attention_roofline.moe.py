"""Latent attention's share of its roofline in the traced stretch: the
least time the chip could take for every forward call of the attention
kernel at dk ≠ dv (the larger of FLOPs over the bf16 peak and bytes over
HBM bandwidth, from each call's shapes), over the time those calls took.
The backward is XLA's and is not counted here."""


def read(ctx):
    km = ctx["kernels"]("mla_attention")
    least = took = 0.0
    for name, secs in ctx["trace"]["op_seconds"].items():
        if km.matches(name):
            t, _ = km.least_seconds(name, ctx["peaks"])
            least += t * ctx["trace"]["op_calls"][name]
            took += secs
    return 100.0 * least / took if took > 0 else None
