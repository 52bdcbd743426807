"""Share of the chip's busy time in the traced stretch spent in the grouped
expert FFN's grouped-matmul calls (``gmm``, ``tgmm``)."""


def read(ctx):
    km = ctx["kernels"]("expert_ffn")
    took = sum(s for name, s in ctx["trace"]["op_seconds"].items() if km.matches(name))
    busy = ctx["trace"]["busy_s"]
    return 100.0 * took / busy if took > 0 and busy > 0 else None
