"""The whole solve's share of the chip's bf16 peak: the FLOPs Algorithm 1
requires per solve, over the solve's time (``solve_s`` of the same run),
over the peak of the chips used.

Required work, over all s workers of m rows each and the coordinator's
M = s k weighted points: seeding compares each point with each new center
once (2 m k d per worker, not the k passes over all k slots the program
makes); each Lloyd round assigns (2 m k d) and makes 4 Weiszfeld steps of
a distance to the own center (3 m d) and a weighted segment sum (2 m d,
adds, not a one-hot matmul); a last assignment; the coordinator the same
over M points; and the full cost over all n points (2 n k d)."""


def solve_flops(cfg: dict) -> float:
    n, d, k, s, ell = cfg["n"], cfg["d"], cfg["k"], cfg["nodes"], cfg["ell"]
    m = ell * n // s

    def kmedian(rows, iters):
        return 2.0 * rows * k * d * (1 + iters + 1) + iters * 4 * 5.0 * rows * d

    return s * kmedian(m, cfg["local_iters"]) + kmedian(s * k, cfg["coord_iters"]) + 2.0 * n * k * d


def read(ctx):
    c = ctx["counters"]
    if not c.get("solves"):
        return None
    chips = ctx["cell"].chips
    return 100.0 * solve_flops(c["config"]) / c["solve_s"] / (chips * ctx["peaks"]["bf16_flops_per_s"])
