"""The whole solve's share of the chip's peak: the nominal operations of
one solve (getrf + getrs or potrf + potrs, the reference tester's
counts) over the chips' peak FLOP/s times the device busy time per
solve.  The solve is bound by compute (O(n^3) operations on O(n^2)
bytes), so the peak rate is its roofline."""


def read(ctx):
    tr, sysctx, peak = ctx["trace"], ctx["system"], ctx["peak"]
    solves = sysctx.get("solves", 0)
    chips = len(tr["devices"])
    if not solves or not chips or tr["busy_s"] <= 0:
        return None
    busy_per_solve = tr["busy_s"] / solves
    return 100.0 * sysctx["flops_per_solve"] / (
        chips * peak["flops_per_s"] * busy_per_solve)
