"""Device time per solve in operations that are not matrix multiplies
(panels, pivot swaps, triangular solves, layout copies): the share of
the recorded op time that ops outside the matmul and collective
categories take on their own (a loop's time less its body's), in the
executions whose ops the trace holds, times the device's busy time per
solve; averaged over chips.  A share, and not a sum, so that ops the
trace's buffer dropped move it little."""


def read(ctx):
    solves = ctx["system"].get("solves", 0)
    devs = [d for d in ctx["trace"]["devices"].values()
            if d["modules_covered"]
            and d["matmul_s"] + d["other_s"] + d["collective_s"] > 0]
    if not solves or not devs:
        return None
    return 1e3 * sum(
        d["other_s"] / (d["matmul_s"] + d["other_s"] + d["collective_s"])
        * d["busy_s"] for d in devs) / (len(devs) * solves)
