"""Device time per solve in which a collective runs and no compute op
does, averaged over the cell's chips.

Per chip, ``trace.reduce`` gives ``collective_exposed_s``: over the
executions whose ops the trace holds, the union of the collective ops'
spans less its overlap with the leaf compute ops.  That time's share of
the chip's recorded op time (matmul, collective and other ops, each its
own time) times the chip's busy time per solve gives milliseconds per
solve, as ``nonmatmul_ms.solve`` scales its share; the chips' values
are averaged.  None where no chip recorded a collective op.

Which op is a collective is ``trace.category``'s rule: by the HLO
instruction's name.
"""


def read(ctx):
    solves = ctx["system"].get("solves", 0)
    devs = [d for d in ctx["trace"]["devices"].values()
            if d["modules_covered"]
            and d["matmul_s"] + d["other_s"] + d["collective_s"] > 0]
    if not solves or not any(d["collective_s"] > 0 for d in devs):
        return None
    return 1e3 * sum(
        d["collective_exposed_s"]
        / (d["matmul_s"] + d["other_s"] + d["collective_s"])
        * d["busy_s"] for d in devs) / (len(devs) * solves)
