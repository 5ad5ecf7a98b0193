"""Share of the window in which no program ran on the device, averaged
over the cell's chips: 1 - busy / window, busy being the union of the
trace's program executions (its ``XLA Modules`` events)."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0 or not any(
            d["modules"] for d in tr["devices"].values()):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
