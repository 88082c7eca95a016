"""Device idle share of a fleet cell's traced window, in percent: one minus
the union of device-operation intervals over the window."""


def read(ctx):
    if "events" not in ctx["units"] or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
