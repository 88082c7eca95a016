"""Device busy microseconds per simulated event (a fresh arrival or a
forward's re-arrival), summed over the window's calls and sweep points.
Scan steps that carry no event are charged to the events."""


def read(ctx):
    events = ctx["units"].get("events")
    if not events or ctx["busy_s"] <= 0:
        return None
    return 1e6 * ctx["busy_s"] / events
