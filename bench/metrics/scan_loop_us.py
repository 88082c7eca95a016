"""Device microseconds per simulated event charged to ``fleetsim.scan``:
the event scan's own operations outside every phase scope (its loop
condition and counter, any step operation with no phase scope of its
own) and, where the trace names it, the self time of the scan's loop
operation (its control and the gaps between the step's operations)."""


def read(ctx):
    events = ctx["units"].get("events")
    t = ctx["scope_s"].get("fleetsim.scan")
    if not events or not t:
        return None
    return 1e6 * t / events
