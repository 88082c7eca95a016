"""Device microseconds per simulated event spent in operations under the
``fleetsim.retire`` scope (the completion fast-forward loop)."""


def read(ctx):
    events = ctx["units"].get("events")
    t = ctx["scope_s"].get("fleetsim.retire")
    if not events or not t:
        return None
    return 1e6 * t / events
