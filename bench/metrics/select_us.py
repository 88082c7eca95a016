"""Device microseconds per simulated event spent in the fleet-wide scoring:
operations under ``fleetsim.windows`` (every node's live window cut from
its ledger row) plus ``kernels.event_select`` (the merge and per-hop
feasibility scoring over those windows, jnp or Pallas).  Only
``batched_feasible`` runs them; elsewhere there is nothing to read."""


def read(ctx):
    events = ctx["units"].get("events")
    t = sum(ctx["scope_s"].get(s, 0.0)
            for s in ("fleetsim.windows", "kernels.event_select"))
    if not events or not t:
        return None
    return 1e6 * t / events
