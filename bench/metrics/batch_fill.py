"""Frames answered per device batch (padding not counted), from the
serving engine's batch counter."""


def read(ctx):
    u = ctx["units"]
    if not u.get("batches"):
        return None
    return u["frames"] / u["batches"]
