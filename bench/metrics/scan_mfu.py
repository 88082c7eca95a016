"""The whole fleet call's share of the chip's memory-bandwidth peak, in
percent: the least time the chip needs to move the call's compulsory
bytes (per-request inputs and outputs, topology and network tensors,
once) over the traced window's time per call."""


def read(ctx):
    calls = ctx["units"].get("calls")
    if "events" not in ctx["units"] or not calls or ctx["window_s"] <= 0:
        return None
    least_s = ctx["cell"].compulsory_bytes() / ctx["peaks"]()["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx["window_s"] / calls)
