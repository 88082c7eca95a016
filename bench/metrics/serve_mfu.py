"""The served forward's share of the chip's bf16 peak, in percent: frames
answered times the model's matrix-multiply FLOPs per frame (counted from
the configuration's shapes, padding not counted), over the traced window."""


def read(ctx):
    u = ctx["units"]
    if "frames" not in u or not u["frames"] or ctx["window_s"] <= 0:
        return None
    flops = u["frames"] * ctx["cell"].frame_flops()
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]()["bf16_flops_per_s"]
