"""The vision tower's share of the chip's bf16 peak, in percent: answered
frames times the FLOPs of the tower's encoder blocks per frame (from
shapes, ``bench/vlm_work.py``; padding rows not counted) over the device
time of ``kernels.vit_block``."""


def read(ctx):
    flops = ctx["units"].get("tower_flops")
    t = ctx["scope_s"].get("kernels.vit_block")
    if not flops or not t:
        return None
    return 100.0 * flops / t / ctx["peaks"]()["bf16_flops_per_s"]
