"""The routed experts' share of their roofline, in percent: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, over the device time
of ``kernels.moe_experts``.  FLOPs come from the program's count of
(token, held expert) routes; bytes are the weights of the held experts
that a token picked, once a layer and pass, and each route's row in and
out (``bench/vlm_work.py``)."""


def read(ctx):
    u = ctx["units"]
    t = ctx["scope_s"].get("kernels.moe_experts")
    if not u.get("moe_flops") or not t:
        return None
    pk = ctx["peaks"]()
    least = max(u["moe_flops"] / pk["bf16_flops_per_s"],
                u["moe_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
