"""Decode attention's share of the chip's memory-bandwidth roofline, in
percent: the least time to move its compulsory bytes (each layer's latent
cache up to each answered request's length at every decode step, and each
layer's latent-attention weights once a step; ``bench/vlm_work.py``) over
the device time of ``kernels.mla_decode``."""


def read(ctx):
    b = ctx["units"].get("mla_decode_bytes")
    t = ctx["scope_s"].get("kernels.mla_decode")
    if not b or not t:
        return None
    return 100.0 * b / ctx["peaks"]()["hbm_bytes_per_s"] / t
