"""Work counts from shapes: the operations and bytes each cell's calls need.

These are the numerators of the ``*_mfu`` metrics, kept with the
benchmark so that no change to the program can move them.
"""
from __future__ import annotations

import numpy as np

#: per-request float32/int32 words a fleet call must read (arrival, origin,
#: deadline, proc, payload) and write (outcome, served_by, completion,
#: forwards_used, transfer_used)
SCAN_WORDS_PER_REQUEST = 10


def scan_bytes(n_requests: int, topo, net) -> int:
    """Compulsory bytes of one fleet call: per-request inputs and outputs,
    plus the topology and ``(K, K)`` network tensors read once."""
    once = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in tuple(topo) + tuple(net))
    return n_requests * SCAN_WORDS_PER_REQUEST * 4 + once


def vit_forward_flops(cfg: dict) -> int:
    """Matrix-multiply FLOPs (2 per multiply-add) of one frame through a
    ViT encoder and its classifier head: patch embedding, per layer the
    q/k/v/o projections, attention scores and weighted sum, and the MLP."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    grid = (cfg["img_res"] // cfg["patch"]) ** 2
    S = grid + 1 + int(cfg["distill_token"])
    patch = grid * cfg["patch"] ** 2 * cfg["in_channels"] * d
    layer = 4 * S * d * d + 2 * S * S * d + 2 * S * d * f
    head = d * cfg["n_classes"]
    return 2 * (patch + L * layer + head)
