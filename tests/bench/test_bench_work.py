"""Work counts kept with the benchmark: FLOPs of a served frame and the
compulsory bytes of a fleet call, against counts made by hand."""
import json
import os

from conftest import REPO

from bench import work


def _config(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_deit_b_frame_flops_match_hand_count():
    # 224/16 = 14, 196 patches + class + distillation token = 198 tokens
    S, d, f, L = 198, 768, 3072, 12
    macs = (196 * (16 * 16 * 3) * d                       # patch embedding
            + L * (4 * S * d * d                          # q, k, v, o
                   + 2 * S * S * d                        # scores, weighted sum
                   + 2 * S * d * f)                       # MLP in and out
            + d * 1000)                                   # head
    assert macs == 17_656_043_520
    assert work.vit_forward_flops(_config("deit-b")["arch"]) == 2 * macs


def test_fleet256_call_bytes_match_shapes():
    from bench.drivers import fleet
    topo, net = fleet.topology(_config("fleet256-campus"))
    K, R = 256, 256 * 100                 # Table II's 2,000 a node, cut by 20
    topo_bytes = K * K * 1 + K * (K - 1) * 4 + K * 4 + K * 4  # adj, nbrs, deg, speed
    net_bytes = 2 * K * K * 4                                 # latency, inv_bw
    assert work.scan_bytes(R, topo, net) == R * 40 + topo_bytes + net_bytes
    assert work.scan_bytes(R, topo, net) == 1_876_992
