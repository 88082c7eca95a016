"""Serving launcher: deadline-aware engine over N model replicas.

The paper's deployment: requests with per-resolution SLA deadlines are
admitted by the preferential queue (or FIFO for comparison), forwarded
between replicas on rejection, and executed in deadline-aware batches.

    PYTHONPATH=src python -m repro.launch.serve --arch deit-b \
        --replicas 3 --requests 60 --queue preferential [--full]

Without ``--full`` the registry's reduced smoke configuration runs (CPU
friendly); ``--full`` serves the published configuration.  :func:`serve`
is the same path as a function, for callers that need the per-request
results (``chip_smoke.py``).  A vision-language model (``--arch
kimi-vl-a3b``) answers a random prompt about each frame, through the
``run_batch`` of ``repro.models.kimi_vl.Runner``.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class ServeReport:
    """What one :func:`serve` run answered."""
    stats: Dict[str, int]             # DeadlineAwareEngine.stats()
    # per-request argmax class (vision) or answer ids (VLM), by rid
    results: List[Optional[Any]]
    devices: List[str]                # the device each replica ran on

    @property
    def met_pct(self) -> float:
        s = self.stats
        return 100 * s["met"] / max(1, s["met"] + s["missed"])

    @property
    def answered(self) -> int:
        return sum(r is not None for r in self.results)


def _replica_runner(mod, cfg, params, device, max_batch: int):
    """A replica's ``run_batch``: the jitted forward on ``device``.

    ``params`` are placed on ``device`` once; every batch is padded to
    ``max_batch`` and placed there too, so each device compiles one
    executable and runs only its own replica's work.
    """
    params = jax.device_put(params, device)
    fwd = jax.jit(lambda p, imgs: jnp.argmax(mod.forward(p, imgs, cfg), -1))

    def run_batch(cls_name, payloads):
        b = len(payloads)
        imgs = np.stack(payloads + [payloads[0]] * (max_batch - b))
        return [int(c) for c in
                np.asarray(fwd(params, jax.device_put(imgs, device)))[:b]]

    return run_batch


def serve(arch: str = "deit-b", *, full: bool = False, replicas: int = 3,
          requests: int = 60, queue: str = "preferential",
          max_batch: int = 8, deadline: float = 30.0,
          inter_arrival: float = 1.2,
          devices: Optional[Sequence] = None) -> ServeReport:
    """Serve ``requests`` random frames through ``replicas`` replicas.

    Replica ``i`` runs on ``devices[i % len(devices)]`` (default: every
    replica on ``jax.devices()[0]``).  Weights and frames are random,
    from seed 0; arrivals are a seeded Poisson stream on the engine's
    simulated clock, so the engine's decisions do not depend on the
    device and two runs with different placements must agree request
    for request.
    """
    from repro.configs import get_config, get_smoke_config
    from repro.core.queues import FIFOQueue
    from repro.launch.steps import model_module
    from repro.serving.engine import (DeadlineAwareEngine, ServiceClass,
                                      ServingReplica)

    cfg = get_config(arch) if full else get_smoke_config(arch)
    if cfg.family not in ("vit", "resnet", "vlm"):
        raise SystemExit("serve launcher supports vision and vision-language "
                         "archs; see examples/ for LM decode serving")
    mod = model_module(cfg)
    # one compiled init: op by op, each parameter shape compiles its own
    # small programs, which on the TPU costs more than the forward
    params = jax.jit(mod.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    devices = list(devices) if devices else [jax.devices()[0]]
    runners = {}
    for dev in devices:
        if dev in runners:
            continue
        if cfg.family == "vlm":
            from repro.models import kimi_vl
            runners[dev] = kimi_vl.Runner(jax.device_put(params, dev), cfg,
                                          max_batch)
        else:
            runners[dev] = _replica_runner(mod, cfg, params, dev, max_batch)

    rng = np.random.default_rng(0)
    if cfg.family == "vlm":
        from repro.models import kimi_vl
        h, w = cfg.frame_hw
        payloads = [kimi_vl.Request(
            rng.standard_normal((h, w, 3), np.float32),
            rng.integers(0, cfg.lm.vocab_size, int(rng.integers(1, 129)),
                         dtype=np.int32)) for _ in range(requests)]
    else:
        h = cfg.img_res
        payloads = list(rng.standard_normal(
            (requests, cfg.img_res, cfg.img_res, 3), np.float32))
    cls = ServiceClass("hd", h, deadline=deadline, proc_time=4.0)
    cls.batch_proc_time = {1: 4.0, 2: 4.6, 4: 5.8, 8: 8.0}
    reps = []
    for i in range(replicas):
        q = FIFOQueue() if queue == "fifo" else None
        reps.append(ServingReplica(i, runners[devices[i % len(devices)]],
                                   queue=q, max_batch=max_batch))
    eng = DeadlineAwareEngine(reps)

    arrivals = np.cumsum(rng.exponential(inter_arrival, size=requests))
    reqs = [eng.submit(payloads[i], cls, now=float(at), origin=i % replicas)
            for i, at in enumerate(arrivals)]
    eng.drain(float(arrivals[-1]))
    results = [r.result for r in reqs]
    if cfg.family == "vlm":
        results = [None if a is None else tuple(int(t) for t in a.ids)
                   for a in results]
    return ServeReport(stats=eng.stats(), results=results,
                       devices=[str(devices[i % len(devices)])
                                for i in range(replicas)])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deit-b")
    ap.add_argument("--full", action="store_true",
                    help="serve the full published config (default: the "
                         "registry's reduced smoke config)")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--queue", default="preferential",
                    choices=["preferential", "fifo"])
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline", type=float, default=30.0)
    ap.add_argument("--inter-arrival", type=float, default=1.2)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rep = serve(args.arch, full=args.full, replicas=args.replicas,
                requests=args.requests, queue=args.queue,
                max_batch=args.max_batch, deadline=args.deadline,
                inter_arrival=args.inter_arrival)
    s = rep.stats
    print(f"{args.queue}: {rep.met_pct:.1f}% deadlines met, "
          f"{s['forwards']} forwards, {s['forced']} forced, "
          f"{s['batches']} device batches, "
          f"{rep.answered}/{args.requests} answered")


if __name__ == "__main__":
    main()
