"""Serving cells: ``DeadlineAwareEngine`` over ``ServingReplica``s whose
``run_batch`` calls the jitted ``repro.models.vit.forward``.

One call is one round of the mix: its frames are submitted on the
engine's clock at the mix's arrival gaps and the engine is drained.
Frames come from a host pool made at set-up; a batch is stacked on the
host, padded to ``max_batch``, copied to the chip, run, and its logits
fetched.  Every answered frame's logits are kept and compared, once the
window has closed, with the plain reference on the same frame.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen


def param_shapes(a: dict) -> Dict[str, tuple]:
    """The served tree's leaves: path -> (shape, fan_in or kind)."""
    L, d, f, p, c = (a["n_layers"], a["d_model"], a["d_ff"], a["patch"],
                     a["in_channels"])
    n_extra = 1 + int(a["distill_token"])
    n_tok = (a["img_res"] // p) ** 2 + n_extra
    ncls = a["n_classes"]
    out = {"patch_embed/w": ((p, p, c, d), p * p * c),
           "patch_embed/b": ((d,), "bias"),
           "cls_token": ((n_extra, d), "embed"),
           "pos_embed": ((n_tok, d), "embed"),
           "final_ln/scale": ((d,), "scale"), "final_ln/bias": ((d,), "bias"),
           "head/w": ((d, ncls), d), "head/b": ((ncls,), "bias")}
    for n in ("ln1", "ln2"):
        out[f"layers/{n}/scale"] = ((L, d), "scale")
        out[f"layers/{n}/bias"] = ((L, d), "bias")
    for n in ("wq", "wk", "wv", "wo"):
        out[f"layers/{n}"] = ((L, d, d), d)
    for n in ("bq", "bk", "bv", "bo", "b_out"):
        out[f"layers/{n}"] = ((L, d), "bias")
    out["layers/w_in"] = ((L, d, f), d)
    out["layers/b_in"] = ((L, f), "bias")
    out["layers/w_out"] = ((L, f, d), f)
    return out


def make_params(arch: tuple, dtype: str, key):
    """Random weights for the whole tree, in one program on the device:
    matrices N(0, 1/fan_in), biases and embeddings N(0, 0.02^2), LayerNorm
    scales 1 + N(0, 0.1^2)."""
    tree: dict = {}
    shapes = param_shapes(dict(arch))
    keys = jax.random.split(key, len(shapes))
    for (path, (shape, kind)), k in zip(sorted(shapes.items()), keys):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "scale":
            v = 1.0 + 0.1 * z
        elif kind in ("bias", "embed"):
            v = 0.02 * z
        else:
            v = z / math.sqrt(kind)
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v.astype(dtype)
    return tree


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits allowed)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, trace: bool):
        from repro.configs.base import ViTConfig
        from repro.models import vit
        from repro.serving.engine import ServiceClass
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        a = cfg["arch"]
        self.arch = tuple(sorted(a.items()))
        vcfg = ViTConfig(name=cfg["name"], **a)
        self.params = jax.jit(make_params, static_argnums=(0, 1))(
            self.arch, a["param_dtype"], seed_key(seed))
        rng = np.random.default_rng(seed)
        res = a["img_res"]
        self.pool = rng.standard_normal(
            (traffic["pool_frames"], res, res, a["in_channels"]), np.float32)
        self.max_batch = cfg["max_batch"]
        fwd = jax.jit(lambda p, x: vit.forward(p, x, vcfg))
        params, pool, mb = self.params, self.pool, self.max_batch

        def run_batch(_cls, payloads):
            idx = list(payloads) + [payloads[0]] * (mb - len(payloads))
            with jax.profiler.TraceAnnotation("bench.stack"):
                imgs = pool[idx]
            with jax.profiler.TraceAnnotation("bench.h2d"):
                x = jax.device_put(imgs)
            with jax.profiler.TraceAnnotation("bench.forward"):
                y = fwd(params, x)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                logits = np.asarray(y)
            return [(i, logits[j]) for j, i in enumerate(payloads)]

        self.run_batch = run_batch
        bpt = {int(k): v for k, v in traffic["batch_proc_time_ut"].items()}
        self.cls = ServiceClass("frame", res, deadline=traffic["deadline_ut"],
                                proc_time=traffic["proc_time_ut"],
                                batch_proc_time=bpt)
        self.rounds = 0
        self.rng = np.random.default_rng([seed, 1])
        run_batch(None, list(range(mb)))      # compile (or load) and warm up

    @staticmethod
    def wait(out):
        return out

    def dispatch(self):
        """One round: submit the mix's frames, drain, return what came back.
        The engine runs every batch to its end, so nothing is left to wait
        for."""
        from repro.core.queues import FIFOQueue
        from repro.serving.engine import DeadlineAwareEngine, ServingReplica
        cfg, tr = self.cfg, self.traffic
        reps = [ServingReplica(i, self.run_batch,
                               queue=FIFOQueue() if cfg["queue"] == "fifo"
                               else None, max_batch=self.max_batch)
                for i in range(cfg["replicas"])]
        eng = DeadlineAwareEngine(reps, max_forwards=cfg["max_forwards"],
                                  rng_seed=self.seed + self.rounds)
        self.rounds += 1
        arrivals = np.cumsum(gen.frame_gaps(tr, self.rng))
        frames = self.rng.integers(0, len(self.pool), len(arrivals))
        reqs = [eng.submit(int(f), self.cls, now=float(t),
                           origin=i % cfg["replicas"])
                for i, (f, t) in enumerate(zip(frames, arrivals))]
        eng.drain(float(arrivals[-1]))
        return dict(results=[r.result for r in reqs], frames=frames,
                    batches=eng.stats()["batches"])

    def units(self, outs) -> Dict[str, float]:
        answered = sum(r is not None for o in outs for r in o["results"])
        return dict(frames=answered, batches=sum(o["batches"] for o in outs),
                    attempted=sum(len(o["results"]) for o in outs),
                    calls=len(outs))

    def end_to_end(self, outs, elapsed: float) -> Dict[str, float]:
        return dict(frames_per_s=self.units(outs)["frames"] / elapsed)

    def frame_flops(self) -> int:
        from bench import work
        return work.vit_forward_flops(self.cfg["arch"])

    def release(self):
        self.run_batch = None

    def check(self, outs, workers: int = 1) -> dict:
        """Every submitted frame's logits against the reference on its pool
        frame: the widest relative L2 error over frames, where a frame that
        was never answered reads 1 (its logits taken as zero)."""
        ref_mod = importlib.import_module(
            f"bench.reference.{self.cfg['reference']}")
        mb = self.max_batch
        ref = np.concatenate([
            np.asarray(ref_mod.forward(self.params, self.pool[i:i + mb],
                                       arch=self.arch))
            for i in range(0, len(self.pool), mb)])
        errs = [1.0 if got is None else
                float(np.linalg.norm(got[1] - ref[want])
                      / np.linalg.norm(ref[want]))
                for o in outs for want, got in zip(o["frames"], o["results"])]
        limit = self.cfg["limits"]["logit_rel_err"]
        self._failed = sum(e > limit for e in errs)
        return dict(logit_rel_err=max(errs, default=0.0))

    def failed(self, _readings) -> int:
        return int(self._failed)
