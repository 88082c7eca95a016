"""Vision-language serving cells: ``DeadlineAwareEngine`` over
``ServingReplica``s whose ``run_batch`` is ``repro.models.kimi_vl.Runner``.

One call is one round of the mix: bursts of frames, each burst at one
instant and one origin replica (one motion event seen by a group of
cameras), at Poisson gaps on the engine's clock; each frame comes with a
question, a prompt of random token ids whose length is lognormal and
clipped.  The engine is drained at the end of the round.  Frames come
from a host pool made at set-up; every answered request keeps its first
answer token's logits, and the first request of each round all its answer
tokens' logits, and every request the experts each layer picked at each
position.  Once the window has closed they are compared with the plain
reference on the same (frame, prompt), the answer teacher-forced and the
routing forced to the program's picks; each pick's score is compared with
the reference's own top-k.
"""
from __future__ import annotations

import importlib
import math
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, vlm_work
from bench.drivers.vit_serve import seed_key


def vlm_config(cfg: dict, traffic: dict):
    """The program's configuration from the file's catalog keys."""
    from repro.configs.base import LMConfig, ViTConfig, VLMConfig
    v = cfg["vision"]
    vision = ViTConfig(
        name=cfg["name"] + "-tower", img_res=v["pos_grid"] * v["patch"],
        patch=v["patch"], n_layers=v["num_hidden_layers"],
        d_model=v["hidden_size"], n_heads=v["num_attention_heads"],
        d_ff=v["intermediate_size"], n_classes=0, class_token=False,
        rope_2d=True, pos_interp="bicubic", param_dtype=cfg["param_dtype"],
        remat=False, attn_impl="chunked", attn_chunk=768)
    held = cfg["experts_held"]
    lm = LMConfig(
        name=cfg["name"] + "-lm", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        moe=True, n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"], rope_theta=cfg["rope_theta"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], router=cfg["scoring_func"],
        routed_scale=cfg["routed_scaling_factor"], held_experts=(0, held),
        embed_scale=False, norm_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["param_dtype"], remat=False, attn_impl="naive")
    return VLMConfig(name=cfg["name"], vision=vision, lm=lm,
                     merge=cfg["projector"]["merge"],
                     frame_hw=(traffic["frame_h"], traffic["frame_w"]),
                     answer_len=traffic["answer_tokens"])


def arch(cfg: dict) -> tuple:
    """What the reference needs of the configuration, hashable."""
    v = cfg["vision"]
    return tuple(sorted(dict(
        patch=v["patch"], v_d_model=v["hidden_size"],
        v_layers=v["num_hidden_layers"], v_heads=v["num_attention_heads"],
        pos_grid=v["pos_grid"], merge=cfg["projector"]["merge"],
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"], first_expert=0).items()))


def _init(path: str, shape, dtype, key):
    """One leaf: matrices N(0, 1/fan_in), biases and the position table
    N(0, 0.02^2), token embeddings N(0, 1), LayerNorm scales and RMSNorm
    weights (stored as offsets from 1) 1 + N(0, 0.1^2) and 0.1 N(0, 1),
    the expert-score correction bias 0.05 N(0, 1)."""
    name = path.split("/")[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        v = 1.0 + 0.1 * z
    elif name in ("ln1", "ln2", "kv_norm", "final_norm"):
        v = 0.1 * z
    elif name == "router_bias":
        v = 0.05 * z
    elif name == "embed":
        v = z
    elif name in ("pos_embed", "bias") or name.startswith("b"):
        v = 0.02 * z
    else:
        fan_in = int(np.prod(shape[-4:-1])) if name == "w" else shape[-2]
        v = z / math.sqrt(fan_in)
    return v.astype(dtype)


def make_params(vcfg):
    """Random weights for the whole tree on the device, stacked layers drawn
    one layer at a time so that no float32 draw of a stack is ever whole."""
    from repro.models import kimi_vl

    def make(key):
        tree: dict = {}
        defs = kimi_vl.param_defs(vcfg)
        for i, path in enumerate(sorted(defs)):
            d = defs[path]
            k = jax.random.fold_in(key, i)
            stacked = {"layers", "dense_layers"} & set(path.split("/"))
            if stacked and len(d.shape) >= 3:
                keys = jax.random.split(k, d.shape[0])
                v = jax.lax.map(lambda kk: _init(path, d.shape[1:], d.dtype,
                                                 kk), keys)
            else:
                v = _init(path, d.shape, d.dtype, k)
            node = tree
            parts = path.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = v
        return tree

    return make


def prompt_lengths(traffic: dict, rng: np.random.Generator, n: int):
    """Lognormal prompt lengths with the mix's median, clipped."""
    x = np.exp(math.log(traffic["prompt_median"])
               + traffic["prompt_sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), traffic["prompt_min"],
                   traffic["prompt_max"]).astype(int)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, trace: bool):
        from repro.models import kimi_vl
        from repro.serving.engine import ServiceClass
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.vcfg = vlm_config(cfg, traffic)
        self.arch = arch(cfg)
        self.top_k = cfg["num_experts_per_tok"]
        self.params = jax.jit(make_params(self.vcfg))(seed_key(seed))
        rng = np.random.default_rng(seed)
        h, w = traffic["frame_h"], traffic["frame_w"]
        self.pool = rng.standard_normal((traffic["pool_frames"], h, w, 3),
                                        np.float32)
        self.max_batch = cfg["max_batch"]
        self.runner = kimi_vl.Runner(
            self.params, self.vcfg, self.max_batch, traffic["prompt_max"],
            span=lambda name: jax.profiler.TraceAnnotation("bench." + name))
        bpt = {int(k): v for k, v in traffic["batch_proc_time_ut"].items()}
        self.cls = ServiceClass("vqa", w, deadline=traffic["deadline_ut"],
                                proc_time=traffic["proc_time_ut"],
                                batch_proc_time=bpt)
        self.rounds = 0
        self.rng = np.random.default_rng([seed, 1])
        # compile (or load) and warm up every prompt block count
        V = self.vcfg.lm.vocab_size
        for P in range(kimi_vl.PROMPT_BLOCK, traffic["prompt_max"] + 1,
                       kimi_vl.PROMPT_BLOCK):
            self.runner("vqa", [kimi_vl.Request(
                self.pool[i % len(self.pool)],
                rng.integers(0, V, P).astype(np.int32), logits=i == 0)
                for i in range(self.max_batch)])
        self._routed = self.runner.routed

    @staticmethod
    def wait(out):
        return out

    def dispatch(self):
        """One round: submit the bursts, drain, return what came back."""
        from repro.core.queues import FIFOQueue
        from repro.models import kimi_vl
        from repro.serving.engine import DeadlineAwareEngine, ServingReplica
        cfg, tr = self.cfg, self.traffic
        reps = [ServingReplica(i, self.runner,
                               queue=FIFOQueue() if cfg["queue"] == "fifo"
                               else None, max_batch=self.max_batch)
                for i in range(cfg["replicas"])]
        eng = DeadlineAwareEngine(reps, max_forwards=cfg["max_forwards"],
                                  rng_seed=self.seed + self.rounds)
        self.rounds += 1
        nb, bf = tr["bursts_per_round"], tr["burst_frames"]
        bursts = np.cumsum(gen.frame_gaps(dict(
            frames_per_round=nb, inter_arrival_ut=tr["inter_burst_ut"]),
            self.rng))
        frames = self.rng.integers(0, len(self.pool), nb * bf)
        lens = prompt_lengths(tr, self.rng, nb * bf)
        V = self.vcfg.lm.vocab_size
        prompts = [self.rng.integers(0, V, n).astype(np.int32) for n in lens]
        reqs = [eng.submit(kimi_vl.Request(self.pool[f], p, logits=i == 0),
                           self.cls, now=float(bursts[i // bf]),
                           origin=(i // bf) % cfg["replicas"])
                for i, (f, p) in enumerate(zip(frames, prompts))]
        eng.drain(float(bursts[-1]))
        routed, self._routed = self.runner.routed - self._routed, \
            self.runner.routed
        return dict(results=[r.result for r in reqs], frames=frames,
                    prompts=prompts, batches=eng.stats()["batches"],
                    routed=routed)

    def units(self, outs) -> Dict[str, float]:
        answered = [len(p) for o in outs
                    for p, r in zip(o["prompts"], o["results"])
                    if r is not None]
        batches = sum(o["batches"] for o in outs)
        routes, experts = (int(sum(o["routed"][i] for o in outs))
                           for i in (0, 1))
        cfg, tr = self.cfg, self.traffic
        frames = len(answered)
        self._frame_flops = (vlm_work.served_flops(cfg, tr, answered, routes)
                             / frames if frames else 0.0)
        return dict(
            frames=frames, batches=batches,
            attempted=sum(len(o["results"]) for o in outs), calls=len(outs),
            routes=routes, experts_used=experts,
            tower_flops=frames * vlm_work.tower_block_flops(cfg, tr),
            mla_decode_bytes=vlm_work.mla_decode_bytes(cfg, tr, answered,
                                                       batches),
            moe_flops=routes * vlm_work.expert_route_flops(cfg),
            moe_bytes=vlm_work.moe_expert_bytes(cfg, routes, experts))

    def end_to_end(self, outs, elapsed: float) -> Dict[str, float]:
        return dict(frames_per_s=self.units(outs)["frames"] / elapsed)

    def frame_flops(self) -> float:
        """Mean model FLOPs of an answered request of the last ``units``."""
        return self._frame_flops

    def release(self):
        self.runner = None

    def errors(self, outs, fp8: bool = False):
        """Relative L2 errors against the reference (``fp8``: the float8
        control) routed as the program routed: every answered request's
        first answer token's logits, and each round's first request's worst
        answer token; a request never answered reads 1.  Returns (prefill
        errors, decode errors, routing), where routing holds each compared
        request's largest score gap (how far a program's pick falls below
        the reference's own k-th best biased score, over its image and text
        positions and every layer; ``gaps`` in the order of the prefill
        errors, ``gaps_decode`` of the decode errors) and the totals of the
        picks compared."""
        from repro.models import kimi_vl
        ref = importlib.import_module(
            f"bench.reference.{self.cfg['reference']}")
        block, A = kimi_vl.PROMPT_BLOCK, self.traffic["answer_tokens"]
        n_img = vlm_work.image_tokens(self.cfg, self.traffic)
        answered = [(f, p, r) for o in outs
                    for f, p, r in zip(o["frames"], o["prompts"], o["results"])
                    if r is not None]
        firsts = [(o["frames"][0], o["prompts"][0], o["results"][0])
                  for o in outs if o["results"][0] is not None]
        # the image rows of one frame run alike wherever the program routed
        # them alike: one reference pass per (frame, image picks)
        variants: Dict[tuple, int] = {}

        def image_of(f, r):
            key = (int(f), r.routes[:, :n_img].tobytes())
            return variants.setdefault(key, len(variants))

        def text_routes(rows, T, extra):
            """The program's picks at each row's prompt and its first
            ``extra`` answer tokens; -1 past them."""
            out = -np.ones((len(rows), *rows[0][2].routes.shape[:1], T,
                            rows[0][2].routes.shape[-1]), np.int16)
            for i, (_, p, r) in enumerate(rows):
                n = len(p) + extra
                out[i, :, :n] = r.routes[:, n_img:n_img + n]
            return out

        groups, got = [], []
        for P in sorted({-(-len(p) // block) * block for _, p, _ in answered}):
            rows = [(f, p, r) for f, p, r in answered
                    if -(-len(p) // block) * block == P]
            tok = np.zeros((len(rows), P), np.int32)
            for i, (_, p, _) in enumerate(rows):
                tok[i, :len(p)] = p
            groups.append((np.array([image_of(f, r) for f, _, r in rows]), tok,
                           np.array([[len(p) - 1] for _, p, _ in rows]),
                           text_routes(rows, P, 0)))
            got.append(np.stack([r.first_logits for _, _, r in rows])[:, None])
        if firsts:
            T = self.traffic["prompt_max"] + A
            tok = np.zeros((len(firsts), T), np.int32)
            for i, (_, p, r) in enumerate(firsts):
                tok[i, :len(p) + A - 1] = np.concatenate([p, r.ids[:-1]])
            groups.append((np.array([image_of(f, r) for f, _, r in firsts]),
                           tok, np.array([len(p) - 1 + np.arange(A)
                                          for _, p, _ in firsts]),
                           text_routes(firsts, T, A - 1)))
            got.append(np.stack([r.logits for _, _, r in firsts]))
        want, routing = [], dict(gaps=[], gaps_decode=[], picks=0, flips=0,
                                 token_layers=0, held_program=0,
                                 held_reference=0)
        if groups:
            keys = sorted(variants, key=variants.get)
            # the tower runs on the whole pool; a frame's image rows route
            # alike at one prompt block count, so as a rule there are at
            # most pool x block counts variants: padded to a multiple of that
            # with rows that route on their own (and so count in no routing
            # total), every shape of the check repeats from run to run, and
            # the compile cache serves it
            pad = -len(keys) % (len(self.pool)
                                * (self.traffic["prompt_max"] // block))
            frames = np.array([f for f, _ in keys] + [0] * pad)
            images = ref.image_tokens(self.params, self.pool, arch=self.arch,
                                      fp8=fp8)[frames]
            routes = [np.frombuffer(b, np.int16).reshape(
                -1, n_img, self.top_k) for _, b in keys]
            img_routes = np.stack(routes + [-np.ones_like(routes[0])] * pad)
            want, stats = ref.forward(self.params, images, groups,
                                      arch=self.arch, fp8=fp8,
                                      image_routes=img_routes)
            want = [np.asarray(w) for w in want]
            img = stats["image"]
            for k, ((fo, *_), st) in enumerate(zip(groups, stats["text"])):
                gaps = [float(max(g, img[f, 1])) for f, g in zip(fo, st[:, 1])]
                if firsts and k == len(groups) - 1:
                    routing["gaps_decode"] = gaps
                else:
                    routing["gaps"] += gaps
            # picks of the image rows count once per variant, not per request
            for st in [img] + stats["text"]:
                routing["flips"] += int(st[:, 0].sum())
                routing["held_reference"] += int(st[:, 2].sum())
                routing["held_program"] += int(st[:, 3].sum())
                routing["token_layers"] += int(st[:, 4].sum())
            routing["picks"] = routing["token_layers"] * self.top_k
        errs = [np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                for g, w in zip(got, want)]
        n_pre = len(groups) - bool(firsts)
        pre = [float(e) for x in errs[:n_pre] for e in x.ravel()]
        pre += [1.0] * sum(r is None for o in outs for r in o["results"])
        dec = [float(e) for e in errs[-1].max(axis=1)] if firsts else []
        dec += [1.0] * sum(o["results"][0] is None for o in outs)
        return pre, dec, routing

    def check(self, outs, workers: int = 1) -> dict:
        """Every answered request's first answer token's logits, and all
        answer tokens' logits of each round's first request, against the
        reference on the same frame and prompt, teacher-forced on the
        program's answer and routed as the program routed: the widest
        relative L2 error of each; and the widest score gap of a program's
        pick below the reference's own top-k."""
        pre, dec, routing = self.errors(outs)
        lim = self.cfg["limits"]
        gap = lim["route_score_gap"]
        # an unanswered request has no gap and reads 1, over every limit
        gaps = routing["gaps"] + [0.0] * (len(pre) - len(routing["gaps"]))
        dgaps = routing["gaps_decode"] + [0.0] * (
            len(dec) - len(routing["gaps_decode"]))
        self._failed = (
            sum(e > lim["prefill_logit_rel_err"] or g > gap
                for e, g in zip(pre, gaps))
            + sum(e > lim["decode_logit_rel_err"] or g > gap
                  for e, g in zip(dec, dgaps)))
        tl = max(routing["token_layers"], 1)
        print(f"routing: {routing['picks']} picks compared, "
              f"{routing['flips']} outside the reference's own top-"
              f"{self.top_k}; held experts per token and layer: program "
              f"{routing['held_program'] / tl!r}, reference "
              f"{routing['held_reference'] / tl!r}", file=sys.stderr,
              flush=True)
        return dict(prefill_logit_rel_err=max(pre, default=0.0),
                    decode_logit_rel_err=max(dec, default=0.0),
                    route_score_gap=max(gaps + dgaps, default=0.0))

    def failed(self, _readings) -> int:
        return int(self._failed)
