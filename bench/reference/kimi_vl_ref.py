"""Plain reference for Kimi-VL-A3B answering about camera frames, in float32.

The model as published (Kimi-VL technical report, arXiv 2504.07491; the
language model is the DeepSeek-V3 block, arXiv 2412.19437):

* MoonViT: non-overlapping patches flattened and projected (with bias),
  plus the learned 64 x 64 position table resized bicubically to the
  frame's patch grid; pre-LN blocks (LayerNorm eps 1e-6) of multi-head
  self-attention with 2-D RoPE on q and k and a tanh-GELU MLP, biases on
  every projection; a final LayerNorm; no class token.
* Projector: LayerNorm, each 2 x 2 patches concatenated (row-major in the
  window) to one 4 x 1152 vector, Linear, exact GELU, Linear to 2048.
* LM over [image tokens, prompt, answer], causal: RMSNorm (eps 1e-5)
  pre-norms; multi-head latent attention in its textbook, unabsorbed
  form, ``k = [W_UK c; rope(k_pe)]``, ``v = W_UV c`` for every head, no
  cache; layer 0 a dense SwiGLU, the rest MoE: sigmoid scores, top-6 by
  score plus the correction bias, the picked scores normalized and times
  2.446, each held expert's SwiGLU weighed by its gate (zero where the
  token did not pick it: a loop over the held experts, no capacity), plus
  the shared experts; a final RMSNorm and the untied head.

Conventions of the parameter tree, shared with the program: RMSNorm
weights are stored as offsets from 1 (the weight is ``1 + s``); a RoPE
rotates the first half of a vector's channels against the second
(``rotate_half``); MoonViT's rotary pair j is channels (2j, 2j+1), turned
with the patch's column for even j and its row for odd j, at frequency
``10000 ** (-4 (j // 2) / 72)``.

Departures from the published model: the chip holds experts ``first ..
first + E_held`` of the 64 (the configuration's deployment); what the
other experts would add is left out, here as in the program.  The
position table is resized with ``jax.image.resize`` ("bicubic", Keys
a = -0.5, no antialiasing); PyTorch's bicubic takes a = -0.75.  The chat
template's special tokens are not added: the prompt's ids stand alone.

Routing may be forced (:func:`forward`'s ``image_routes`` and a group's
``routes``): the reference then computes the experts the program picked,
weighed by its own float32 scores, and reports how far each forced pick's
biased score falls below its own k-th best.  Among 26 layers' top-6 of 64
sigmoid scores some near-ties fall the other way in bfloat16, and one
expert swapped moves a token's logits as far as float8 moves every
product; forced, the logits compare the arithmetic, and the score gap
compares the routing.

Causal attention lets each frame's image tokens go through each layer
once: no token after them changes their states, so every request's text
attends to its frame's image keys and values and to its own.  The whole
computation is layer-major: each layer's weights are cast to float32 as
it runs, so the reference fits beside the bfloat16 weights.  Every
matrix product runs at ``highest`` precision; ``fp8=True`` rounds both
operands of every product to float8 (e4m3, one scale per tensor) first:
the control one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _ln(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rms(x, s, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1 + s)


def _softmax_attn(q, k, v, mask, fp8):
    """q (..., Sq, H, e), k (..., Sk, H, e), v (..., Sk, H, f); mask (Sq, Sk)
    or broadcastable (..., 1, Sq, Sk)."""
    s = _mm("...qhe,...khe->...hqk", q, k, fp8) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, NEG), -1)
    return _mm("...hqk,...khf->...qhf", p, v, fp8)


# ---------------------------------------------------------------------------
# MoonViT and the projector
# ---------------------------------------------------------------------------
def _rope2d(x, rows, cols):
    """x (N, H, D) with N = rows * cols patches, row-major."""
    D = x.shape[-1]
    freqs = 10000.0 ** (-np.arange(0, D, 4) / D)               # (D/4,)
    r, c = np.divmod(np.arange(rows * cols), cols)
    ang = np.zeros((rows * cols, D // 2))
    ang[:, 0::2] = c[:, None] * freqs
    ang[:, 1::2] = r[:, None] * freqs
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _patches(vp, frames, *, a, fp8):
    d, p = dict(a)["v_d_model"], dict(a)["patch"]
    vp = _f32(vp)
    F, Hh, W, C = frames.shape
    gh, gw = Hh // p, W // p
    x = frames.reshape(F, gh, p, gw, p, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(F, gh * gw, p * p * C)
    x = _mm("fnk,kd->fnd", x, vp["patch_embed"]["w"].reshape(-1, d), fp8)
    g0 = dict(a)["pos_grid"]
    pos = jax.image.resize(vp["pos_embed"].reshape(g0, g0, d), (gh, gw, d),
                           "bicubic", antialias=False)
    return x + vp["patch_embed"]["b"] + pos.reshape(gh * gw, d)


@functools.partial(jax.jit, static_argnames=("grid", "a", "fp8"))
def _tower_layer(lp, x, *, grid, a, fp8):
    """One MoonViT block over x (F, N, d), a frame at a time."""
    nh = dict(a)["v_heads"]
    lp = _f32(lp)

    def one(xf):
        N, d = xf.shape
        y = _ln(xf, lp["ln1"]["scale"], lp["ln1"]["bias"])
        q, k, v = ((_mm("nd,de->ne", y, lp[f"w{n}"], fp8) + lp[f"b{n}"])
                   .reshape(N, nh, d // nh) for n in "qkv")
        q, k = _rope2d(q, *grid), _rope2d(k, *grid)
        o = _softmax_attn(q, k, v, jnp.ones((N, N), bool), fp8)
        xf = xf + _mm("nd,de->ne", o.reshape(N, d), lp["wo"], fp8) + lp["bo"]
        y = _ln(xf, lp["ln2"]["scale"], lp["ln2"]["bias"])
        z = jax.nn.gelu(_mm("nd,df->nf", y, lp["w_in"], fp8) + lp["b_in"],
                        approximate=True)
        return xf + _mm("nf,fd->nd", z, lp["w_out"], fp8) + lp["b_out"]

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("grid", "a", "fp8"))
def _project(vp, pp, x, *, grid, a, fp8):
    vp, pp = _f32(vp), _f32(pp)
    x = _ln(x, vp["final_ln"]["scale"], vp["final_ln"]["bias"])
    x = _ln(x, pp["ln"]["scale"], pp["ln"]["bias"])
    F, _, d = x.shape
    m = dict(a)["merge"]
    (gh, gw) = grid
    x = x.reshape(F, gh // m, m, gw // m, m, d).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(F, (gh // m) * (gw // m), m * m * d)
    x = jax.nn.gelu(_mm("fnk,km->fnm", x, pp["w1"], fp8) + pp["b1"],
                    approximate=False)
    return _mm("fnm,md->fnd", x, pp["w2"], fp8) + pp["b2"]


def image_tokens(params, frames, *, arch, fp8: bool = False):
    """frames (F, H, W, 3) -> the LM's image tokens (F, n_image, d) f32."""
    a = dict(arch)
    p = a["patch"]
    grid = (frames.shape[1] // p, frames.shape[2] // p)
    vp = params["vision"]
    x = _patches({k: vp[k] for k in ("patch_embed", "pos_embed")},
                 jnp.asarray(frames, jnp.float32), a=arch, fp8=fp8)
    for i in range(a["v_layers"]):
        x = _tower_layer(jax.tree.map(lambda t: t[i], vp["layers"]), x,
                         grid=grid, a=arch, fp8=fp8)
    return _project({"final_ln": vp["final_ln"]}, params["projector"], x,
                    grid=grid, a=arch, fp8=fp8)


# ---------------------------------------------------------------------------
# The language model
# ---------------------------------------------------------------------------
def _rope(x, pos, theta):
    """x (..., S, H, e), pos (S,): rotate_half RoPE."""
    e = x.shape[-1] // 2
    ang = pos[:, None] * theta ** (-np.arange(e) / e)            # (S, e)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :e], x[..., e:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _qkv(lp, h, pos, a, fp8):
    """h (..., S, d) -> per-head q, k (..., S, H, dn + dr), v (..., S, H, dv)."""
    H, dn, dr, dv, r = (a["heads"], a["qk_nope_head_dim"],
                        a["qk_rope_head_dim"], a["v_head_dim"],
                        a["kv_lora_rank"])
    x = _rms(h, lp["ln1"], a["eps"])
    q = _mm("...d,de->...e", x, lp["wq"], fp8)
    q = q.reshape(*q.shape[:-1], H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, a["theta"])], -1)
    kv = _mm("...d,de->...e", x, lp["wkv_a"], fp8)
    c = _rms(kv[..., :r], lp["kv_norm"], a["eps"])
    k_pe = _rope(kv[..., None, r:], pos, a["theta"])             # one head
    up = _mm("...r,re->...e", c, lp["wkv_b"], fp8)
    up = up.reshape(*up.shape[:-1], H, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_pe, (*up.shape[:-1], dr))], -1)
    return q, k, up[..., dn:]


def _swiglu(x, wg, wu, wd, fp8):
    g = _mm("td,df->tf", x, wg, fp8)
    return _mm("tf,fd->td", jax.nn.silu(g) * _mm("td,df->tf", x, wu, fp8),
               wd, fp8)


def _route(lp, x, a, force):
    """Sigmoid routing of x (T, d), normed, in float32 (never float8, as
    published implementations keep the router).  ``force`` (T, K): the
    program's picks, or -1 where this routes on its own; a forced row
    weighs the forced experts by this reference's own scores.  Returns
    (picks, gates, per-row stats (T, 4): forced picks outside this
    reference's own top-k; how far the lowest forced pick's biased score
    falls below the k-th best (0 where the sets agree); own picks among the
    held experts; forced picks among them)."""
    K, first = a["top_k"], a["first_expert"]
    scores = jax.nn.sigmoid(_mm("td,de->te", x, lp["router"], False))
    biased = scores + lp["router_bias"]
    best, own = jax.lax.top_k(biased, K)
    forced = force[:, :1] >= 0
    top = jnp.where(forced, force, own)
    picked = jnp.take_along_axis(scores, top, -1)
    gates = picked / jnp.sum(picked, -1, keepdims=True) * a["routed_scale"]
    n_held = lp["we_gate"].shape[0]
    held = lambda e: jnp.sum((e >= first) & (e < first + n_held), -1)
    stats = jnp.stack([
        jnp.sum(~jnp.any(top[:, :, None] == own[:, None, :], -1), -1),
        jnp.maximum(best[:, -1] - jnp.min(
            jnp.take_along_axis(biased, top, -1), -1), 0.0),
        held(own), held(top)], -1).astype(jnp.float32)
    return top, gates, jnp.where(forced, stats, 0.0)


def _ffn(lp, x, a, dense, fp8, force=None):
    """x (T, d) normed -> (output, per-row routing stats (T, 4) of
    :func:`_route`, zero for a dense layer)."""
    if dense:
        return (_swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], fp8),
                jnp.zeros((x.shape[0], 4)))
    if force is None:
        force = -jnp.ones((x.shape[0], a["top_k"]), jnp.int32)
    top, gates, stats = _route(lp, x, a, force)

    def expert(acc, e):
        w = jnp.sum(jnp.where(top == a["first_expert"] + e, gates, 0.0), -1)
        y = _swiglu(x, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e], fp8)
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          jnp.arange(lp["we_gate"].shape[0]))
    return (out + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], fp8),
            stats)


def _block_ffn(lp, h, a, dense, fp8, force):
    """h (N, T, d) -> (h after the FFN half, per-row stats (N, 5): forced
    picks outside the own top-k, the largest score gap, own and forced
    picks among the held experts, forced positions)."""
    shape = h.shape
    x = _rms(h, lp["ln2"], a["eps"]).reshape(-1, shape[-1])
    if force is not None:
        force = force.reshape(-1, force.shape[-1]).astype(jnp.int32)
    out, st = _ffn(lp, x, a, dense, fp8, force)
    st = st.reshape(*shape[:2], 4)
    n = (jnp.sum(force.reshape(*shape[:2], -1)[..., 0] >= 0, -1)
         if force is not None else jnp.zeros(shape[:1]))
    stats = jnp.stack([st[..., 0].sum(-1), st[..., 1].max(-1),
                       st[..., 2].sum(-1), st[..., 3].sum(-1),
                       n.astype(jnp.float32)], -1)
    return h + out.reshape(shape), stats


def _attend(lp, q, k, v, mask, fp8):
    o = _softmax_attn(q, k, v, mask, fp8)
    return _mm("...e,ed->...d", o.reshape(*o.shape[:2], -1), lp["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("a", "dense", "fp8"))
def _image_layer(lp, h, force, *, a, dense, fp8):
    """One layer over the image rows h (F, n, d), causal, routed as
    ``force`` (F, n, K) says (None: on its own); returns the new rows, the
    layer's keys and values of the old ones, and the routing stats."""
    a_ = dict(a)
    lp = _f32(lp)
    n = h.shape[1]
    q, k, v = _qkv(lp, h, np.arange(n), a_, fp8)
    h = h + _attend(lp, q, k, v, np.tril(np.ones((n, n), bool)), fp8)
    h, stats = _block_ffn(lp, h, a_, dense, fp8, force)
    return h, k, v, stats


@functools.partial(jax.jit, static_argnames=("a", "dense", "fp8"))
def _text_layer(lp, h, frame_of, k_img, v_img, force, *, a, dense, fp8):
    """One layer over text rows h (N, T, d) at positions n .. n + T - 1,
    each attending to its image rows' keys and values and its own, routed
    as ``force`` (N, T, K) says; returns the new rows and routing stats."""
    a_ = dict(a)
    lp = _f32(lp)
    n, T = k_img.shape[1], h.shape[1]
    q, k, v = _qkv(lp, h, n + np.arange(T), a_, fp8)
    mask = np.concatenate([np.ones((T, n), bool),
                           np.tril(np.ones((T, T), bool))], axis=1)

    def one(xs):                      # a request against its frame's keys
        q1, k1, v1, f = xs
        return _softmax_attn(q1, jnp.concatenate([k_img[f], k1]),
                             jnp.concatenate([v_img[f], v1]), mask, fp8)

    o = jax.lax.map(one, (q, k, v, frame_of), batch_size=8)
    h = h + _mm("...e,ed->...d", o.reshape(*o.shape[:2], -1), lp["wo"], fp8)
    return _block_ffn(lp, h, a_, dense, fp8, force)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _embed(lm, tokens, *, a, fp8):
    return lm["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _head(lm, h, read_at, *, a, fp8):
    h = h[jnp.arange(h.shape[0])[:, None], read_at]             # (N, R, d)
    h = _rms(h, lm["final_norm"].astype(jnp.float32), dict(a)["eps"])
    return _mm("nrd,dv->nrv", h, lm["lm_head"].astype(jnp.float32), fp8)


def forward(params, images, groups, *, arch, fp8: bool = False,
            chunk: int = 32, image_routes=None):
    """Logits of requests, each an image and a text.

    ``images`` (F, n_image, d): :func:`image_tokens` of the frames.
    ``groups``: a list of ``(image_of (N,), tokens (N, T), read_at (N, R))``
    or of ``(image_of, tokens, read_at, routes)``, tokens right-padded
    (causal attention keeps padding out of every earlier position); returns,
    per group, the logits (N, R, V) that predict the token after each text
    position ``read_at``.  Requests go through in ``chunk``-row blocks, so
    that a block's shape depends on T alone.

    Routing is this reference's own, except where the program's picks are
    given: ``image_routes`` (F, L_moe, n_image, K) for the image rows and a
    group's ``routes`` (N, L_moe, T, K) for its text, -1 at a position that
    routes on its own.  Forced positions weigh the forced experts by this
    reference's scores, so that a bfloat16 rounding that flips a near-tie
    pick cannot move the logits as far as a wrong expert would; how far
    each forced pick falls from this reference's own top-k is returned
    beside the logits: ``(logits per group, stats)``, ``stats["image"]``
    (F, 5) and ``stats["text"]`` a list per group of (N, 5), summed over the
    layers (the score gap: its largest), as :func:`_block_ffn` gives."""
    a = dict(arch)
    lm = params["lm"]
    blocks = []                                   # (group, rows, arrays)
    for g, grp in enumerate(groups):
        fo, tok, read = map(np.asarray, grp[:3])
        N, T = tok.shape
        routes = (np.asarray(grp[3]) if len(grp) > 3 else
                  None)
        pad = -N % chunk
        fo, tok, read = (np.concatenate([x, np.zeros((pad, *x.shape[1:]),
                                                     x.dtype)])
                         for x in (fo, tok, read))
        if routes is not None:
            routes = np.concatenate([routes, -np.ones(
                (pad, *routes.shape[1:]), routes.dtype)])
        for r in range(0, N + pad, chunk):
            blocks.append((g, min(chunk, N - r), fo[r:r + chunk],
                           tok[r:r + chunk], read[r:r + chunk],
                           None if routes is None else
                           jnp.asarray(routes[r:r + chunk], jnp.int32)))
    h_txt = [_embed({"embed": lm["embed"]}, jnp.asarray(b[3]), a=arch,
                    fp8=fp8) for b in blocks]
    image_of = [jnp.asarray(b[2]) for b in blocks]
    h_img = images
    img_routes = (None if image_routes is None
                  else jnp.asarray(image_routes, jnp.int32))
    # each layer's stats stay on the device until the end: a fetch per
    # layer and block would stall the device between calls
    st_img, st_txt = [], [[] for _ in blocks]
    stacks = [(lm["dense_layers"], True)] if "dense_layers" in lm else []
    moe_layer = 0
    for stack, dense in stacks + [(lm["layers"], False)]:
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            lp = jax.tree.map(lambda t: t[i], stack)
            j = None if dense else moe_layer
            force = (None if j is None or img_routes is None
                     else img_routes[:, j])
            new_img, k, v, st = _image_layer(lp, h_img, force, a=arch,
                                             dense=dense, fp8=fp8)
            st_img.append(st)
            new_txt = []
            for h, b, fo, acc in zip(h_txt, blocks, image_of, st_txt):
                force = None if j is None or b[5] is None else b[5][:, j]
                h, st = _text_layer(lp, h, fo, k, v, force, a=arch,
                                    dense=dense, fp8=fp8)
                acc.append(st)
                new_txt.append(h)
            h_txt, h_img = new_txt, new_img
            moe_layer += not dense
    head = {"final_norm": lm["final_norm"], "lm_head": lm["lm_head"]}
    out = [[] for _ in groups]
    stats = [[] for _ in groups]
    st_img, st_txt = jax.device_get((st_img, st_txt))
    st_img, st_txt = _total(st_img), [_total(st) for st in st_txt]
    for h, (g, rows, _, _, read, _), st in zip(h_txt, blocks, st_txt):
        out[g].append(np.asarray(_head(head, h, jnp.asarray(read), a=arch,
                                       fp8=fp8))[:rows])
        stats[g].append(st[:rows])
    return ([np.concatenate(o) for o in out],
            dict(image=st_img, text=[np.concatenate(s) for s in stats]))


def _total(per_layer):
    """Per-row stats of every layer -> their sum over the layers; the score
    gap: its largest."""
    st = np.asarray(per_layer, np.float64)                 # (layers, rows, 5)
    tot = st.sum(0)
    tot[:, 1] = st[:, :, 1].max(0)
    return tot
