"""Plain float32 reference of UViT training: forward, DDPM loss, gradients
and AdamW, written from the equations and imported from nowhere in the
program under test.

The model is U-ViT (Bao et al., arXiv:2209.12152) as PULSE scales it
(arXiv:2606.19163, section VII-B): a ViT over the patch tokens plus a time
token and a class token, ``n_layers / 2`` encoder blocks whose outputs are
kept, and as many decoder blocks, decoder block ``j`` taking the output of
encoder block ``half - 1 - j`` through ``[x | skip] @ skip_proj``.  Where it
departs from U-ViT it follows the configuration the program trains (these
are the configuration's, not shortcuts of the reference):

- RMSNorm with a scale and no bias, where U-ViT uses LayerNorm;
- GELU in its tanh form, where U-ViT's MLP uses the exact one;
- no biases on the attention projections or on ``skip_proj``;
- the time token is a GELU MLP (width 4d) over the sinusoidal embedding;
- no middle block: 16 + 16 blocks, not U-ViT's odd depths;
- the DDPM objective with the cosine schedule (Nichol & Dhariwal) and
  ``t`` uniform in [0, 1), predicting the noise, mean squared error.

Weights come from the seed by the same splitting of the key that the
configuration's initialisation states (normal, scaled by 1/sqrt(fan-in),
rounded to the parameter dtype); the reference makes them itself.

Every matrix product runs at ``Precision.HIGHEST`` in float32.  The
control of the benchmark's comparison is this same reference with every
matrix product computed in float8 (e4m3 under a per-tensor scale, forward
and backward), the next precision below the configuration's bfloat16.

The network runs block by block, each block on the device that holds it,
and the backward pass recomputes one block at a time from its saved input
(``jax.vjp``), so the 32-layer model fits on four chips beside nothing
else.  Parameters are stored in the configuration's dtype (bfloat16) and
AdamW's moments in float32, as the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: largest finite value of float8 e4m3 (what a per-tensor scale maps to)
E4M3_MAX = 448.0


# --------------------------------------------------------------------------
# precision of the matrix products
# --------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale, back in f32."""
    s = jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_FP8_PRODUCTS = {}


def _fp8_product(spec: str):
    """``einsum(spec)`` whose operands are rounded to float8 in the forward
    pass, and whose backward products take the forward's rounded operands
    and the cotangent rounded to float8: a matrix product computed in fp8
    both ways, accumulated in float32."""
    if spec not in _FP8_PRODUCTS:
        prod = lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST)

        @jax.custom_vjp
        def f(a, b):
            return prod(_fp8(a), _fp8(b))

        def fwd(a, b):
            qa, qb = _fp8(a), _fp8(b)
            return prod(qa, qb), (qa, qb)

        def bwd(res, g):
            return jax.vjp(prod, *res)[1](_fp8(g))

        f.defvjp(fwd, bwd)
        _FP8_PRODUCTS[spec] = f
    return _FP8_PRODUCTS[spec]


def einsum(spec, a, b, operand: str = "float32"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if operand == "float8":
        return _fp8_product(spec)(a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def mm(a, b, operand: str = "float32"):
    return einsum("...k,kn->...n", a, b, operand)


# --------------------------------------------------------------------------
# initialisation from the seed
# --------------------------------------------------------------------------

def _dense(key, fan_in: int, fan_out: int, dtype):
    return (jax.random.normal(key, (fan_in, fan_out))
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _mlp_init(key, d: int, hidden: int, dtype) -> dict:
    k = jax.random.split(key, 2)
    return {"w_up": _dense(k[0], d, hidden, dtype),
            "b_up": jnp.zeros((hidden,), dtype),
            "w_down": _dense(k[1], hidden, d, dtype),
            "b_down": jnp.zeros((d,), dtype)}


def _block_init(key, c: dict, with_skip: bool, dtype) -> dict:
    d, inner = c["d_model"], c["n_heads"] * c["head_dim"]
    k = jax.random.split(key, 6)
    ka = jax.random.split(k[0], 4)
    p = {"ln1": jnp.ones((d,), dtype),
         "attn": {"wq": _dense(ka[0], d, inner, dtype),
                  "wk": _dense(ka[1], d, inner, dtype),
                  "wv": _dense(ka[2], d, inner, dtype),
                  "wo": _dense(ka[3], inner, d, dtype)},
         "ln2": jnp.ones((d,), dtype),
         "mlp": _mlp_init(k[1], d, c["d_ff"], dtype)}
    if with_skip:
        p["skip_proj"] = _dense(k[2], 2 * d, d, dtype)
    return p


def n_tokens(c: dict, latent: int) -> int:
    return (latent // c["patch"]) ** 2 + 2


def _edge_init(k, c: dict, latent: int, dtype) -> dict:
    d, pix = c["d_model"], c["patch"] ** 2 * c["in_ch"]
    return {"patch_embed": _dense(k[2], pix, d, dtype),
            "pos_embed": (jax.random.normal(k[3], (n_tokens(c, latent), d))
                          * 0.02).astype(dtype),
            "time_mlp": _mlp_init(k[4], d, 4 * d, dtype),
            "class_embed": _dense(k[5], c["n_classes"], d, dtype),
            "out_norm": jnp.ones((d,), dtype),
            "out_proj": _dense(k[6], d, pix, dtype)}


def init(key, c: dict, latent: int) -> dict:
    """Model-space parameters: edge leaves plus ``enc_blocks`` /
    ``dec_blocks`` stacked over the ``n_layers / 2`` blocks of each half."""
    dtype = jnp.dtype(c["param_dtype"])
    half = c["n_layers"] // 2
    k = jax.random.split(key, 8)
    enc = jax.vmap(lambda kk: _block_init(kk, c, False, dtype))(
        jax.random.split(k[0], half))
    dec = jax.vmap(lambda kk: _block_init(kk, c, True, dtype))(
        jax.random.split(k[1], half))
    return {**_edge_init(k, c, latent, dtype),
            "enc_blocks": enc, "dec_blocks": dec}


# --------------------------------------------------------------------------
# the network, in float32
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def mlp(p, x, op):
    h = gelu_tanh(mm(x, p["w_up"], op) + p["b_up"].astype(jnp.float32))
    return mm(h, p["w_down"], op) + p["b_down"].astype(jnp.float32)


def attention(p, x, c, op):
    B, S, _ = x.shape
    H, hd = c["n_heads"], c["head_dim"]
    q = mm(x, p["wq"], op).reshape(B, S, H, hd)
    k = mm(x, p["wk"], op).reshape(B, S, H, hd)
    v = mm(x, p["wv"], op).reshape(B, S, H, hd)
    logits = einsum("bshd,bthd->bhst", q, k, op) / math.sqrt(hd)
    probs = jax.nn.softmax(logits, axis=-1)
    out = einsum("bhst,bthd->bshd", probs, v, op).reshape(B, S, H * hd)
    return mm(out, p["wo"], op)


def block(p, x, c, op, skip=None):
    if skip is not None:
        x = mm(jnp.concatenate([x, skip], -1), p["skip_proj"], op)
    x = x + attention(p["attn"], rms_norm(x, p["ln1"], c["norm_eps"]), c, op)
    return x + mlp(p["mlp"], rms_norm(x, p["ln2"], c["norm_eps"]), op)


def timestep_embedding(t, dim: int):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def cosine_alpha_bar(t, s: float = 0.008):
    f = jnp.cos((t + s) / (1 + s) * jnp.pi / 2) ** 2
    f0 = math.cos(s / (1 + s) * math.pi / 2) ** 2
    return jnp.clip(f / f0, 1e-5, 1.0)


def patchify(x, p: int):
    B, H, W, C = x.shape
    x = x.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(x, p: int, size: int, ch: int):
    g = size // p
    x = x.reshape(x.shape[0], g, g, p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(x.shape[0], size, size, ch)


def noised(latents, rng):
    """DDPM forward process: ``(xt, t, noise)`` for a batch and a key."""
    rt, rn = jax.random.split(rng)
    t = jax.random.uniform(rt, (latents.shape[0],))
    noise = jax.random.normal(rn, latents.shape, jnp.float32)
    ab = cosine_alpha_bar(t)[:, None, None, None]
    return jnp.sqrt(ab) * latents + jnp.sqrt(1 - ab) * noise, t, noise


def embed(e, xt, t, labels, c, op):
    tok = mm(patchify(xt, c["patch"]), e["patch_embed"], op)
    temb = mlp(e["time_mlp"], timestep_embedding(t, c["d_model"]), op)
    cemb = e["class_embed"].astype(jnp.float32)[labels]
    x = jnp.concatenate([temb[:, None], cemb[:, None], tok], 1)
    return x + e["pos_embed"].astype(jnp.float32)[None]


def head_loss(e, x, noise, c, op):
    x = rms_norm(x, e["out_norm"], c["norm_eps"])
    pred = unpatchify(mm(x[:, 2:], e["out_proj"], op), c["patch"],
                      noise.shape[1], c["in_ch"])
    return jnp.mean(jnp.square(pred - noise))


# --------------------------------------------------------------------------
# training, block by block
# --------------------------------------------------------------------------

class Reference:
    """Runs the reference's training steps for one configuration.

    ``devices`` hold the blocks in contiguous runs of the encoder and of
    the decoder (block ``i`` of each half on device ``i * n // half``);
    edge parameters and the loss live on the first device.  ``operand``
    is ``"float32"`` (the reference) or ``"float8"`` (the control).
    ``fault`` plants a fault in the reference, for reading what a broken
    program would read: ``"half_batch"`` trains on the first half of the
    noised rows only (the second half replaced by copies of the first, so
    the mean is taken over the first half), and
    ``"no_exchange"`` zeroes the activation at every boundary between the
    pipeline stages of a ``stages``-way fold, as if the hop between chips
    carried nothing.
    """

    def __init__(self, c: dict, latent: int, devices, *,
                 operand: str = "float32", fault: str | None = None,
                 stages: int = 1):
        self.c, self.latent, self.devices = c, latent, list(devices)
        self.op, self.fault = operand, fault
        self.half = c["n_layers"] // 2
        per = self.half // stages
        # encoder inputs of blocks k * per, and decoder inputs of the
        # mirror boundaries, arrive over a hop in a stages-way fold
        self.cut = ({k * per for k in range(1, stages)}
                    if fault == "no_exchange" else set())
        op = operand
        enc_f = lambda p, x: block(p, x, c, op)
        dec_f = lambda p, x, s: block(p, x, c, op, skip=s)
        self._enc = jax.jit(enc_f)
        self._dec = jax.jit(dec_f)
        # gradients are taken with respect to float32 copies of the
        # parameters, so that they come out in float32
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        self._enc_vjp = jax.jit(
            lambda p, x, g: jax.vjp(enc_f, f32(p), x)[1](g))
        self._dec_vjp = jax.jit(
            lambda p, x, s, g: jax.vjp(dec_f, f32(p), x, s)[1](g))
        self._embed = jax.jit(lambda e, xt, t, y: embed(e, xt, t, y, c, op))
        self._embed_vjp = jax.jit(lambda e, xt, t, y, g: jax.vjp(
            lambda ee: embed(ee, xt, t, y, c, op), f32(e))[1](g)[0])
        self._head = jax.jit(lambda e, x, n: jax.value_and_grad(
            lambda ee, xx: head_loss(ee, xx, n, c, op), argnums=(0, 1))(
                f32(e), x))
        self._noised = jax.jit(noised)
        # the initialisers, kept so that the starting point made again for
        # the parameters' change comes from the executables that made it
        self._made = {}

    def device_of(self, i: int):
        return self.devices[i * len(self.devices) // self.half]

    # ---- parameters ------------------------------------------------------
    def init(self, key) -> dict:
        """:func:`init`, made block by block on the device that holds the
        block: ``{"edge", "enc": [..], "dec": [..]}``."""
        from jax.sharding import SingleDeviceSharding

        c, latent = self.c, self.latent
        dtype = jnp.dtype(c["param_dtype"])
        k = jax.random.split(key, 8)
        made = self._made

        def maker(skip, dev):
            if (skip, dev) not in made:
                made[skip, dev] = jax.jit(
                    lambda bk: _block_init(bk, c, skip, dtype),
                    out_shardings=SingleDeviceSharding(dev))
            return made[skip, dev]

        if "edge" not in made:
            made["edge"] = jax.jit(
                lambda kk: _edge_init(kk, c, latent, dtype),
                out_shardings=SingleDeviceSharding(self.devices[0]))
        out = {"edge": made["edge"](k), "enc": [], "dec": []}
        for part, kk, skip in (("enc", k[0], False), ("dec", k[1], True)):
            keys = jax.random.split(kk, self.half)
            for i in range(self.half):
                # decoder block i sits beside its mirror encoder block
                at = i if part == "enc" else self.half - 1 - i
                out[part].append(maker(skip, self.device_of(at))(keys[i]))
        return out

    # ---- one forward and backward ----------------------------------------
    def loss_and_grads(self, P: dict, latents, labels, rng):
        d0 = self.devices[0]
        latents = jax.device_put(jnp.asarray(latents, jnp.float32), d0)
        labels = jax.device_put(jnp.asarray(labels, jnp.int32), d0)
        xt, t, noise = self._noised(latents, jax.device_put(rng, d0))
        if self.fault == "half_batch":
            h = latents.shape[0] // 2
            xt, t, noise, labels = (jnp.concatenate([a[:h], a[:h]])
                                    for a in (xt, t, noise, labels))
        x = self._embed(P["edge"], xt, t, labels)
        enc_in, skips = [], []
        for i in range(self.half):
            x = jax.device_put(x, self.device_of(i))
            if i in self.cut:
                x = jnp.zeros_like(x)
            enc_in.append(x)
            x = self._enc(P["enc"][i], x)
            skips.append(x)
        dec_in = []
        for j in range(self.half):
            dev = self.device_of(self.half - 1 - j)
            x = jax.device_put(x, dev)
            if self.half - j in self.cut:
                x = jnp.zeros_like(x)
            dec_in.append(x)
            x = self._dec(P["dec"][j], x,
                          jax.device_put(skips[self.half - 1 - j], dev))
        x = jax.device_put(x, d0)
        loss, (g_edge_head, g) = self._head(P["edge"], x, noise)
        g_dec = [None] * self.half
        g_skip = [None] * self.half
        for j in reversed(range(self.half)):
            dev = self.device_of(self.half - 1 - j)
            s = jax.device_put(skips[self.half - 1 - j], dev)
            g_dec[j], g, gs = self._dec_vjp(P["dec"][j], dec_in[j], s,
                                             jax.device_put(g, dev))
            g_skip[self.half - 1 - j] = gs
            if self.half - j in self.cut:
                g = jnp.zeros_like(g)
        g_enc = [None] * self.half
        for i in reversed(range(self.half)):
            dev = self.device_of(i)
            g = jax.device_put(g, dev) + g_skip[i]
            g_enc[i], g = self._enc_vjp(P["enc"][i], enc_in[i], g)
            if i in self.cut:
                g = jnp.zeros_like(g)
        g_edge = self._embed_vjp(P["edge"], xt, t, labels,
                                 jax.device_put(g, d0))
        g_edge = jax.tree.map(jnp.add, g_edge, g_edge_head)
        return float(loss), {"edge": g_edge, "enc": g_enc, "dec": g_dec}

    # ---- AdamW -------------------------------------------------------------
    def train(self, key, batches, rng_of, steps: int, opt: dict) -> dict:
        """Follow ``steps`` training steps from the parameters :meth:`init`
        makes from ``key``.

        Returns the losses, the per-leaf norms of the first step's gradient
        as the optimizer gets it (after clipping), and the per-leaf norms of
        the parameters' change over the steps; leaves of the block stacks
        are read per block."""
        P = self.init(key)
        # each moment on the device of its parameter
        zeros = lambda t: jax.tree.map(
            lambda a: jnp.zeros_like(a, jnp.float32), t)
        m, v = zeros(P), zeros(P)
        hyper = tuple(float(opt[k]) for k in
                      ("lr", "b1", "b2", "eps", "weight_decay"))

        # the old parameters and moments are donated: two copies of the
        # optimizer state would not fit beside the gradients on a chip
        @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
        def update(p, g, mo, vo, scale, t):
            tdef = jax.tree.structure(p)
            out = [_adamw_leaf(*a, scale, t, hyper) for a in zip(
                *(tdef.flatten_up_to(x) for x in (p, g, mo, vo)))]
            return tuple(tdef.unflatten([o[n] for o in out])
                         for n in range(3))

        losses, grad_rows = [], None
        for k in range(steps):
            b = batches(k)
            loss, g = self.loss_and_grads(P, b["latents"], b["labels"],
                                          rng_of(k))
            losses.append(loss)
            sq = _rows(jax.tree.map(lambda a: jnp.sum(a * a), g))
            gnorm = math.sqrt(sum(float(np.sum(x))
                                  for x in jax.tree.leaves(sq)))
            scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            if k == 0:
                grad_rows = jax.tree.map(lambda x: np.sqrt(x) * scale, sq)
            new = {"edge": None, "enc": [], "dec": []}
            for part in ("enc", "dec"):
                for i in range(self.half):
                    new[part].append(update(P[part][i], g[part][i],
                                            m[part][i], v[part][i],
                                            scale, float(k + 1)))
            new["edge"] = update(P["edge"], g["edge"], m["edge"],
                                 v["edge"], scale, float(k + 1))
            P, m, v = ({"edge": new["edge"][n],
                        "enc": [o[n] for o in new["enc"]],
                        "dec": [o[n] for o in new["dec"]]}
                       for n in range(3))
            del g, new      # not live while the next step's gradients are
        del m, v
        # the starting point is made again rather than kept, to leave room
        change = _rows(jax.tree.map(
            lambda a, b: jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))),
            P, self.init(key)))
        change = jax.tree.map(np.sqrt, change)
        return {"losses": losses, "grad": _model_space(grad_rows),
                "change": _model_space(change)}


def _adamw_leaf(p, g, m, v, scale, t, hyper):
    """AdamW (Loshchilov & Hutter) on one leaf, the gradient clipped by
    ``scale``; the parameter kept in its own dtype, the moments in f32."""
    lr, b1, b2, eps, wd = hyper
    g = g.astype(jnp.float32) * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p32 = p.astype(jnp.float32)
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return (p32 - lr * (upd + wd * p32)).astype(p.dtype), m, v


def _rows(tree) -> Any:
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x), np.float64),
                        tree)


def _model_space(tree: dict) -> dict:
    """``{"edge", "enc": [..], "dec": [..]}`` of per-leaf numbers ->
    model-space dict whose block leaves are arrays over the blocks."""
    out = dict(tree["edge"])
    for name, rows in (("enc_blocks", tree["enc"]), ("dec_blocks",
                                                      tree["dec"])):
        out[name] = jax.tree.map(lambda *xs: np.stack(xs), *rows)
    return out
