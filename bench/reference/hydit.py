"""Plain float32 reference of Hunyuan-DiT training: conditioning, forward,
DDPM loss, gradients and AdamW, written from the equations and imported
from nowhere in the program under test.

The model is Hunyuan-DiT (Tencent, arXiv:2405.08748; the module
definitions of github.com/Tencent/HunyuanDiT, ``hydit/modules``), block
``l`` of ``n_layers`` (x the image tokens, c the conditioning vector,
text the ``clip_len + t5_len`` text tokens; every linear has a bias; every
LayerNorm is taken in float32 with eps ``norm_eps``):

    if l > n_layers / 2:  x = Linear(LayerNorm([x | skip_pop]))
    x = x + SelfAttn(LayerNorm(x) + Linear(SiLU(c)))
    x = x + CrossAttn(LayerNorm(x), text)
    x = x + MLP_tanhGELU(LayerNorm(x))
    if l < n_layers / 2 - 1:  push x

SelfAttn: q, k, v projections, per-head affine LayerNorm of q and k, 2D
RoPE on q and k, softmax(q k^T / sqrt(hd)) v, output projection.
CrossAttn: q from x, k and v from the text, per-head LayerNorm of q and k,
RoPE on q alone, no text mask.  2D RoPE (theta ``rope_theta``): head dims
``2k, 2k+1`` form a complex number turned by an angle, pair ``k < hd/4``
by the token's column times ``theta^(-2k/(hd/2))``, pair ``k >= hd/4`` by
its row times ``theta^(-2(k - hd/4)/(hd/2))``; grid index ``i`` of a
``g x g`` grid sits at ``i * rope_base / g``.

Conditioning: ``c = MLP_SiLU(sinusoid(t)) + Linear(SiLU(Linear(
AttentionPool(t5))))``, the pool CLIP's (the mean token queries the mean
and every token, with a position table, ``pool_heads`` heads); text =
``[clip | Linear(SiLU(Linear(t5)))]`` with the slots past each sample's
valid counts replaced by the learned padding.  Final layer: ``shift,
scale = Linear(SiLU(c))``; ``Linear(LayerNorm_noaffine(x) * (1 + scale) +
shift)``, unpatchified to ``out_ch`` channels.  The loss is the
configuration's: the DDPM eps-MSE (cosine schedule, ``t`` uniform in
[0, 1)) on the first ``in_ch`` output channels.

Weights come from the seed by the splitting of the key that the
configuration's initialisation states (normal over sqrt(fan-in), biases
zero, norms one and zero; the pooler's position table normal over
sqrt(width), the text padding normal), rounded to the parameter dtype.

Every matrix product runs at ``Precision.HIGHEST`` in float32 (the
control: in float8, ``bench/reference/uvit.py``'s products).  The network
runs one block at a time on one sample per device, and the backward pass
recomputes one block at a time from its saved input (``jax.vjp``), so the
40-block model at 4,096 tokens fits on four chips; each block's forward
and backward is one program over the devices, compiled once.  The
conditioning's backward runs once, from the summed cotangents of ``c``
and the text.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.uvit import (_adamw_leaf, _model_space, _rows, einsum,
                                  gelu_tanh, mm, noised, patchify,
                                  timestep_embedding, unpatchify)


# --------------------------------------------------------------------------
# initialisation from the seed
# --------------------------------------------------------------------------

def _dense(key, fan_in: int, fan_out: int, dtype):
    return (jax.random.normal(key, (fan_in, fan_out))
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _lin(key, fan_in: int, fan_out: int, dtype) -> dict:
    return {"w": _dense(key, fan_in, fan_out, dtype),
            "b": jnp.zeros((fan_out,), dtype)}


def _norm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def _attn_init(key, d: int, kv_in: int, hd: int, dtype) -> dict:
    k = jax.random.split(key, 4)
    return {"wq": _dense(k[0], d, d, dtype),
            "wk": _dense(k[1], kv_in, d, dtype),
            "wv": _dense(k[2], kv_in, d, dtype),
            "wo": _dense(k[3], d, d, dtype),
            "bq": jnp.zeros((d,), dtype), "bk": jnp.zeros((d,), dtype),
            "bv": jnp.zeros((d,), dtype), "bo": jnp.zeros((d,), dtype),
            "q_norm": _norm(hd, dtype), "k_norm": _norm(hd, dtype)}


def _block_init(key, c: dict, with_skip: bool, dtype) -> dict:
    d, hd, ff = c["d_model"], c["head_dim"], c["d_ff"]
    k = jax.random.split(key, 5)
    km = jax.random.split(k[3], 2)
    p = {"norm1": _norm(d, dtype),
         "attn1": _attn_init(k[0], d, d, hd, dtype),
         "mod": _lin(k[1], d, d, dtype),
         "norm3": _norm(d, dtype),
         "attn2": _attn_init(k[2], d, c["text_dim"], hd, dtype),
         "norm2": _norm(d, dtype),
         "mlp": {"w_up": _dense(km[0], d, ff, dtype),
                 "b_up": jnp.zeros((ff,), dtype),
                 "w_down": _dense(km[1], ff, d, dtype),
                 "b_down": jnp.zeros((d,), dtype)}}
    if with_skip:
        p["skip_norm"] = _norm(2 * d, dtype)
        p["skip_proj"] = _dense(k[4], 2 * d, d, dtype)
        p["skip_bias"] = jnp.zeros((d,), dtype)
    return p


def counts(c: dict) -> tuple[int, int, int]:
    """``(blocks without skip parameters, with them, long skips)``."""
    n = c["n_layers"]
    return n // 2 + 1, n - (n // 2 + 1), n // 2 - 1


def _edge_init(ks, c: dict, dtype) -> dict:
    d, c5 = c["d_model"], c["t5_dim"]
    pk = jax.random.split(ks[4], 4)
    return {
        "x_embed": _lin(ks[2], c["patch"] ** 2 * c["in_ch"], d, dtype),
        "t_embed": {"fc1": _lin(ks[3], c["freq_dim"], d, dtype),
                    "fc2": _lin(ks[5], d, d, dtype)},
        "pooler": {"pos": (jax.random.normal(ks[6], (c["t5_len"] + 1, c5))
                           / math.sqrt(c5)).astype(dtype),
                   "q": _lin(pk[0], c5, c5, dtype),
                   "k": _lin(pk[1], c5, c5, dtype),
                   "v": _lin(pk[2], c5, c5, dtype),
                   "out": _lin(pk[3], c5, c["pool_dim"], dtype)},
        "extra": {"fc1": _lin(ks[7], c["pool_dim"], 4 * d, dtype),
                  "fc2": _lin(ks[8], 4 * d, d, dtype)},
        "t5_mlp": {"fc1": _lin(ks[9], c5, c["t5_hidden"], dtype),
                   "fc2": _lin(ks[10], c["t5_hidden"], c["text_dim"],
                               dtype)},
        "text_pad": jax.random.normal(
            ks[11], (c["clip_len"] + c["t5_len"], c["text_dim"])
        ).astype(dtype),
        "final": {"mod": _lin(ks[12], d, 2 * d, dtype),
                  "proj": _lin(ks[13], d, c["patch"] ** 2 * c["out_ch"],
                               dtype)},
    }


def init(key, c: dict) -> dict:
    """Model-space parameters: edge leaves plus ``enc_blocks`` (blocks
    without skip parameters) and ``dec_blocks`` stacked over their
    blocks."""
    dtype = jnp.dtype(c["param_dtype"])
    n_enc, n_dec, _ = counts(c)
    ks = jax.random.split(key, 14)
    stack = lambda *xs: jnp.stack(xs)
    enc = [_block_init(k, c, False, dtype)
           for k in jax.random.split(ks[0], n_enc)]
    dec = [_block_init(k, c, True, dtype)
           for k in jax.random.split(ks[1], n_dec)]
    return {**_edge_init(ks, c, dtype),
            "enc_blocks": jax.tree.map(stack, *enc),
            "dec_blocks": jax.tree.map(stack, *dec)}


# --------------------------------------------------------------------------
# the network, in float32
# --------------------------------------------------------------------------

def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y if p is None else y * p["scale"] + p["bias"]


def linear(p, x, op):
    return mm(x, p["w"], op) + p["b"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rope_angles(c: dict, latent: int):
    """``(tokens, hd / 2)``: the angle of each complex pair of head dims."""
    hd = c["head_dim"]
    g = latent // c["patch"]
    pos = np.arange(g) * (c["rope_base"] / g)
    quarter = hd // 4
    freq = c["rope_theta"] ** (-2.0 * np.arange(quarter) / (hd // 2))
    rows, cols = np.meshgrid(pos, pos, indexing="ij")     # token r*g + c
    ang = np.concatenate([cols.reshape(-1, 1) * freq[None],
                          rows.reshape(-1, 1) * freq[None]], axis=1)
    return ang.astype(np.float32)


def rotate(x, ang):
    """x ``[S, H, hd]`` turned pair by pair: ``x cos + rot(x) sin`` with
    each angle repeated over its pair and ``rot`` taking ``(a, b)`` to
    ``(-b, a)`` (the published ``use_real`` form)."""
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, None, :]
    rot = jnp.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + rot * sin


def attend(q, k, v, op):
    """One sample: q ``[S, H, hd]``, k/v ``[T, H, hd]``."""
    hd = q.shape[-1]
    logits = einsum("shd,thd->hst", q, k, op) / math.sqrt(hd)
    return einsum("hst,thd->shd", jax.nn.softmax(logits, -1), v, op)


def self_attn(p, h, c, ang, op):
    S, H = h.shape[0], c["n_heads"]
    split = lambda y: y.reshape(S, H, -1)
    q = layer_norm(split(linear({"w": p["wq"], "b": p["bq"]}, h, op)),
                   p["q_norm"], c["norm_eps"])
    k = layer_norm(split(linear({"w": p["wk"], "b": p["bk"]}, h, op)),
                   p["k_norm"], c["norm_eps"])
    v = split(linear({"w": p["wv"], "b": p["bv"]}, h, op))
    out = attend(rotate(q, ang), rotate(k, ang), v, op).reshape(S, -1)
    return linear({"w": p["wo"], "b": p["bo"]}, out, op)


def cross_attn(p, h, text, c, ang, op):
    S, T, H = h.shape[0], text.shape[0], c["n_heads"]
    q = layer_norm(linear({"w": p["wq"], "b": p["bq"]}, h, op)
                   .reshape(S, H, -1), p["q_norm"], c["norm_eps"])
    k = layer_norm(linear({"w": p["wk"], "b": p["bk"]}, text, op)
                   .reshape(T, H, -1), p["k_norm"], c["norm_eps"])
    v = linear({"w": p["wv"], "b": p["bv"]}, text, op).reshape(T, H, -1)
    out = attend(rotate(q, ang), k, v, op).reshape(S, -1)
    return linear({"w": p["wo"], "b": p["bo"]}, out, op)


def block(p, x, cvec, text, c, ang, op, skip=None):
    """One block on one sample: x ``[S, d]``, cvec ``[d]``, text
    ``[T, text_dim]``."""
    eps = c["norm_eps"]
    if skip is not None:
        x = mm(layer_norm(jnp.concatenate([x, skip], -1), p["skip_norm"],
                          eps), p["skip_proj"], op) + p["skip_bias"]
    shift = linear(p["mod"], silu(cvec), op)
    x = x + self_attn(p["attn1"], layer_norm(x, p["norm1"], eps) + shift,
                      c, ang, op)
    x = x + cross_attn(p["attn2"], layer_norm(x, p["norm3"], eps), text, c,
                       ang, op)
    m = p["mlp"]
    h = gelu_tanh(mm(layer_norm(x, p["norm2"], eps), m["w_up"], op)
                  + m["b_up"])
    return x + mm(h, m["w_down"], op) + m["b_down"]


def attention_pool(p, t5, heads, op):
    """CLIP's AttentionPool on one batch ``[B, L, C]`` -> ``[B, out]``."""
    B, L, C = t5.shape
    x = jnp.concatenate([jnp.mean(t5, 1, keepdims=True), t5], 1) + p["pos"]
    hd = C // heads
    q = linear(p["q"], x[:, :1], op).reshape(B, 1, heads, hd)
    k = linear(p["k"], x, op).reshape(B, L + 1, heads, hd)
    v = linear(p["v"], x, op).reshape(B, L + 1, heads, hd)
    w = jax.nn.softmax(einsum("bqhd,bkhd->bhqk", q, k, op) / math.sqrt(hd),
                       -1)
    out = einsum("bhqk,bkhd->bqhd", w, v, op).reshape(B, C)
    return linear(p["out"], out, op)


def condition(e, t, clip, clip_len, t5, t5_len, c, op):
    """``(cvec [B, d], text [B, T, text_dim])`` of a batch."""
    te = e["t_embed"]
    cvec = linear(te["fc2"], silu(linear(
        te["fc1"], timestep_embedding(t, c["freq_dim"]), op)), op)
    ex = e["extra"]
    pooled = attention_pool(e["pooler"], t5, c["pool_heads"], op)
    cvec = cvec + linear(ex["fc2"], silu(linear(ex["fc1"], pooled, op)), op)
    tm = e["t5_mlp"]
    text = jnp.concatenate(
        [clip, linear(tm["fc2"], silu(linear(tm["fc1"], t5, op)), op)], 1)
    valid = jnp.concatenate(
        [jnp.arange(c["clip_len"])[None] < clip_len[:, None],
         jnp.arange(c["t5_len"])[None] < t5_len[:, None]], 1)
    return cvec, jnp.where(valid[..., None], text, e["text_pad"][None])


def embed(e, xt, c, op):
    """Patch embedding of one sample ``[H, W, C]`` -> ``[S, d]``."""
    return linear(e["x_embed"], patchify(xt[None], c["patch"])[0], op)


def head_loss(e, x, cvec, noise, c, op, batch: int):
    """One sample's share of the batch's loss: its eps-MSE over ``batch``."""
    f = e["final"]
    shift, scale = jnp.split(linear(f["mod"], silu(cvec), op), 2)
    h = layer_norm(x, None, c["norm_eps"]) * (1 + scale) + shift
    pred = unpatchify(linear(f["proj"], h, op)[None], c["patch"],
                      noise.shape[0], c["out_ch"])[0]
    return jnp.mean(jnp.square(pred[..., :c["in_ch"]] - noise)) / batch


# --------------------------------------------------------------------------
# training, a sample per device and a block at a time
# --------------------------------------------------------------------------

#: compiled functions, shared by every reference of one configuration,
#: resolution, operand precision and device set in a process
_COMPILED: dict = {}


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_noised = jax.jit(noised)


class _Programs:
    """The reference's compiled functions for ``len(devices)`` samples at a
    time, one on each device: a block's forward and backward (its
    parameters replicated, their gradient summed over the samples), the
    patch embedding and the final layer the same way, and the conditioning
    on the first device.  Each is one program over all the devices, so it
    compiles once."""

    def __init__(self, c: dict, latent: int, devices, op: str):
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        self.mesh = Mesh(np.array(devices), ("s",))
        self.rep = NamedSharding(self.mesh, P())
        self.row = NamedSharding(self.mesh, P("s"))
        ang = rope_angles(c, latent)
        self.c, self.op = c, op

        def per_sample(f):
            """``f`` on one sample (leading axis 1 dropped), on each
            device."""
            return lambda p, *rows: f(p, *(r[0] for r in rows))[None]

        blk = per_sample(lambda p, x, cv, tx: block(p, x, cv, tx, c, ang, op))
        blk_s = per_sample(lambda p, x, cv, tx, s: block(p, x, cv, tx, c, ang,
                                                         op, s))

        def vjp(f):
            # gradients with respect to float32 copies of the parameters,
            # so that they come out in float32; the transpose of the
            # replicated parameters' use on every device sums their
            # gradient over the devices
            def run(p, *args):
                *rows, g = args
                return jax.vjp(f, f32(p), *rows)[1](g)
            return run

        emb = per_sample(lambda e, xt: embed(e, xt, c, op))
        self.blk = self._spmd(blk, 3, ())
        self.blk_s = self._spmd(blk_s, 4, ())
        self.blk_vjp = self._spmd(vjp(blk), 4, (True, False, False, False))
        self.blk_s_vjp = self._spmd(vjp(blk_s), 5,
                                    (True, False, False, False, False))
        self.embed = self._spmd(emb, 1, ())
        self.embed_vjp = self._spmd(vjp(emb), 2, (True, False))
        self._heads = {}
        cond = lambda e, t, b: condition(
            e, t, b["clip"], b["clip_len"], b["t5"], b["t5_len"], c, op)
        self.cond = jax.jit(lambda e, t, b: cond(f32(e), t, b))
        self.cond_vjp = jax.jit(lambda e, t, b, g: jax.vjp(
            lambda ee: cond(ee, t, b), f32(e))[1](g)[0])

    def _spmd(self, f, n_rows: int, summed: tuple):
        """``f(params, *rows)`` as one program: params replicated, each
        row argument split over the samples; outputs replicated where
        ``summed`` says (sums over the devices), else split."""
        from jax.sharding import PartitionSpec as P

        out = (tuple(P() if s else P("s") for s in summed) if summed
               else P("s"))
        return jax.jit(jax.shard_map(
            f, mesh=self.mesh, in_specs=(P(),) + (P("s"),) * n_rows,
            out_specs=out))

    def head(self, batch: int):
        """The final layer and the loss of each device's sample: the
        summed loss and edge gradient, and each sample's cotangents of its
        activation and of ``c``."""
        from jax.sharding import PartitionSpec as P

        if batch not in self._heads:
            c, op = self.c, self.op

            def f(e, x, cv, nz):
                ls, g = jax.value_and_grad(
                    lambda ee, xx, cc: head_loss(ee, xx[0], cc[0], nz[0], c,
                                                 op, batch),
                    argnums=(0, 1, 2))(f32(e), x, cv)
                return (jax.lax.psum(ls, "s"),) + g

            self._heads[batch] = jax.jit(jax.shard_map(
                f, mesh=self.mesh, in_specs=(P(),) + (P("s"),) * 3,
                out_specs=(P(), P(), P("s"), P("s"))))
        return self._heads[batch]


def _programs(c: dict, latent: int, devices, op: str) -> _Programs:
    key = (json.dumps(c, sort_keys=True, default=str), latent, op,
           tuple(d.id for d in devices))
    if key not in _COMPILED:
        _COMPILED[key] = _Programs(c, latent, devices, op)
    return _COMPILED[key]


def _makers(c: dict, devices) -> dict:
    """Jitted initialisers of the edge (first device) and of a block
    with or without skip parameters on each device."""
    from jax.sharding import SingleDeviceSharding

    key = ("init", json.dumps(c, sort_keys=True, default=str),
           tuple(d.id for d in devices))
    if key not in _COMPILED:
        dtype = jnp.dtype(c["param_dtype"])
        made = {"edge": jax.jit(
            lambda kk: _edge_init(kk, c, dtype),
            out_shardings=SingleDeviceSharding(devices[0]))}
        for skip in (False, True):
            for dev in devices:
                made[skip, dev.id] = jax.jit(
                    functools.partial(lambda s, bk: _block_init(bk, c, s,
                                                                dtype), skip),
                    out_shardings=SingleDeviceSharding(dev))
        _COMPILED[key] = made
    return _COMPILED[key]


@functools.partial(jax.jit, donate_argnums=(0, 2, 3), static_argnums=6)
def _update(p, g, mo, vo, scale, t, hyper):
    tdef = jax.tree.structure(p)
    out = [_adamw_leaf(*a, scale, t, hyper) for a in zip(
        *(tdef.flatten_up_to(x) for x in (p, g, mo, vo)))]
    return tuple(tdef.unflatten([o[n] for o in out]) for n in range(3))


class Reference:
    """Runs the reference's training steps for one configuration.

    Block ``l``'s parameters, gradient and AdamW moments live on
    ``devices[l * len(devices) // n_layers]``, the edge parameters on the
    first device.  The batch runs in rounds of one sample per device (as
    many devices as divide the batch): each block's forward and backward
    is one program over the devices, its parameters replicated for the
    call and its gradient summed over the round's samples.  The
    conditioning runs once a batch on the first device, and its backward
    once, from the summed cotangents of ``c`` and the text.
    ``operand`` is ``"float32"`` (the reference) or ``"float8"`` (the
    control).  ``fault`` plants a fault, for reading what a broken program
    would read: ``"half_batch"`` replaces the second half of every batch
    (noised latents, times, noise and text) by copies of the first, and
    ``"no_exchange"`` zeroes the activation, and its cotangent, at every
    boundary between the stages of a ``stages``-way fold (each side of
    the turnaround split evenly; the turnaround stays on one device)."""

    def __init__(self, c: dict, latent: int, devices, *,
                 operand: str = "float32", fault: str | None = None,
                 stages: int = 1):
        self.c, self.latent, self.devices = c, latent, list(devices)
        self.op, self.fault = operand, fault
        self.n_enc, self.n_dec, self.n_skips = counts(c)
        self.n = c["n_layers"]
        self.cut = set()
        if fault == "no_exchange":
            for lo, size in ((0, self.n_enc), (self.n_enc, self.n_dec)):
                self.cut |= {lo + round(k * size / stages)
                             for k in range(1, stages)}

    def device_of(self, layer: int):
        return self.devices[layer * len(self.devices) // self.n]

    # ---- parameters ------------------------------------------------------
    def init(self, key) -> dict:
        """:func:`init`, made block by block on the device that holds the
        block: ``{"edge", "enc": [..], "dec": [..]}``."""
        made = _makers(self.c, self.devices)
        ks = jax.random.split(key, 14)
        out = {"edge": made["edge"](ks), "enc": [], "dec": []}
        for i, k in enumerate(jax.random.split(ks[0], self.n_enc)):
            out["enc"].append(made[False, self.device_of(i).id](k))
        for j, k in enumerate(jax.random.split(ks[1], self.n_dec)):
            out["dec"].append(
                made[True, self.device_of(self.n_enc + j).id](k))
        return out

    # ---- one forward and backward ----------------------------------------
    def _batch(self, batch: dict, rng):
        d0 = self.devices[0]
        put = lambda a, t: jax.device_put(jnp.asarray(a, t), d0)
        lat = put(batch["latents"], jnp.float32)
        text = {"clip": put(batch["clip"], jnp.float32),
                "t5": put(batch["t5"], jnp.float32),
                "clip_len": put(batch["clip_len"], jnp.int32),
                "t5_len": put(batch["t5_len"], jnp.int32)}
        xt, t, noise = _noised(lat, jax.device_put(rng, d0))
        if self.fault == "half_batch":
            h = lat.shape[0] // 2
            dup = lambda a: jnp.concatenate([a[:h], a[:h]])
            xt, t, noise = dup(xt), dup(t), dup(noise)
            text = jax.tree.map(dup, text)
        return xt, t, noise, text

    def loss_and_grads(self, P: dict, batch: dict, rng):
        """The batch's loss and the float32 gradient of every parameter.

        Block by block, each round of samples in turn; a block's
        parameters are replicated once for all the rounds."""
        xt, t, noise, text = self._batch(batch, rng)
        B, n, d0 = xt.shape[0], self.n, self.devices[0]
        nd = max(k for k in range(1, len(self.devices) + 1) if B % k == 0)
        F = _programs(self.c, self.latent, self.devices[:nd], self.op)
        rep = lambda p: jax.device_put(p, F.rep)
        home = lambda a, dev: jax.device_put(a, dev)
        params = lambda layer: (P["enc"][layer] if layer < self.n_enc
                                else P["dec"][layer - self.n_enc])
        cv, tx = F.cond(P["edge"], t, text)
        e = P["edge"]
        x_embed = rep({"x_embed": e["x_embed"]})
        rows = [{k: jax.device_put(a[r * nd:(r + 1) * nd], F.row) for k, a in
                 (("xt", xt), ("noise", noise), ("cv", cv), ("tx", tx))}
                for r in range(B // nd)]
        ins = [[None] * n for _ in rows]
        skips = [[None] * self.n_skips for _ in rows]
        xs = [F.embed(x_embed, rw["xt"]) for rw in rows]
        for layer in range(n):
            p = rep(params(layer))
            for r, rw in enumerate(rows):
                x = xs[r]
                if layer in self.cut:
                    x = jnp.zeros_like(x)
                ins[r][layer] = x
                if layer < self.n_enc:
                    x = F.blk(p, x, rw["cv"], rw["tx"])
                    if layer < self.n_skips:
                        skips[r][layer] = x
                else:
                    x = F.blk_s(p, x, rw["cv"], rw["tx"],
                                skips[r][n - 1 - layer])
                xs[r] = x
        zeros = lambda a: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), a)
        g_edge = zeros(e)
        g_blk = [zeros(params(layer)) for layer in range(n)]
        final = rep({"final": e["final"]})
        losses, gs, g_cv, g_tx = [], [], [], []
        for r, rw in enumerate(rows):
            ls, ge, g, gc = F.head(B)(final, xs[r], rw["cv"], rw["noise"])
            losses.append(ls)
            g_edge["final"] = _add(g_edge["final"], home(ge["final"], d0))
            gs.append(g)
            g_cv.append(gc)
            g_tx.append(jnp.zeros_like(rw["tx"]))
        g_skip = [[None] * self.n_skips for _ in rows]
        for layer in reversed(range(n)):
            dev, p = self.device_of(layer), rep(params(layer))
            src = n - 1 - layer
            for r, rw in enumerate(rows):
                if layer >= self.n_enc:
                    gp, g, gc, gt, g_skip[r][src] = F.blk_s_vjp(
                        p, ins[r][layer], rw["cv"], rw["tx"], skips[r][src],
                        gs[r])
                else:
                    g = gs[r]
                    if layer < self.n_skips:
                        g = g + g_skip[r][layer]
                    gp, g, gc, gt = F.blk_vjp(p, ins[r][layer], rw["cv"],
                                              rw["tx"], g)
                g_blk[layer] = _add(g_blk[layer], home(gp, dev))
                if layer in self.cut:
                    g = jnp.zeros_like(g)
                gs[r], ins[r][layer] = g, None
                g_cv[r], g_tx[r] = g_cv[r] + gc, g_tx[r] + gt
        for r, rw in enumerate(rows):
            ge = F.embed_vjp(x_embed, rw["xt"], gs[r])[0]
            g_edge["x_embed"] = _add(g_edge["x_embed"],
                                     home(ge["x_embed"], d0))
        gather = lambda parts: jnp.concatenate([home(a, d0) for a in parts])
        g_edge = _add(g_edge, F.cond_vjp(e, t, text,
                                         (gather(g_cv), gather(g_tx))))
        return sum(float(ls) for ls in losses), {
            "edge": g_edge, "enc": g_blk[:self.n_enc],
            "dec": g_blk[self.n_enc:]}

    # ---- AdamW -------------------------------------------------------------
    def train(self, key, batches, rng_of, steps: int, opt: dict) -> dict:
        """Follow ``steps`` training steps from the parameters :meth:`init`
        makes from ``key``: the losses, the per-leaf norms of the first
        step's gradient as the optimizer gets it (after clipping), and the
        per-leaf norms of the parameters' change (block leaves per block)."""
        P = self.init(key)
        zeros = lambda t: jax.tree.map(
            lambda a: jnp.zeros_like(a, jnp.float32), t)
        m, v = zeros(P), zeros(P)
        hyper = tuple(float(opt[k]) for k in
                      ("lr", "b1", "b2", "eps", "weight_decay"))
        losses, grad_rows = [], None
        for k in range(steps):
            loss, g = self.loss_and_grads(P, batches(k), rng_of(k))
            losses.append(loss)
            sq = _rows(jax.tree.map(lambda a: jnp.sum(a * a), g))
            gnorm = math.sqrt(sum(float(np.sum(x))
                                  for x in jax.tree.leaves(sq)))
            scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            if k == 0:
                grad_rows = jax.tree.map(lambda x: np.sqrt(x) * scale, sq)
            new = {"edge": _update(P["edge"], g["edge"], m["edge"],
                                   v["edge"], scale, float(k + 1), hyper)}
            for part in ("enc", "dec"):
                new[part] = [_update(P[part][i], g[part][i], m[part][i],
                                     v[part][i], scale, float(k + 1), hyper)
                             for i in range(len(P[part]))]
            P, m, v = ({"edge": new["edge"][n],
                        "enc": [o[n] for o in new["enc"]],
                        "dec": [o[n] for o in new["dec"]]}
                       for n in range(3))
            del g, new
        del m, v
        change = _rows(jax.tree.map(
            lambda a, b: jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))),
            P, self.init(key)))
        change = jax.tree.map(np.sqrt, change)
        return {"losses": losses, "grad": _model_space(grad_rows),
                "change": _model_space(change)}
