"""Plain float32 reference of the dense decoder family.

Written from the published description of a pre-norm decoder (RMSNorm,
rotary positions, grouped-query attention with a bias on q/k/v, SwiGLU FFN,
untied output head) in ``jax.numpy``, with every matrix product at
``Precision.HIGHEST``.  It shares nothing with the program under test: its
weights come from ``weights.py``, made again from the seed, layer by layer,
so that only one layer's float32 weights and the activations of the
sequences being checked sit in device memory at a time.  Attention runs in
blocks of queries.

Departures from the published models are those of the configuration as it
is run, and each is named in the configuration's ``departures``: rotary
positions over the whole head in rotate-half form, and one RMSNorm epsilon.

``precision="fp8"`` is the control: every operand of every product (the
weights, the residual stream, the normed inputs, q, k, v, the attention
probabilities, the FFN hidden state) rounded to float8 e4m3 before use,
with float32 accumulation, the next precision below the bfloat16 that the
configuration serves in.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _rmsnorm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def _rope(x, theta):
    """x (h, S, hd): rotate-half rotary positions 0..S-1 over the head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_weights(key, model, served, layer):
    """One layer's weights in float32, made from the seed."""
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv, f = model["n_heads"], model["n_kv_heads"], model["d_ff"]
    shapes = {"ln_attn/gamma": (d,), "ln_mlp/gamma": (d,),
              "attn/wq": (d, hq * hd), "attn/wk": (d, hkv * hd),
              "attn/wv": (d, hkv * hd), "attn/wo": (hq * hd, d),
              "mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)}
    if model["qkv_bias"]:
        shapes.update({"attn/bq": (hq * hd,), "attn/bk": (hkv * hd,),
                       "attn/bv": (hkv * hd,)})
    return {p: weights.layer_leaf(key, "blocks/" + p, layer, s,
                                  served).astype(jnp.float32)
            for p, s in shapes.items()}


def _attention(q, k, v, precision):
    """Causal attention; q (hq, S, hd), k/v (hkv, S, hd) -> (S, hq*hd)."""
    hq, s, hd = q.shape
    hkv = k.shape[0]
    qg = q.reshape(hkv, hq // hkv, s // Q_BLOCK, Q_BLOCK, hd)
    qg = jnp.moveaxis(qg, 2, 0)                   # (nb, hkv, rep, Qb, hd)
    kpos = jnp.arange(s)

    def block(args):
        i, qb = args
        sc = jnp.einsum("grqd,gkd->grqk", qb, k, precision=HIGHEST)
        sc = sc / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = _round(jax.nn.softmax(sc, axis=-1), precision)
        return jnp.einsum("grqk,gkd->grqd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(qg.shape[0]), qg))
    o = jnp.moveaxis(o, 0, 2).reshape(hq, s, hd)
    return o.transpose(1, 0, 2).reshape(s, hq * hd)


@functools.partial(jax.jit, static_argnames=("mdl", "precision"))
def _block(w, x, *, mdl, precision):
    model = json.loads(mdl)
    hd, hq, hkv = model["head_dim"], model["n_heads"], model["n_kv_heads"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    s = x.shape[0]
    h = _rmsnorm(x, w["ln_attn/gamma"], eps)
    q, k, v = (_mm(h, w[f"attn/w{n}"], precision) for n in "qkv")
    if model["qkv_bias"]:
        q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
    q = _rope(q.reshape(s, hq, hd).transpose(1, 0, 2), theta)
    k = _rope(k.reshape(s, hkv, hd).transpose(1, 0, 2), theta)
    v = v.reshape(s, hkv, hd).transpose(1, 0, 2)
    q, k, v = (_round(t, precision) for t in (q, k, v))
    o = _attention(q, k, v, precision)
    x = _round(x + _mm(o, w["attn/wo"], precision), precision)
    h = _rmsnorm(x, w["ln_mlp/gamma"], eps)
    g = jax.nn.silu(_mm(h, w["mlp/gate"], precision)) \
        * _mm(h, w["mlp/up"], precision)
    return _round(x + _mm(g, w["mlp/down"], precision), precision)


@functools.partial(jax.jit, static_argnames=("mdl", "srv"))
def _gen_layer(key, layer, *, mdl, srv):
    return _layer_weights(key, json.loads(mdl), json.loads(srv), layer)


@functools.partial(jax.jit, static_argnames=("mdl", "srv"))
def _head_weights(key, *, mdl, srv):
    model, served = json.loads(mdl), json.loads(srv)
    d, vocab = model["d_model"], model["vocab_size"]
    gamma = weights.layer_leaf(key, "ln_f/gamma", 0, (d,), served)
    head = weights.layer_leaf(key, "lm_head", 0, (d, vocab), served)
    return gamma.astype(jnp.float32), head.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(gamma, head, x, *, eps, precision):
    return _mm(_round(_rmsnorm(x, gamma, eps), precision), head, precision)


@functools.partial(jax.jit, static_argnames=("mdl", "srv"))
def _embed_table(key, *, mdl, srv):
    model = json.loads(mdl)
    table = weights.layer_leaf(key, "embed", 0,
                               (model["vocab_size"], model["d_model"]),
                               json.loads(srv))
    return table.astype(jnp.float32)


def bucket(n: int, least: int = Q_BLOCK) -> int:
    """The power of two from ``least`` up that holds ``n`` (few shapes, so
    the compiled reference comes from the cache in every later run)."""
    b = least
    while b < n:
        b *= 2
    return b


def logits(key, model: dict, served: dict, seqs, positions,
           precision: str = "f32") -> list[jax.Array]:
    """Float32 logits (len(pos), V) at ``positions[i]`` of each token
    sequence ``seqs[i]``, each sequence run causally from position 0."""
    mdl = json.dumps(model, sort_keys=True)
    srv = json.dumps(served, sort_keys=True)
    table = _embed_table(key, mdl=mdl, srv=srv)
    xs = []
    for seq in seqs:
        toks = np.zeros(bucket(len(seq)), np.int32)
        toks[: len(seq)] = seq
        xs.append(_round(table[jnp.asarray(toks)], precision))
    del table
    for layer in range(model["n_layers"]):
        w = _gen_layer(key, jnp.int32(layer), mdl=mdl, srv=srv)
        if precision == "fp8":
            w = {k: _round(v, precision) for k, v in w.items()}
        xs = [_block(w, x, mdl=mdl, precision=precision) for x in xs]
        del w
    gamma, head = _head_weights(key, mdl=mdl, srv=srv)
    if precision == "fp8":
        gamma = _round(gamma, precision)
    out = []
    for x, pos in zip(xs, positions):
        idx = np.zeros(bucket(len(pos), 64), np.int32)
        idx[: len(pos)] = pos
        lg = _head(gamma, head, x[jnp.asarray(idx)], eps=model["norm_eps"],
                   precision=precision)
        out.append(lg[: len(pos)])
    return out
