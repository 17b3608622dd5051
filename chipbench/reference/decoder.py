"""The plain reference of the decoder configurations: this repo's one
block (pre-LayerNorm, grouped-query attention with rotate-half RoPE,
tanh-GELU feed-forward, tied embedding) as a float32 `jax.numpy`
forward at matmul precision `highest`, with a materialised causal
softmax: no kernel, no cache, no batching tricks, nothing of hpx_tpu.

Departures from StarCoder2's published block are the program's own
and are listed in the configuration file (`departures`).

It is run once the window has closed, over prompt ++ served tokens of
a few requests, layer by layer and in blocks of rows so that it fits
beside the weights. `quant="int8"` is the CONTROL, the nearest precision
below the bfloat16 the configuration states: the same forward as a
bfloat16 model served in int8 would compute it. Every weight matrix is
rounded to int8 per output channel, every matmul input to int8 per
token (W8A8), K and V to int8 per token and head (the program's own
`kv_dtype="int8"`), and everything between (LayerNorm outputs, matmul
results, probabilities, the residual stream, the logits) to bfloat16,
as the program keeps them. int8 in a float32 pipeline would be nearer
to float32 than the bfloat16 program itself is.

Weights come in the layout the harness makes them in (see
drivers/serving.py `make_params`): {"emb", "ln_f", "layers": [{"ln1",
"wq" [d,n,h], "wkv" [2,d,nkv,h] | "wqkv" [3,d,n,h], "wo" [n,h,d],
"ln2", "w1" [d,f], "b1", "w2" [f,d]}]}.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _q8(x, axis):
    """Symmetric int8 rounding along `axis` (absmax scaling), returned
    in float32: what an int8 path would feed its matmul."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _r(x, quant):
    """What lies between the control's matmuls is kept in bfloat16."""
    return x.astype(jnp.bfloat16).astype(F32) if quant else x


def _mm(x, w, quant):
    """x [..., d] @ w [d, ...]: contraction over x's last and w's first."""
    if quant == "int8":
        x = _q8(x, -1)
        w = _q8(w, 0)
    return _r(jnp.tensordot(x, w, axes=1), quant)


def _ln(x, scale, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "quant"))
def _layer(x, lp, *, theta, eps, quant):
    with jax.default_matmul_precision("highest"):
        s_len = x.shape[1]
        pos = jnp.arange(s_len)
        causal = pos[None, :] <= pos[:, None]
        h = _r(_ln(x, lp["ln1"], eps), quant)
        if "wqkv" in lp:
            w = lp["wqkv"].astype(F32)
            q, k, v = (_mm(h, w[i], quant) for i in range(3))
        else:
            q = _mm(h, lp["wq"].astype(F32), quant)
            wkv = lp["wkv"].astype(F32)
            k, v = _mm(h, wkv[0], quant), _mm(h, wkv[1], quant)
        if theta:
            q, k = _r(_rope(q, pos, theta), quant), _r(_rope(k, pos, theta),
                                                       quant)
        if quant == "int8":
            k, v = _q8(k, -1), _q8(v, -1)
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        sc = jnp.einsum("bqnh,bknh->bnqk", q, k) / math.sqrt(q.shape[-1])
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        att = _r(jnp.einsum("bnqk,bknh->bqnh",
                            _r(jax.nn.softmax(sc, -1), quant), v), quant)
        wo = lp["wo"].astype(F32)
        att = att.reshape(att.shape[:2] + (-1,))
        x = _r(x + _mm(att, wo.reshape(-1, wo.shape[-1]), quant), quant)
        h = _r(_ln(x, lp["ln2"], eps), quant)
        h = _r(jax.nn.gelu(_mm(h, lp["w1"].astype(F32), quant)
                           + lp["b1"].astype(F32), approximate=True), quant)
        return _r(x + _mm(h, lp["w2"].astype(F32), quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, emb, rows, picks, *, eps, quant):
    """Logits of the rows asked for: their best value, the value of the
    picked token, and the token the forward itself puts first."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, rows[..., None], axis=1)
        x = _r(_ln(x, ln_f, eps), quant)
        logits = _mm(x, emb.astype(F32).T, quant)
        best = logits.max(-1)
        picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
        return best, picked, jnp.argmax(logits, -1)


def sizes(config: dict) -> dict:
    """theta and eps of a configuration file (Hugging Face key names)."""
    return {"theta": float(config.get("rope_theta") or 0.0),
            "eps": float(config.get("norm_epsilon", 1e-5))}


def score(params, config: dict, tokens, rows, picks, quant=None,
          block: int = 4):
    """tokens [B, L] int32 (tail-padded; padding never reaches an
    earlier row through the causal mask), rows [B, R] the positions
    whose logits are wanted, picks [B, R] token ids. Returns numpy
    (best, picked, argmax), each [B, R]."""
    sz = sizes(config)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    picks = np.asarray(picks, np.int32)
    outs = []
    for b0 in range(0, tokens.shape[0], block):
        tk = jnp.asarray(tokens[b0:b0 + block])
        x = params["emb"].astype(F32)[tk]
        for lp in params["layers"]:
            x = _layer(x, lp, theta=sz["theta"], eps=sz["eps"], quant=quant)
        outs.append(jax.device_get(_head(
            x, params["ln_f"], params["emb"],
            jnp.asarray(rows[b0:b0 + block]),
            jnp.asarray(picks[b0:b0 + block]),
            eps=sz["eps"], quant=quant)))
    return tuple(np.concatenate([o[i] for o in outs]) for i in range(3))


def pack(requests, length: int, out_max: int):
    """requests: [(prompt, served)] -> tokens [B, length], rows, picks,
    mask [B, out_max]. Row j of a request predicts served[j]: it is the
    position of prompt ++ served[:j]'s last token."""
    n = len(requests)
    tokens = np.zeros((n, length), np.int32)
    rows = np.zeros((n, out_max), np.int32)
    picks = np.zeros((n, out_max), np.int32)
    mask = np.zeros((n, out_max), bool)
    for i, (prompt, served) in enumerate(requests):
        seq = list(prompt) + list(served[:-1])
        if len(seq) > length or len(served) > out_max:
            raise ValueError("request longer than the reference's frame")
        tokens[i, :len(seq)] = seq
        m = len(served)
        rows[i, :m] = len(prompt) - 1 + np.arange(m)
        picks[i, :m] = served
        mask[i, :m] = True
    return tokens, rows, picks, mask


def served_gaps(params, config, requests, length, out_max, quant=None):
    """For each served token, how far its float32-reference logit lies
    below the reference's best at that position. With `quant`, the
    CONTROL's reading instead: the gap of the token the lower precision
    puts first at each position of the same prompts and tokens.
    Returns the gaps of all served positions, flat."""
    tokens, rows, picks, mask = pack(requests, length, out_max)
    if quant is not None:
        _, _, picks = score(params, config, tokens, rows, picks, quant)
    best, picked, _ = score(params, config, tokens, rows, picks, None)
    return (best - picked)[mask]
