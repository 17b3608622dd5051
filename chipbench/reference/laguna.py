"""The plain reference of the Laguna configurations (poolside
Laguna-XS.2): the layer equations of the published config as a float32
`jax.numpy` forward at matmul precision `highest`, with a materialised
causal / windowed softmax: no kernel, no cache, no batching tricks,
nothing of hpx_tpu.

u = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w; no bias anywhere.

    h = x + Attn_l(RMSNorm_1(x));  y = h + FFN_l(RMSNorm_2(h))
    after the last layer RMSNorm, then the untied head.

Attn_l: n_q = num_attention_heads_per_layer[l] query heads, 8 kv heads
of 128. RoPE (rotate-half) on the first partial_rotary_factor * 128
dims of q and k: full layers YaRN exactly as `rope_parameters.
full_attention` says (inverse frequencies blended between theta-spaced
and those / factor by the linear ramp between the two correction dims;
cos and sin times attention_factor), sliding layers plain over the
whole head. Scores q.k / sqrt(128), causal; on a sliding layer position
i sees j with i - sliding_window < j <= i. Softmax in float32.
o_h = sum_j p_hj v_j (kv head h // (n_q / 8)), then the per-head gate
o_h <- sigmoid(W_g u)_h * o_h, then W_o.

FFN of a dense layer: W_down(silu(W_gate u) * W_up u). Of a sparse one:
s = sigmoid(W_r u) in float32; the num_experts_per_tok largest s; w_i =
moe_routed_scaling_factor * s_i / sum_chosen s; sum_i w_i E_i(u) +
E_shared(u), every E a SiLU-gated MLP. No capacity, nothing dropped;
computed as a loop over the experts, each on the tokens routed to it.

ASSUMED (the config does not spell these; also in the configuration
file): `hidden_act` silu (the catalog strips the key; every gated-MLP
family of this shape); the gate's form, per head (`gating: true` here,
`"per-head"` in the sibling Laguna-S-2.1; an element-wise gate would
add 0.6B parameters and the card says 33.4B, which the per-head count
gives); the router: sigmoid scores with renormalisation over the top 8
(`norm_topk_prob: true` in the sibling; 256 experts / top-8 / 1 shared /
scaling 2.5 is the DeepSeek-V3 router, whose scoring is sigmoid); no
q/k norm and no selection bias (no key names one); the window's edge
(i - 512 < j).

It is run once the window has closed, over prompt ++ served tokens of a
few requests: sequences in blocks of `block`, attention one (sequence,
kv head) at a time, the experts one at a time, logits only at the
served positions, so that 64 sequences of up to 4,864 tokens fit beside
the weights. `quant="int8"` is the CONTROL, the nearest precision below
the bfloat16 the configuration states: the same forward as a bfloat16
model served in int8 would compute it (every weight matrix int8 per
output channel, every matmul input int8 per token, K and V int8 per
token and head, everything between in bfloat16; router scores and
softmax stay float32, as the configuration's `precision` says).

Weights come in the program's layout (drivers/serving_mixed.py
`make_params`): {"emb", "head", "ln_f", "layers": [{"ln1", "ln2", "wq"
[d,n,h], "wkv" [2,d,nkv,h], "wo" [n,h,d], "wgate" [d,n], and "w1",
"w3" [d,f], "w2" [f,d] or "moe": {"wg" [d,E], "w1", "w3" [E,d,f], "w2"
[E,f,d], "shared": {"w1", "w3", "w2"}}}]}.

`leave_out` (tests only) drops one piece of the mathematics: "gate",
"scale" (the 2.5), "window_edge" (i - 512 <= j), "shared".
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
        x, w = _q8(x, -1), _q8(w, 0)
    return _r(jnp.tensordot(x, w, axes=1), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _swiglu(u, w1, w3, w2, quant):
    h = _r(jax.nn.silu(_mm(u, w1.astype(F32), quant))
           * _mm(u, w3.astype(F32), quant), quant)
    return _mm(h, w2.astype(F32), quant)


def inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    """Inverse frequencies of one entry of `rope_parameters`, over the
    rotated dims (partial_rotary_factor * head_dim), in float64."""
    rot = int(round(head_dim * float(rope.get("partial_rotary_factor", 1))))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") != "yarn":
        return 1.0 / pos_freqs
    factor, orig = float(rope["factor"]), float(
        rope["original_max_position_embeddings"])

    def correction_dim(n_rot):
        return rot * math.log(orig / (n_rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (
        1 - ramp)


def _rope(x, pos, freq, att_factor):
    """x [B, S, N, H]; rotate-half over the first 2 * len(freq) dims."""
    half = freq.shape[0]
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * att_factor)[None, :, None, :]
    sin = (jnp.sin(ang) * att_factor)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "quant", "window", "att_factor", "gate"))
def _attention(x, lp, freq, *, eps, quant, window, att_factor, gate):
    """x + Attn(RMSNorm_1(x)), one (sequence, kv head) at a time."""
    with jax.default_matmul_precision("highest"):
        b, s_len, _ = x.shape
        pos = jnp.arange(s_len)
        seen = pos[None, :] <= pos[:, None]
        if window:
            seen = jnp.logical_and(seen, pos[None, :] > pos[:, None] - window)
        u = _r(_rms(x, lp["ln1"], eps), quant)
        q = _mm(u, lp["wq"].astype(F32), quant)
        wkv = lp["wkv"].astype(F32)
        k, v = _mm(u, wkv[0], quant), _mm(u, wkv[1], quant)
        q = _r(_rope(q, pos, freq, att_factor), quant)
        k = _r(_rope(k, pos, freq, att_factor), quant)
        if quant == "int8":
            k, v = _q8(k, -1), _q8(v, -1)
        nq, nkv, hd = q.shape[2], k.shape[2], q.shape[3]
        qg = q.reshape(b, s_len, nkv, nq // nkv, hd)

        def one(args):
            qh, kh, vh = args                  # [S, g, H], [S, H], [S, H]
            sc = jnp.einsum("qgh,kh->gqk", qh, kh) / math.sqrt(hd)
            p = _r(jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1),
                   quant)
            return jnp.einsum("gqk,kh->qgh", p, vh)
        flat = lambda t: jnp.moveaxis(t, 2, 1).reshape(  # noqa: E731
            (b * nkv, s_len) + t.shape[3:])
        att = jax.lax.map(one, (flat(qg), flat(k), flat(v)))
        att = jnp.moveaxis(att.reshape(b, nkv, s_len, nq // nkv, hd), 1, 2)
        att = _r(att.reshape(b, s_len, nq, hd), quant)
        if gate:
            g = jax.nn.sigmoid(_mm(u, lp["wgate"].astype(F32), quant))
            att = _r(att * g[..., None], quant)
        wo = lp["wo"].astype(F32)
        return _r(x + _mm(att.reshape(b, s_len, -1),
                          wo.reshape(-1, wo.shape[-1]), quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, lp, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        u = _r(_rms(x, lp["ln2"], eps), quant)
        return _r(x + _swiglu(u, lp["w1"], lp["w3"], lp["w2"], quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "top_k",
                                             "scale"))
def _route(x, lp, *, eps, quant, top_k, scale):
    """(u, expert ids [N, k], weights [N, k]) of the flattened tokens."""
    with jax.default_matmul_precision("highest"):
        u = _r(_rms(x, lp["ln2"], eps), quant).reshape(-1, x.shape[-1])
        s = jax.nn.sigmoid(jnp.tensordot(
            u, lp["moe"]["wg"].astype(F32), axes=1))
        w, idx = jax.lax.top_k(s, top_k)
        return u, idx, scale * w / jnp.sum(w, -1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("quant", "cap", "shared"))
def _experts(x, u, tok, wgt, start, size, place, mp, *, quant, cap, shared):
    """x + sum_i w_i E_i(u) + E_shared(u): a loop over the experts, each
    on the (at most `cap`) tokens routed to it. The assignments come
    sorted by expert (`tok`, `wgt`: token id and weight of each;
    `start`, `size`: each expert's segment of them), so an expert reads
    and writes one contiguous run of rows (a row-by-row scatter is
    slow on the chip); rows past its `size` are the next expert's and
    are overwritten by it. `place` [N, k]: where each token's k rows
    lie in that order."""
    with jax.default_matmul_precision("highest"):
        n_exp, d = mp["w1"].shape[0], u.shape[-1]
        rows = jnp.concatenate([u[tok], jnp.zeros((cap, d), F32)])

        def body(e, out):
            seg = jax.lax.dynamic_slice_in_dim(rows, start[e], cap)
            y = _swiglu(seg, mp["w1"][e], mp["w3"][e], mp["w2"][e], quant)
            return jax.lax.dynamic_update_slice_in_dim(out, y, start[e], 0)
        out = jax.lax.fori_loop(0, n_exp, body, jnp.zeros_like(rows))
        out = out[:tok.shape[0]] * wgt[:, None]
        out = _r(jnp.sum(out[place], axis=1), quant)
        if shared:
            sp = mp["shared"]
            out = _r(out + _swiglu(u, sp["w1"], sp["w3"], sp["w2"], quant),
                     quant)
        return _r(x + out.reshape(x.shape), quant)


def _sparse_ffn(x, lp, *, eps, quant, top_k, scale, shared):
    u, idx, w = _route(x, lp, eps=eps, quant=quant, top_k=top_k, scale=scale)
    n_exp = lp["moe"]["w1"].shape[0]
    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    size = np.bincount(flat, minlength=n_exp)
    # one static segment length a frame (a new length is a new compile):
    # half again the mean load, doubled until the fullest expert fits
    cap = -(-3 * flat.size // (2 * n_exp) // 64) * 64
    while cap < size.max():
        cap *= 2
    place = np.empty_like(order)
    place[order] = np.arange(order.size)
    return _experts(
        x, u, jnp.asarray(order // top_k, jnp.int32),
        jnp.asarray(np.asarray(w).reshape(-1)[order]),
        jnp.asarray(np.cumsum(size) - size, jnp.int32),
        jnp.asarray(size, jnp.int32),
        jnp.asarray(place.reshape(-1, top_k), jnp.int32), lp["moe"],
        quant=quant, cap=cap, shared=shared)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, rows, picks, *, eps, quant):
    """Logits of the rows asked for: their best value, the value of the
    picked token, and the token the forward itself puts first."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, rows[..., None], axis=1)
        x = _r(_rms(x, ln_f, eps), quant)
        logits = _mm(x, head.astype(F32).T, quant)
        best = logits.max(-1)
        picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
        return best, picked, jnp.argmax(logits, -1)


def forward(params, config: dict, tokens, quant=None, leave_out=()):
    """Hidden states [B, L, d] after the last layer (before the final
    norm) of tokens [B, L]."""
    eps = float(config["rms_norm_eps"])
    hd = int(config["head_dim"])
    ropes = config["rope_parameters"]
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    for li, lp in enumerate(params["layers"]):
        kind = config["layer_types"][li]
        rope = ropes[kind]
        window = int(config["sliding_window"]) if \
            kind == "sliding_attention" else 0
        if window and "window_edge" in leave_out:
            window += 1
        x = _attention(
            x, lp, jnp.asarray(inv_freq(rope, hd), F32), eps=eps,
            quant=quant, window=window,
            att_factor=float(rope.get("attention_factor", 1.0)),
            gate=bool(config["gating"]) and "gate" not in leave_out)
        if config["mlp_layer_types"][li] == "dense":
            x = _dense_ffn(x, lp, eps=eps, quant=quant)
        else:
            x = _sparse_ffn(
                x, lp, eps=eps, quant=quant,
                top_k=int(config["num_experts_per_tok"]),
                scale=1.0 if "scale" in leave_out
                else float(config["moe_routed_scaling_factor"]),
                shared="shared" not in leave_out)
    return x


def score(params, config: dict, tokens, rows, picks, quant=None,
          block: int = 2, leave_out=()):
    """tokens [B, L] int32 (tail-padded; padding never reaches an
    earlier row through the causal mask), rows [B, R] the positions
    whose logits are wanted, picks [B, R] token ids. Returns numpy
    (best, picked, argmax), each [B, R]."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    picks = np.asarray(picks, np.int32)
    outs = []
    for b0 in range(0, tokens.shape[0], block):
        x = forward(params, config, tokens[b0:b0 + block], quant, leave_out)
        outs.append(jax.device_get(_head(
            x, params["ln_f"], params["head"],
            jnp.asarray(rows[b0:b0 + block]),
            jnp.asarray(picks[b0:b0 + block]),
            eps=float(config["rms_norm_eps"]), quant=quant)))
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


def served_gaps(params, config, requests, length, out_max, quant=None,
                leave_out=()):
    """For each served token, how far its float32-reference logit lies
    below the reference's best at that position. With `quant`, the
    CONTROL's reading instead: the gap of the token the lower precision
    puts first at each position of the same prompts and tokens.
    Returns the gaps of all served positions, flat."""
    tokens, rows, picks, mask = pack(requests, length, out_max)
    if quant is not None:
        _, _, picks = score(params, config, tokens, rows, picks, quant)
    best, picked, _ = score(params, config, tokens, rows, picks, None,
                            leave_out=leave_out)
    return (best - picked)[mask]
