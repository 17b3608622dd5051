"""The plain reference of the EvaByte configurations (EvaByte 6.5B,
`model_type` evabyte, `attention_class` eva): the layer equations as a
float32 `jax.numpy` forward at matmul precision `highest`. No cache, no
kernel, nothing of hpx_tpu: K and V of the whole sequence, the summary
of every whole chunk, and for each block of queries the score matrix
over [exact rows | summary rows] with both masks materialised and ONE
softmax over the concatenation.

A byte model: vocabulary V = 320, no tokenizer. `h` the residual
stream, `t` a byte position, `n` a head, C = `chunk_size`, W =
`window_size`, RMSNorm(x; s) = x / sqrt(mean(x^2) + eps) * s.

    h += EVA(RMSNorm(h; 1 + g_in));   h += W_down(silu(W_gate x) * W_up x),
    x = RMSNorm(h; 1 + g_ff)   (`norm_add_unit_offset`: the scale is 1 + g)
    after the last layer RMSNorm(h; 1 + g_f); logits = W_head x in
    float32, [P, V] a position: head m (rows V m .. V m + V - 1 of
    W_head, P = `num_pred_heads`) predicts byte t + 1 + m.

EVA (the public modeling code's eva.py / eva_prep_kv_kernel.py /
eva_agg_kernel.py):
    q, k, v = x W_q, x W_k, x W_v, each [H, d];  q, k <- RoPE(.; t,
    theta) (rotate-half over all d dims, absolute position): keys are
    rotated BEFORE they are pooled.
    chunk c = bytes C c .. C c + C - 1, head n, learned phi_n, mu_n in R^d:
        a_j = (k_j . phi_n) / sqrt(d);  p = softmax_j(a) over the chunk's
        C rows, float32;  k~_c = sum_j p_j k_j + mu_n;  v~_c = sum_j p_j v_j
    the query at t, with w = floor(t / W), attends the EXACT rows
    {j : W w <= j <= t} (its own ALIGNED window) and the SUMMARY rows
    {c : c < (W / C) w} (every chunk of every window behind its own),
    under ONE softmax: scores q_t . k_j / sqrt(d) and q_t . k~_c /
    sqrt(d) side by side, values v_j and v~_c. Then W_o.

Departures from the published code, none in a number: only complete
windows are summarised and C divides W, so every summarised chunk is
whole and no chunk mask exists (the published training path pads); the
frame is padded to whole windows with byte 0, which no earlier row sees
(causal); weights come in the PROGRAM's layout
(drivers/serving_eva.py `make_params`).

`quant` is a CONTROL: "int8" = the same forward as a bfloat16 model
served in int8 (every weight matrix int8 per output channel, every
matmul input int8 per token, the cached K and V rows, exact and
summary, int8 per row, everything between in bfloat16; pooling
weights and every softmax float32); "window_only" = the float32
forward in which NO summary row is attended (the mechanism left out);
"summary_bf16_weights" = the float32 forward with the pooling weights p
rounded to bfloat16. Controls round with `lax.reduce_precision` (the
chip's compiler elides a pair of converts).

`leave_out` (tests only) drops one piece of the mathematics: "mu",
"phi_scale" (a_j without the 1 / sqrt(d)), "pool_v" (v~ the plain mean
of the chunk's rows), "rope_before_pool" (summaries pooled from
UNROTATED keys), "aligned" (the exact window slides: t - W < j <= t),
"gate" (summaries visible one window early: every chunk complete at
t), "unit_offset" (the norm's scale is g, not 1 + g).

Weights: {"emb" [V, D], "ln_f" [D], "head" [P V, D], "layers":
[{"ln1", "ln2" [D], "eva": {"wq", "wk", "wv" [D, H d], "phi", "mu" [H,
d], "wo" [H d, D]}, "w1", "w3" [D, f], "w2" [f, D]}]}.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERIES_A_BLOCK = 128


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _r(x, quant):
    """What lies between the int8 control's matmuls is bfloat16."""
    return _bf16(x) if quant == "int8" else x


def _mm(x, w, quant):
    """x [..., d] @ w [d, ...]."""
    w = w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return _r(jnp.tensordot(x, w, axes=1), quant)


def _rms(x, g, eps, leave_out):
    scale = g.astype(F32) + (0.0 if "unit_offset" in leave_out else 1.0)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x [L, H, d] at positions 0 .. L - 1, rotate-half over all d."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _summaries(k, v, kp, phi, mu, chunk, quant, leave_out):
    """(k~, v~) [L / C, H, d] of K / V rows [L, H, d]; `kp`: the keys
    the pooling weights and sums are taken from (the rotated keys)."""
    n, h, d = k.shape
    g = lambda t: t.reshape(n // chunk, chunk, h, d)        # noqa: E731
    a = jnp.einsum("gchd,hd->gch", g(kp), phi.astype(F32))
    if "phi_scale" not in leave_out:
        a = a / math.sqrt(d)
    p = jax.nn.softmax(a, axis=1)
    if quant == "summary_bf16_weights":
        p = _bf16(p)
    ks = jnp.einsum("gch,gchd->ghd", p, g(kp))
    if "mu" not in leave_out:
        ks = ks + mu.astype(F32)
    vs = g(v).mean(1) if "pool_v" in leave_out else \
        jnp.einsum("gch,gchd->ghd", p, g(v))
    return ks, vs


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "chunk", "window", "quant", "leave_out"))
def _eva(x, lp, *, eps, theta, chunk, window, quant, leave_out):
    """(x + EVA(RMSNorm_1(x)), k~, v~) of x [L, D], L whole windows."""
    with jax.default_matmul_precision("highest"):
        m = lp["eva"]
        n = x.shape[0]
        h, d = m["phi"].shape
        u = _r(_rms(x, lp["ln1"], eps, leave_out), quant)
        q, k0, v = (_mm(u, m[w], quant).reshape(n, h, d)
                    for w in ("wq", "wk", "wv"))
        q, k = _r(_rope(q, theta), quant), _r(_rope(k0, theta), quant)
        ks, vs = _summaries(
            k, v, k0 if "rope_before_pool" in leave_out else k,
            m["phi"], m["mu"], chunk, quant, leave_out)
        ks, vs = _r(ks, quant), _r(vs, quant)
        if quant == "int8":                 # the cached rows, as served
            k, v, ks, vs = (_q8(t, -1) for t in (k, v, ks, vs))
        j = jnp.arange(n)
        c = jnp.arange(n // chunk)
        per = QUERIES_A_BLOCK if n % QUERIES_A_BLOCK == 0 else window

        def block(t0):
            t = t0 + jnp.arange(per)
            qb = jax.lax.dynamic_slice_in_dim(q, t0, per, 0)
            w = t // window
            if "aligned" in leave_out:
                exact = (j[None] <= t[:, None]) \
                    & (j[None] > t[:, None] - window)
            else:
                exact = (j[None] <= t[:, None]) \
                    & (j[None] >= (window * w)[:, None])
            if quant == "window_only":
                seen = jnp.zeros((t.shape[0], c.shape[0]), bool)
            elif "gate" in leave_out:
                seen = (c[None] + 1) * chunk <= t[:, None] + 1
            else:
                seen = c[None] < (window // chunk * w)[:, None]
            s = jnp.concatenate(
                [jnp.einsum("qhd,khd->hqk", qb, k),
                 jnp.einsum("qhd,khd->hqk", qb, ks)], -1) / math.sqrt(d)
            live = jnp.concatenate([exact, seen], -1)
            p = _r(jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), -1),
                   quant)
            return jnp.einsum("hqk,khd->qhd", p[..., :n], v) \
                + jnp.einsum("hqk,khd->qhd", p[..., n:], vs)
        att = jax.lax.map(block, jnp.arange(0, n, per))
        att = _r(att.reshape(n, h * d), quant)
        return _r(x + _mm(att, m["wo"], quant), quant), ks, vs


@functools.partial(jax.jit, static_argnames=("eps", "quant", "leave_out"))
def _ffn(x, lp, *, eps, quant, leave_out):
    with jax.default_matmul_precision("highest"):
        v = _r(_rms(x, lp["ln2"], eps, leave_out), quant)
        h = _r(jax.nn.silu(_mm(v, lp["w1"], quant))
               * _mm(v, lp["w3"], quant), quant)
        return _r(x + _mm(h, lp["w2"], quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "leave_out"))
def _head(x, ln_f, head, *, eps, quant, leave_out):
    """Every head's logits [R, P V] of the rows x [R, D], float32."""
    with jax.default_matmul_precision("highest"):
        x = _r(_rms(x, ln_f, eps, leave_out), quant)
        w = head.astype(F32).T
        if quant == "int8":
            x, w = _q8(x, -1), _q8(w, 0)
        return x @ w


def _statics(config, quant, leave_out):
    return dict(eps=float(config["rms_norm_eps"]), quant=quant,
                leave_out=tuple(sorted(leave_out)))


def forward(params, config: dict, tokens, quant=None, leave_out=(),
            summaries_of=None):
    """Hidden states [L, D] after the last layer (before the final
    norm) of tokens [L], L whole windows; with `summaries_of` a layer's
    index, (hidden, (k~, v~) [L / C, H, d] of that layer) instead."""
    st = _statics(config, quant, leave_out)
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    kept = None
    for i, lp in enumerate(params["layers"]):
        x, ks, vs = _eva(x, lp, theta=float(config["rope_theta"]),
                         chunk=int(config["chunk_size"]),
                         window=int(config["window_size"]), **st)
        if i == summaries_of:
            kept = (ks, vs)
        x = _ffn(x, lp, **st)
    return x if summaries_of is None else (x, kept)


def _frame(n: int, config: dict) -> int:
    w = int(config["window_size"])
    return max(w, -(-n // w) * w)


def logits(params, config: dict, tokens, quant=None, leave_out=(),
           summaries_of=None):
    """Every position's logits [T, P, V] float32 of tokens [T] (padded
    here to whole windows); with `summaries_of`, also that layer's
    summary rows (k~, v~) [T // C, H, d] of the whole chunks."""
    tokens = np.asarray(tokens, np.int32)
    t = tokens.shape[0]
    frame = np.zeros((_frame(t, config),), np.int32)
    frame[:t] = tokens
    out = forward(params, config, frame, quant, leave_out, summaries_of)
    x, kept = out if summaries_of is not None else (out, None)
    lg = _head(x[:t], params["ln_f"], params["head"],
               **_statics(config, quant, leave_out))
    lg = lg.reshape(t, int(config["num_pred_heads"]),
                    int(config["vocab_size"]))
    if kept is None:
        return lg
    whole = t // int(config["chunk_size"])
    return lg, (kept[0][:whole], kept[1][:whole])


def served_gaps(params, config, requests, quant=None, leave_out=()):
    """For each served byte, how far its float32-reference logit (head
    0, the next byte's) lies below the reference's best at that
    position. With `quant`, a CONTROL's reading instead: the gap of the
    byte the control puts first at each position of the same prompts
    and bytes. Each request alone in a frame of its length rounded up
    to whole windows. Returns the gaps of all served positions, flat."""
    v = int(config["vocab_size"])
    st = _statics(config, None, leave_out)
    gaps = []
    for prompt, served in requests:
        seq = np.asarray(list(prompt) + list(served[:-1]), np.int32)
        frame = np.zeros((_frame(len(seq), config),), np.int32)
        frame[:len(seq)] = seq
        rows = len(prompt) - 1 + np.arange(len(served))
        picks = np.asarray(served, np.int32)
        if quant is not None:
            x = forward(params, config, frame, quant)
            picks = np.asarray(jnp.argmax(_head(
                x[rows], params["ln_f"], params["head"],
                **_statics(config, quant, ()))[:, :v], -1))
        x = forward(params, config, frame, None, leave_out)
        lg = np.asarray(_head(x[rows], params["ln_f"], params["head"],
                              **st)[:, :v])
        gaps.append(lg.max(-1) - lg[np.arange(len(rows)), picks])
    return np.concatenate(gaps)


def first_summaries(params, config, tokens, quant=None):
    """(k~, v~) [T // C, H, d] of the model's FIRST layer over tokens
    [T]: it sees the embeddings alone, so no other layer runs."""
    tokens = np.asarray(tokens, np.int32)
    frame = np.zeros((_frame(len(tokens), config),), np.int32)
    frame[:len(tokens)] = tokens
    x = params["emb"][jnp.asarray(frame)].astype(F32)
    _, ks, vs = _eva(x, params["layers"][0],
                     theta=float(config["rope_theta"]),
                     chunk=int(config["chunk_size"]),
                     window=int(config["window_size"]),
                     **_statics(config, quant, ()))
    whole = len(tokens) // int(config["chunk_size"])
    return np.asarray(ks[:whole]), np.asarray(vs[:whole])


def visible_rows(n_tokens: int, config: dict) -> int:
    """Summary rows a query behind `n_tokens` consumed bytes may see:
    those of the complete windows."""
    w, c = int(config["window_size"]), int(config["chunk_size"])
    return w // c * (n_tokens // w)


def summary_errors(params, config, states, quant=None):
    """How far served summary rows lie from the float32 reference's:
    (|S - S_ref|_F / |S_ref|_F a slot over its visible K and V rows
    stacked, the count of slots whose visible count is not the
    reference's). states: [(byte ids consumed, visible count, k~, v~
    [visible, H, d])], the served program's first layer; with `quant`,
    a CONTROL's own rows of the same bytes in their place. A slot with
    no visible row reads 0."""
    errs, miscounted = [], 0
    for tokens, visible, ks, vs in states:
        want_n = visible_rows(len(tokens), config)
        miscounted += int(visible != want_n)
        if not want_n or visible != want_n:
            errs.append(0.0)
            continue
        want = np.stack([a[:want_n] for a in first_summaries(
            params, config, tokens)])
        got = (np.stack([np.asarray(ks, np.float32),
                         np.asarray(vs, np.float32)]) if quant is None
               else np.stack([a[:want_n] for a in first_summaries(
                   params, config, tokens, quant)]))
        errs.append(float(np.sqrt(((got - want) ** 2).sum()
                                  / (want ** 2).sum())))
    return np.asarray(errs), miscounted
