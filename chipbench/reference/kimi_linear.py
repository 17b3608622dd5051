"""The plain reference of the Kimi-Linear configurations (moonshotai
Kimi-Linear-48B-A3B-Instruct, `model_type` kimi_linear): the layer
equations as a float32 `jax.numpy` forward at matmul precision
`highest`. The KDA recurrence is a token-by-token scan, MLA is in the
EXPANDED form (every head's keys and values materialised from the
latent rows), the softmax is materialised: no kernel, no cache, no
chunkwise form, nothing of hpx_tpu.

u = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w; no bias but b_g.

    h = x + Mixer_l(RMSNorm_1(x));  y = h + FFN_l(RMSNorm_2(h))
    after the last layer RMSNorm, then the untied head.

KDA layer (`linear_attn_config.kda_layers`, 1-indexed; H heads of d =
head_dim; per head):
    q~, k~, v = SiLU(conv(W_q u)), SiLU(conv(W_k u)), SiLU(conv(W_v u))
        conv: depthwise causal over time, short_conv_kernel_size taps a
        channel, no bias, zeros before the first token
    q = q~ / sqrt(|q~|^2 + 1e-6) * d^-1/2;  k = k~ / sqrt(|k~|^2 + 1e-6)
    g = -exp(A_log) * softplus(W_f2 (W_f1 u) + dt_bias)  in R^d, float32
    beta = sigmoid(w_b . u)
    S' = Diag(exp g) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
    y = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u) + b_g)]
No positions anywhere.

MLA layer (`full_attn_layers`; `mla_use_nope`: no rotation, the
qk_rope_head_dim dims are plain; `q_lora_rank` null):
    c = RMSNorm(W_dkv u)[:kv_lora_rank], r = (W_dkv u)[kv_lora_rank:]
    [q^C_h; q^R_h] = W_q,h u;  k_h,j = [W_uk,h c_j; r_j];
    v_h,j = W_uv,h c_j
    causal softmax of q . k / sqrt(qk_nope_head_dim + qk_rope_head_dim)
    in float32;  y = W_o concat_h(sum_j p_j v_h,j)

FFN: layers below `first_k_dense_replace` W_2(SiLU(W_1 h) * W_3 h);
the others s = sigmoid(W_r h) in float32 over the PUBLISHED router
width; the num_experts_per_token largest of s + b (the selection bias,
for the choice only); weights s_i / sum_chosen s * routed_scaling_factor;
experts and one shared expert SiLU-gated. The configuration holds a
SHARE of the experts (`experts_held` = [lo, hi)): the router scores all
of them, the held ones compute, what the absent ones would add is left
out (no stand-in). Each held expert runs over every token under its
weight (zero where it was not chosen): dense, static shapes.

The ASSUMED pieces (the config gives only head_dim, num_heads and
short_conv_kernel_size of a KDA layer) are listed with their reasons in
the configuration file under `assumed`.

`quant` is a CONTROL, a precision below the one the configuration
states: "int8" = the same forward as a bfloat16 model served in int8
(every weight matrix int8 per output channel, every matmul input int8
per token, the latent rows int8 per token, everything between in
bfloat16; router scores, decays, the state and every softmax stay
float32); "state_bf16" = the same forward as a bfloat16 model that
carries the KDA state in bfloat16 (rounded after every token), where
the configuration says float32.

Weights come in the program's layout (drivers/serving_hybrid.py
`make_params`): {"emb", "head", "ln_f", "layers": [{"ln1", "ln2",
"kda": {"wqkv" [D,3,H,d], "conv" [K,3,H,d], "wf1" [D,r], "wf2" [r,H,d],
"A_log" [H], "dt_bias" [H,d], "wb" [D,H], "wg1", "wg2", "bg" [H,d],
"onorm" [d], "wo" [H,d,D]} or "mla": {"wq" [D,H,dn+dr], "wdkv"
[D,r+dr], "kvnorm" [r], "wuk" [r,H,dn], "wuv" [r,H,dv], "wo"
[H,dv,D]}, and "w1", "w3" [D,f], "w2" [f,D] or "moe": {"wg" [D,E],
"bias" [E], "w1", "w3" [held,D,f], "w2" [held,f,D], "shared"}}]}.

`first_state` / `state_errors`: the first layer's recurrent state of
given tokens, and how far served states lie from it: what holds the
program to the float32 the configuration states for the state, which
no served token can show (the state's rounding lies under the noise of
bfloat16 activations).

`leave_out` (tests only) drops one piece of the mathematics: "decay",
"beta", "conv", "l2norm", "out_gate", "rope_dims" (the 64 plain dims),
"bias", "shared".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.laguna import pack     # the requests' frame

F32 = jnp.float32


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in float32.
    `reduce_precision`, not a pair of converts: the compiler is allowed
    to keep the excess precision of float32 -> bfloat16 -> float32 and
    on the chip it does (a state "carried in bfloat16" that way came
    out bit for bit the float32 one)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _r(x, quant):
    """What lies between a control's matmuls is kept in bfloat16."""
    return _bf16(x) if quant else x


def _mm(x, w, quant):
    """x [..., d] @ w [d, ...]: contraction over x's last and w's first."""
    w = w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return _r(jnp.tensordot(x, w, axes=1), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _swiglu(u, w1, w3, w2, quant):
    h = _r(jax.nn.silu(_mm(u, w1, quant)) * _mm(u, w3, quant), quant)
    return _mm(h, w2, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "leave_out"))
def _kda(x, lp, lengths=None, *, eps, quant, leave_out):
    """(x + KDA(RMSNorm_1(x)), the state after the last token): the
    recurrence as a scan over the tokens. `lengths` [B]: each
    sequence's real tokens; the padding behind them leaves its state as
    it is (beta 0, no decay), so the state returned is that after
    `lengths` tokens."""
    with jax.default_matmul_precision("highest"):
        m = lp["kda"]
        b, n, _ = x.shape
        taps, _, h, d = m["conv"].shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        pre = _mm(u, m["wqkv"], quant)                    # [B, L, 3, H, d]
        if "conv" in leave_out:
            act = pre
        else:
            full = jnp.pad(pre, ((0, 0), (taps - 1, 0)) + ((0, 0),) * 3)
            cw = m["conv"].astype(F32)
            act = sum(full[:, j:j + n] * cw[j] for j in range(taps))
        q, k, v = jnp.moveaxis(_r(jax.nn.silu(act), quant), 2, 0)
        if "l2norm" not in leave_out:
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q = q * d ** -0.5
        g = -jnp.exp(m["A_log"].astype(F32))[:, None] * jax.nn.softplus(
            _mm(_mm(u, m["wf1"], quant), m["wf2"], quant)
            + m["dt_bias"].astype(F32))
        if "decay" in leave_out:
            g = jnp.zeros_like(g)
        beta = jax.nn.sigmoid(_mm(u, m["wb"], quant))     # [B, L, H]
        if "beta" in leave_out:
            beta = jnp.ones_like(beta)
        if lengths is not None:
            real = jnp.arange(n)[None, :] < lengths[:, None]
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)

        def step(s, t):
            qt, kt, vt, gt, bt = t
            sd = s * jnp.exp(gt)[..., None]
            uu = jnp.sum(sd * kt[..., None], axis=-2)
            s = sd + kt[..., None] * (bt[..., None] * (vt - uu))[..., None, :]
            if quant == "state_bf16":
                s = _bf16(s)
            return s, jnp.sum(s * qt[..., None], axis=-2)
        tm = lambda a: jnp.moveaxis(a, 1, 0)              # noqa: E731
        last, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), F32),
                               (tm(q), tm(k), tm(v), tm(g), tm(beta)))
        o = jnp.moveaxis(o, 0, 1)                         # [B, L, H, d]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * m["onorm"].astype(F32)
        if "out_gate" not in leave_out:
            o = o * jax.nn.sigmoid(
                _mm(_mm(u, m["wg1"], quant), m["wg2"], quant)
                + m["bg"].astype(F32))
        o = _r(o, quant).reshape(b, n, h * d)
        wo = m["wo"].astype(F32)
        return _r(x + _mm(o, wo.reshape(h * d, -1), quant), quant), last


@functools.partial(jax.jit, static_argnames=("eps", "quant", "rank",
                                             "nope", "leave_out"))
def _mla(x, lp, *, eps, quant, rank, nope, leave_out):
    """x + MLA(RMSNorm_1(x)) in the expanded form, one (sequence, head)
    at a time."""
    with jax.default_matmul_precision("highest"):
        m = lp["mla"]
        b, n, _ = x.shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        ckr = _mm(u, m["wdkv"], quant)
        c = _r(_rms(ckr[..., :rank], m["kvnorm"], eps), quant)
        r = ckr[..., rank:]
        if quant == "int8":                 # the cached row, as served
            c, r = _q8(c, -1), _q8(r, -1)
        q = _mm(u, m["wq"], quant)                        # [B, L, H, dn+dr]
        h = q.shape[2]
        kc = _mm(c, m["wuk"], quant)                      # [B, L, H, dn]
        v = _mm(c, m["wuv"], quant)                       # [B, L, H, dv]
        k = jnp.concatenate(
            [kc, jnp.broadcast_to(r[:, :, None, :], (b, n, h, r.shape[-1]))],
            -1)
        if "rope_dims" in leave_out:
            q, k = q[..., :nope], k[..., :nope]
        scale = (nope + r.shape[-1]) ** -0.5
        seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

        def one(args):
            qh, kh, vh = args                             # [L, *]
            sc = jnp.where(seen, (qh @ kh.T) * scale, -jnp.inf)
            return _r(jax.nn.softmax(sc, -1), quant) @ vh
        flat = lambda t: jnp.moveaxis(t, 2, 1).reshape(   # noqa: E731
            (b * h, n, t.shape[-1]))
        att = jax.lax.map(one, (flat(q), flat(k), flat(v)))
        att = _r(jnp.moveaxis(att.reshape(b, h, n, -1), 1, 2), quant)
        wo = m["wo"].astype(F32)
        return _r(x + _mm(att.reshape(b, n, -1),
                          wo.reshape(-1, wo.shape[-1]), quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, lp, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        u = _r(_rms(x, lp["ln2"], eps), quant)
        return _r(x + _swiglu(u, lp["w1"], lp["w3"], lp["w2"], quant), quant)


def _scores(u, mp):
    return jax.nn.sigmoid(jnp.tensordot(u, mp["wg"].astype(F32), axes=1))


@functools.partial(jax.jit, static_argnames=("eps",))
def router_scores(x, lp, *, eps):
    """s [B, L, router width] of a sparse layer whose FFN x enters."""
    with jax.default_matmul_precision("highest"):
        return _scores(_rms(x, lp["ln2"], eps), lp["moe"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "quant", "top_k", "scale", "lo", "leave_out"))
def _sparse_ffn(x, lp, *, eps, quant, top_k, scale, lo, leave_out):
    """x + sum over the HELD experts of w_e E_e(u) + E_shared(u): the
    router over its whole width, each held expert over every token
    under its weight (zero where the token did not choose it)."""
    with jax.default_matmul_precision("highest"):
        mp = lp["moe"]
        u = _r(_rms(x, lp["ln2"], eps), quant)
        s = _scores(u, mp)
        sel = s if "bias" in leave_out else s + mp["bias"].astype(F32)
        _, idx = jax.lax.top_k(sel, top_k)
        w = jnp.take_along_axis(s, idx, -1)
        w = scale * w / jnp.sum(w, -1, keepdims=True)
        n_held = mp["w1"].shape[0]

        def body(e, out):
            we = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)
            y = _swiglu(u, mp["w1"][e], mp["w3"][e], mp["w2"][e], quant)
            return out + we[..., None] * y
        out = _r(jax.lax.fori_loop(0, n_held, body, jnp.zeros_like(x)),
                 quant)
        if "shared" in mp and "shared" not in leave_out:
            sp = mp["shared"]
            out = _r(out + _swiglu(u, sp["w1"], sp["w3"], sp["w2"], quant),
                     quant)
        return _r(x + out, quant)


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


def forward(params, config: dict, tokens, quant=None, leave_out=(),
            visit=None):
    """Hidden states [B, L, d] after the last layer (before the final
    norm) of tokens [B, L]. `visit(lp, s) -> lp`, where given, is
    called at each sparse layer with its parameters and its router's
    scores of these tokens, and returns the parameters the layer runs
    with (drivers/serving_hybrid.py balances the selection bias so)."""
    eps = float(config["rms_norm_eps"])
    leave_out = tuple(sorted(leave_out))
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    for lp in params["layers"]:
        if "kda" in lp:
            x, _ = _kda(x, lp, eps=eps, quant=quant, leave_out=leave_out)
        else:
            x = _mla(x, lp, eps=eps, quant=quant,
                     rank=int(config["kv_lora_rank"]),
                     nope=int(config["qk_nope_head_dim"]),
                     leave_out=leave_out)
        if "moe" in lp:
            if visit is not None:
                lp = visit(lp, router_scores(x, lp, eps=eps))
            x = _sparse_ffn(
                x, lp, eps=eps, quant=quant,
                top_k=int(config["num_experts_per_token"]),
                scale=float(config["routed_scaling_factor"]),
                lo=int(config["experts_held"][0]), leave_out=leave_out)
        else:
            x = _dense_ffn(x, lp, eps=eps, quant=quant)
    return x


def first_state(params, config: dict, tokens, lengths, quant=None):
    """The recurrent state [B, H, d, d] of the model's FIRST layer (a
    KDA layer: it sees the embeddings alone, so no other layer runs)
    after `lengths` [B] tokens of tokens [B, L]."""
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    return _kda(x, params["layers"][0], jnp.asarray(lengths),
                eps=float(config["rms_norm_eps"]), quant=quant,
                leave_out=())[1]


def state_errors(params, config, states, quant=None):
    """How far recurrent states lie from the float32 reference's, as
    |S - S_ref|_F / |S_ref|_F a sequence. states: [(token ids consumed,
    state [H, d, d] of the first layer)], the served program's; with
    `quant`, a CONTROL's own state of the same tokens in their place."""
    n = max(len(t) for t, _ in states)
    n += -n % 256                       # few frames, few compiles
    tokens = np.zeros((len(states), n), np.int32)
    for i, (t, _) in enumerate(states):
        tokens[i, :len(t)] = t
    lengths = np.asarray([len(t) for t, _ in states], np.int32)
    want = np.asarray(first_state(params, config, tokens, lengths))
    got = (np.stack([s for _, s in states]) if quant is None else
           np.asarray(first_state(params, config, tokens, lengths, quant)))
    err = np.sqrt(((got - want) ** 2).sum((1, 2, 3)))
    return err / np.sqrt((want ** 2).sum((1, 2, 3)))


def logits(params, config: dict, tokens, quant=None, leave_out=()):
    """Every position's logits [B, L, V] (tests at a small size)."""
    x = forward(params, config, tokens, quant, leave_out)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"], float(config["rms_norm_eps"]))
        return x @ params["head"].astype(F32).T


def score(params, config: dict, tokens, rows, picks, quant=None,
          block: int = 2, leave_out=()):
    """tokens [B, L] int32 (tail-padded; padding never reaches an
    earlier row: attention is causal and the recurrence runs forward),
    rows [B, R] the positions whose logits are wanted, picks [B, R]
    token ids. Returns numpy (best, picked, argmax), each [B, R]."""
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


def served_gaps(params, config, requests, length, out_max, quant=None,
                leave_out=()):
    """For each served token, how far its float32-reference logit lies
    below the reference's best at that position. With `quant`, a
    CONTROL's reading instead: the gap of the token the lower precision
    puts first at each position of the same prompts and tokens.
    Returns the gaps of all served positions, flat."""
    tokens, rows, picks, mask = pack(requests, length, out_max)
    if quant is not None:
        _, _, picks = score(params, config, tokens, rows, picks, quant)
    best, picked, _ = score(params, config, tokens, rows, picks, None,
                            leave_out=leave_out)
    return (best - picked)[mask]
