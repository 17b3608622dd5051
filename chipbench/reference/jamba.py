"""The plain reference of the Jamba configurations (ai21labs
AI21-Jamba2-3B, `model_type` jamba): the layer equations as a float32
`jax.numpy` forward at matmul precision `highest`. The Mamba-1
recurrence is a token-by-token scan over the state in the PUBLISHED
orientation [d_inner, d_state], attention materialises its softmax: no
kernel, no cache, no chunking of the recurrence, nothing of hpx_tpu.

u = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w; no bias but the conv's
and dt's.

    h = x + Mixer_l(RMSNorm_1(x));  y = h + W_2(SiLU(W_1 v) * W_3 v),
    v = RMSNorm_2(h)   (`num_experts` 1: a plain MLP in every layer)
    after the last layer RMSNorm, then the TIED head (the embedding).

Layer l is attention where l % attn_layer_period == attn_layer_offset,
else Mamba (the `jamba` family's rule; the last layer is Mamba).

Mamba layer (C = mamba_expand * hidden_size channels, N = mamba_d_state,
R = mamba_dt_rank; the `jamba` modeling code's slow path):
    [u~; z] = W_in v
    u = SiLU(conv(u~) + b_conv)    depthwise causal over time,
        mamba_d_conv taps a channel, zeros before the first token
    [dt~; B; C] = W_x u;  each through its OWN RMSNorm (dt_layernorm,
        b_layernorm, c_layernorm: Jamba's addition to Mamba-1)
    dt = softplus(W_dt dt~ + b_dt)             in R^C, float32
    A = -exp(A_log)                            in R^{C x N}
    S[c, n] <- exp(dt[c] A[c, n]) S[c, n] + dt[c] B[n] u[c]
    y[c] = sum_n S[c, n] C[n] + D[c] u[c]
    out = W_out (y * SiLU(z))
No positions anywhere.

Attention layer: num_attention_heads query heads over
num_key_value_heads K/V heads of hidden_size / num_attention_heads,
scores / sqrt(head), causal softmax in float32, NO rotary embedding, no
bias, no window (`sliding_window` null).

Departures from the published code, each forced by what it is held
against: (1) weights come in the PROGRAM's layout
(drivers/serving_ssm.py `make_params`), so `A_log` arrives as [N, C]
and is transposed here, and `W_in`'s columns are u's then z's; (2) the
published code keeps the scan's state in float32 inside a call and the
CACHED state in the model's dtype; here the state is float32 throughout
(the configuration states a float32 state; a bfloat16 state is the
second control); (3) the scan walks a padded frame, rows past a
sequence's `lengths` leaving its state alone (dt = 0: decay 1, input
0).

`quant` is a CONTROL, a precision below the one the configuration
states: "int8" = the same forward as a bfloat16 model served in int8
(every weight matrix int8 per output channel, every matmul input int8
per token, K and V rows int8 per token, everything between in bfloat16;
dt, the decay, the three inner norms, the state and every softmax stay
float32); "state_bf16" = the same forward as a bfloat16 model that
carries the Mamba state in bfloat16 (rounded after every token), where
the configuration says float32.

Weights: {"emb" [V, D], "ln_f", "layers": [{"ln1", "ln2", "w1", "w3"
[D, f], "w2" [f, D], and "mamba": {"win" [D, 2C], "conv" [K, C],
"conv_b" [C], "wx" [C, R + 2N], "dt_norm" [R], "b_norm", "c_norm" [N],
"wdt" [R, C], "dt_bias" [C], "A_log" [N, C], "D" [C], "wo" [C, D]} or
"wq" [D, H, hd], "wkv" [2, D, Hkv, hd], "wo" [H, hd, D]}]}.

`first_state` / `state_errors`: the first layer's recurrent state of
given tokens, and how far served states lie from it: what holds the
program to the float32 the configuration states for the state, which
no served token can show. The distance is taken over the LONG MEMORIES,
the quarter of the channels whose dt bias is smallest: a state carried
in bfloat16 loses its precision where it accumulates longest (0.02-0.04
there against 0.005 on the fastest quarter), while the bfloat16
activations feeding a float32 state cost every channel alike (0.002-
0.005); over the whole state of slots picked at random the two lay a
factor of 1.3-3 apart on the chip, over the long memories of the slots
that have consumed the most tokens (the ones the driver checks) a
factor of six or more (PERF.md section 2).

`leave_out` (tests only) drops one piece of the mathematics:
"conv_bias", "conv", "D", "dt_norm", "b_norm", "c_norm", "dt_bias",
"softplus", "gate".
"""

from __future__ import annotations

import functools
import math

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
    """x rounded to bfloat16's 8 significant bits, kept in float32
    (`reduce_precision`: the chip's compiler elides a pair of
    converts)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _r(x, quant):
    """What lies between a control's matmuls is kept in bfloat16."""
    return _bf16(x) if quant else x


def _mm(x, w, quant, keep=False):
    """x [..., d] @ w [d, ...]: contraction over x's last and w's
    first. `keep`: a control leaves the product float32 (dt, B and C
    are float32 in a bfloat16 model too)."""
    w = w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    out = jnp.tensordot(x, w, axes=1)
    return out if keep else _r(out, quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "leave_out"))
def _mamba(x, lp, lengths=None, *, eps, quant, leave_out):
    """(x + Mamba(RMSNorm_1(x)), the state [B, C, N] after the last
    token): the recurrence as a scan over the tokens. `lengths` [B]:
    each sequence's real tokens; the padding behind them leaves its
    state as it is."""
    with jax.default_matmul_precision("highest"):
        m = lp["mamba"]
        b, n, _ = x.shape
        taps, c = m["conv"].shape
        ns = m["b_norm"].shape[0]
        r = m["wdt"].shape[0]
        v = _r(_rms(x, lp["ln1"], eps), quant)
        uz = _mm(v, m["win"], quant)
        pre, z = uz[..., :c], uz[..., c:]
        if "conv" in leave_out:
            act = pre
        else:
            full = jnp.pad(pre, ((0, 0), (taps - 1, 0), (0, 0)))
            cw = m["conv"].astype(F32)
            act = sum(full[:, j:j + n] * cw[j] for j in range(taps))
        if "conv_b" in m and "conv_bias" not in leave_out:
            act = act + m["conv_b"].astype(F32)
        u = _r(jax.nn.silu(act), quant)
        dbc = _mm(u, m["wx"], quant, keep=True)
        part = {"dt_norm": dbc[..., :r], "b_norm": dbc[..., r:r + ns],
                "c_norm": dbc[..., r + ns:]}
        dt_r, bm, cm = (
            p if k in leave_out else _rms(p, m[k], eps)
            for k, p in part.items())
        dt = _mm(_r(dt_r, quant), m["wdt"], quant, keep=True)
        if "dt_bias" not in leave_out:
            dt = dt + m["dt_bias"].astype(F32)
        if "softplus" not in leave_out:
            dt = jax.nn.softplus(dt)
        if lengths is not None:
            real = jnp.arange(n)[None, :] < lengths[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
        a = -jnp.exp(m["A_log"].astype(F32)).T            # [C, N]

        def step(s, t):
            ut, dtt, bt, ct = t           # [B, C], [B, C], [B, N], [B, N]
            s = jnp.exp(dtt[..., None] * a) * s \
                + (dtt * ut)[..., None] * bt[:, None, :]
            if quant == "state_bf16":
                s = _bf16(s)
            return s, jnp.sum(s * ct[:, None, :], axis=-1)
        tm = lambda t: jnp.moveaxis(t, 1, 0)              # noqa: E731
        last, y = jax.lax.scan(step, jnp.zeros((b, c, ns), F32),
                               (tm(u), tm(dt), tm(bm), tm(cm)))
        y = jnp.moveaxis(y, 0, 1)                         # [B, L, C]
        if "D" not in leave_out:
            y = y + m["D"].astype(F32) * u
        if "gate" not in leave_out:
            y = y * jax.nn.silu(z)
        return _r(x + _mm(_r(y, quant), m["wo"], quant), quant), last


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _attention(x, lp, *, eps, quant):
    """x + Attn(RMSNorm_1(x)), one (sequence, kv head) at a time; no
    rotation."""
    with jax.default_matmul_precision("highest"):
        b, n, _ = x.shape
        seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        v_in = _r(_rms(x, lp["ln1"], eps), quant)
        q = _mm(v_in, lp["wq"], quant)                    # [B, L, H, hd]
        wkv = lp["wkv"].astype(F32)
        k, v = _mm(v_in, wkv[0], quant), _mm(v_in, wkv[1], quant)
        if quant == "int8":                 # the cached rows, as served
            k, v = _q8(k, -1), _q8(v, -1)
        nq, nkv, hd = q.shape[2], k.shape[2], q.shape[3]
        qg = q.reshape(b, n, nkv, nq // nkv, hd)

        def one(args):
            qh, kh, vh = args              # [L, g, hd], [L, hd], [L, hd]
            sc = jnp.einsum("qgh,kh->gqk", qh, kh) / math.sqrt(hd)
            p = _r(jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1),
                   quant)
            return jnp.einsum("gqk,kh->qgh", p, vh)
        flat = lambda t: jnp.moveaxis(t, 2, 1).reshape(   # noqa: E731
            (b * nkv, n) + t.shape[3:])
        att = jax.lax.map(one, (flat(qg), flat(k), flat(v)))
        att = jnp.moveaxis(att.reshape(b, nkv, n, nq // nkv, hd), 1, 2)
        att = _r(att.reshape(b, n, nq * hd), quant)
        wo = lp["wo"].astype(F32)
        return _r(x + _mm(att, wo.reshape(-1, wo.shape[-1]), quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _ffn(x, lp, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        v = _r(_rms(x, lp["ln2"], eps), quant)
        h = _r(jax.nn.silu(_mm(v, lp["w1"], quant))
               * _mm(v, lp["w3"], quant), quant)
        return _r(x + _mm(h, lp["w2"], quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, emb, rows, picks, *, eps, quant):
    """Logits of the rows asked for, through the tied head: their best
    value, the value of the picked token, and the token the forward
    itself puts first."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, rows[..., None], axis=1)
        x = _r(_rms(x, ln_f, eps), quant)
        logits = _mm(x, emb.astype(F32).T, quant)
        best = logits.max(-1)
        picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
        return best, picked, jnp.argmax(logits, -1)


def forward(params, config: dict, tokens, quant=None, leave_out=()):
    """Hidden states [B, L, d] after the last layer (before the final
    norm) of tokens [B, L]."""
    eps = float(config["rms_norm_eps"])
    leave_out = tuple(sorted(leave_out))
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    for lp in params["layers"]:
        if "mamba" in lp:
            x, _ = _mamba(x, lp, eps=eps, quant=quant, leave_out=leave_out)
        else:
            x = _attention(x, lp, eps=eps, quant=quant)
        x = _ffn(x, lp, eps=eps, quant=quant)
    return x


def first_state(params, config: dict, tokens, lengths, quant=None):
    """The recurrent state [B, C, N] of the model's FIRST layer (a
    Mamba layer: it sees the embeddings alone, so no other layer runs)
    after `lengths` [B] tokens of tokens [B, L]."""
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    return _mamba(x, params["layers"][0], jnp.asarray(lengths),
                  eps=float(config["rms_norm_eps"]), quant=quant,
                  leave_out=())[1]


def state_errors(params, config, states, quant=None):
    """How far recurrent states lie from the float32 reference's, as
    |S - S_ref|_F / |S_ref|_F a sequence over the LONG MEMORIES (the
    quarter of the channels with the smallest dt bias). states:
    [(token ids consumed, state [C, N] of the first layer)], the served
    program's; with `quant`, a CONTROL's own state of the same tokens
    in their place."""
    n = max(len(t) for t, _ in states)
    n += -n % 256                       # few frames, few compiles
    tokens = np.zeros((len(states), n), np.int32)
    for i, (t, _) in enumerate(states):
        tokens[i, :len(t)] = t
    lengths = np.asarray([len(t) for t, _ in states], np.int32)
    want = np.asarray(first_state(params, config, tokens, lengths))
    got = (np.stack([s for _, s in states]) if quant is None else
           np.asarray(first_state(params, config, tokens, lengths, quant)))
    bias = np.asarray(params["layers"][0]["mamba"]["dt_bias"])
    keep = np.argsort(bias, kind="stable")[:len(bias) // 4]
    want, got = want[:, keep], got[:, keep]
    err = np.sqrt(((got - want) ** 2).sum((1, 2)))
    return err / np.sqrt((want ** 2).sum((1, 2)))


def logits(params, config: dict, tokens, quant=None, leave_out=()):
    """Every position's logits [B, L, V] (tests at a small size)."""
    x = forward(params, config, tokens, quant, leave_out)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"], float(config["rms_norm_eps"]))
        return x @ params["emb"].astype(F32).T


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
            x, params["ln_f"], params["emb"],
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
