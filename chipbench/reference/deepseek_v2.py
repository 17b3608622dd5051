"""The plain reference of the DeepSeek-V2 configurations (deepseek-ai
DeepSeek-V2, `model_type` deepseek_v2): the layer equations as a
float32 `jax.numpy` forward at matmul precision `highest`. Latent
attention is in the EXPANDED form (every head's keys and values
materialised from the latent rows, a group of heads at a time), the
softmax of a block of queries is materialised over every key up to the
block's end, the router scores every expert: no kernel, no cache, no
absorbed form, nothing of hpx_tpu.

u = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.

    h = x + MLA_l(RMSNorm_1(x));  y = h + FFN_l(RMSNorm_2(h))
    after the last layer RMSNorm, then the untied head.

MLA (every layer; H heads; t the token's position):
    c_q = RMSNorm(W_dq u)                      q_lora_rank, learned scale
    [q^C_i ; q^R_i] = W_uq,i c_q               qk_nope_head_dim + qk_rope_head_dim
    [c_raw ; k_raw] = W_dkv u                  kv_lora_rank + qk_rope_head_dim
    c = RMSNorm(c_raw)   (learned scale);  k^R = RoPE(k_raw, t): ONE
        rotary key shared by the heads, no norm on it
    q^R_i <- RoPE(q^R_i, t)
    k_i = [W_uk,i c ; k^R];  v_i = W_uv,i c
    p = softmax_{j<=t}(q_i . k_i,j * s) in float32
    s = (nope + rope dims)^-1/2 * m^2,  m = 0.1 * mscale_all_dim *
        ln(factor) + 1  (Hugging Face DeepseekV2Attention: softmax_scale
        * mscale * mscale)
    o = W_o concat_i(sum_j p_j v_i,j)
RoPE: rotate-half over the qk_rope_head_dim dims as stored; inverse
frequencies YaRN (`rope_scaling`: factor, original_max_position_
embeddings, beta_fast, beta_slow) blended between theta^(-2i/d) and
that / factor by the linear ramp between the two correction dims;
cos/sin times yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
mscale_all_dim) (1.0 where the two are equal).

FFN: layers below `first_k_dense_replace` W_2(SiLU(W_1 h) * W_3 h);
the others
    g = softmax(W_g h) over the PUBLISHED `router_experts`, float32
    group k = experts k*E/n_group .. ; its score = its LARGEST g_e; the
        `topk_group` best groups stay (ties to the lower group), every
        other group's scores are set to 0
    the `num_experts_per_tok` largest remaining win, ties to the lower
        id; weights = those g_e, NOT renormalised (`norm_topk_prob`
        false), times `routed_scaling_factor`
    y = Shared(h) + sum_chosen w_e E_e(h); experts SiLU-gated; Shared
        ONE SiLU-gated MLP of width n_shared_experts * moe_intermediate_size
The configuration holds a SHARE of the experts (`experts_held` = [lo,
hi), one routing group): the router scores all of them through all the
groups, the held ones compute, what the absent ones would add is left
out (no stand-in). Each held expert runs over every token under its
weight (zero where it was not chosen): dense, static shapes.

DEPARTURES from the published implementation, each in the
configuration file under `assumed`: the rotary dims are rotated as
stored (the published weights store them interleaved and de-interleave
before rotate-half; under seeded random weights that permutation is
absorbed in W_uq / W_dkv); a share of the experts and a slice of the
vocabulary (`reduced`).

`quant` is a CONTROL: "int8" = the same forward as a bfloat16 model
served in int8 (every weight matrix int8 per output channel, every
matmul input int8 per token, the cached latent row int8 per token,
everything between in bfloat16; router scores and every softmax stay
float32); "nope" = the float32 forward with the rotary dims LEFT
UNROTATED (a program that skipped the rotation).

Weights come in the program's layout (drivers/serving_latent.py
`make_params`): {"emb", "head", "ln_f", "layers": [{"ln1", "ln2",
"mla": {"wdq" [D,rq], "qnorm" [rq], "wuq" [rq,H,dn+dr], "wdkv"
[D,r+dr], "kvnorm" [r], "wuk" [r,H,dn], "wuv" [r,H,dv], "wo"
[H,dv,D]}, and "w1", "w3" [D,f], "w2" [f,D] or "moe": {"wg" [D,E],
"w1", "w3" [held,D,f], "w2" [held,f,D], "shared"}}]}.

`leave_out` (tests only) drops one piece of the mathematics:
"rotation", "mscale", "q_norm", "kv_norm", "group_limit", "scaling"
(the x routed_scaling_factor), "shared".
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.kimi_linear import (   # the controls' arithmetic
    _head, _mm, _q8, _r, _rms, _swiglu)
from chipbench.reference.laguna import pack     # the requests' frame

F32 = jnp.float32
HEADS_A_GROUP = 16      # heads whose keys and values exist at one time
QUERY_BLOCK = 1024      # queries whose scores exist at one time
FRAME_STEP = 8192       # a request's frame: its length rounded up to this


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_tables(config: dict, n: int):
    """(cos, sin) [n, rope dims / 2] float32 of positions 0..n-1, in
    float64 until the last step."""
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    sc = config.get("rope_scaling") or {}
    freq = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv, mult = 1.0 / freq, 1.0
    if sc:
        factor, orig = float(sc["factor"]), int(
            sc["original_max_position_embeddings"])

        def corr(n_rot):
            return d * math.log(orig / (n_rot * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(corr(float(sc["beta_fast"]))), 0)
        high = min(math.ceil(corr(float(sc["beta_slow"]))), d - 1)
        ramp = np.clip((np.arange(d // 2) - low)
                       / ((high - low) or 0.001), 0, 1)
        inv = 1.0 / (factor * freq) * ramp + 1.0 / freq * (1 - ramp)
        mult = yarn_mscale(factor, float(sc["mscale"])) \
            / yarn_mscale(factor, float(sc["mscale_all_dim"]))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * mult, F32),
            jnp.asarray(np.sin(ang) * mult, F32))


def softmax_scale(config: dict) -> float:
    s = (int(config["qk_nope_head_dim"])
         + int(config["qk_rope_head_dim"])) ** -0.5
    sc = config.get("rope_scaling") or {}
    if sc.get("mscale_all_dim"):
        s *= yarn_mscale(float(sc["factor"]),
                         float(sc["mscale_all_dim"])) ** 2
    return s


def _rot(x, cos, sin):
    """rotate-half of x [..., L, (H,) d] by tables [L, d/2]."""
    half = x.shape[-1] // 2
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "quant", "rank", "nope", "scale", "leave_out"))
def _mla(x, lp, cos, sin, *, eps, quant, rank, nope, scale, leave_out):
    """x + MLA(RMSNorm_1(x)), expanded: HEADS_A_GROUP heads' keys and
    values at a time, QUERY_BLOCK queries against every key up to
    their block's end."""
    with jax.default_matmul_precision("highest"):
        m = lp["mla"]
        b, n, _ = x.shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        cq = _mm(u, m["wdq"], quant)
        if "q_norm" not in leave_out:
            cq = _r(_rms(cq, m["qnorm"], eps), quant)
        ckr = _mm(u, m["wdkv"], quant)
        c = ckr[..., :rank]
        if "kv_norm" not in leave_out:
            c = _r(_rms(c, m["kvnorm"], eps), quant)
        kr = ckr[..., rank:]
        rotate = "rotation" not in leave_out
        if rotate:
            kr = _rot(kr, cos, sin)
        if quant == "int8":                 # the cached row, as served
            c, kr = _q8(c, -1), _q8(kr, -1)
        h = m["wuq"].shape[1]
        hg = min(HEADS_A_GROUP, h)
        qb = min(QUERY_BLOCK, n)

        def heads(out, ws):
            wuq, wuk, wuv, wo = ws                        # hg heads' own
            q = _mm(cq, wuq, quant)                       # [B, L, hg, dn+dr]
            if rotate:
                q = jnp.concatenate(
                    [q[..., :nope], _rot(q[..., nope:], cos, sin)], -1)
            k = jnp.concatenate(
                [_mm(c, wuk, quant),
                 jnp.broadcast_to(kr[:, :, None, :],
                                  (b, n, hg, kr.shape[-1]))], -1)
            v = _mm(c, wuv, quant)                        # [B, L, hg, dv]
            att = []
            for q0 in range(0, n, qb):
                end = min(q0 + qb, n)
                sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:end],
                                k[:, :end]) * scale
                seen = jnp.arange(end)[None, :] \
                    <= jnp.arange(q0, end)[:, None]
                p = _r(jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1),
                       quant)
                att.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :end]))
            att = _r(jnp.concatenate(att, 1), quant)      # [B, L, hg, dv]
            return out + _mm(att.reshape(b, n, -1),
                             wo.reshape(-1, wo.shape[-1]), quant), None

        def grouped(w, axis):       # [.., H, ..] -> [H / hg, .., hg, ..]
            w = w.reshape(w.shape[:axis] + (h // hg, hg)
                          + w.shape[axis + 1:])
            return jnp.moveaxis(w, axis, 0)
        out, _ = jax.lax.scan(
            heads, jnp.zeros_like(x),
            (grouped(m["wuq"], 1), grouped(m["wuk"], 1),
             grouped(m["wuv"], 1), grouped(m["wo"], 0)))
        return _r(x + _r(out, quant), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, lp, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        u = _r(_rms(x, lp["ln2"], eps), quant)
        return _r(x + _swiglu(u, lp["w1"], lp["w3"], lp["w2"], quant), quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def router_input(x, lp, *, eps):
    """RMSNorm_2(x) [B, L, D]: what a sparse layer's router reads."""
    return _rms(x, lp["ln2"], eps)


def choose(g, *, top_k: int, n_group: int, topk_group: int):
    """The experts a token chooses from its scores g [..., E]:
    (idx [..., top_k], their scores), group-limited."""
    sel = g
    if n_group > 1:
        per = g.shape[-1] // n_group
        best = g.reshape(g.shape[:-1] + (n_group, per)).max(-1)
        _, gi = jax.lax.top_k(best, topk_group)
        kept = (gi[..., None] == jnp.arange(n_group)).any(-2)
        sel = jnp.where(jnp.repeat(kept, per, axis=-1), g, 0.0)
    _, idx = jax.lax.top_k(sel, top_k)
    return idx, jnp.take_along_axis(g, idx, -1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "quant", "top_k", "n_group", "topk_group", "scale", "lo",
    "leave_out"))
def _sparse_ffn(x, lp, *, eps, quant, top_k, n_group, topk_group, scale,
                lo, leave_out):
    """x + sum over the HELD experts of w_e E_e(u) + Shared(u): the
    router over its whole width and all its groups, each held expert
    over every token under its weight (zero where not chosen)."""
    with jax.default_matmul_precision("highest"):
        mp = lp["moe"]
        u = _r(_rms(x, lp["ln2"], eps), quant)
        g = jax.nn.softmax(jnp.tensordot(u, mp["wg"].astype(F32), axes=1),
                           axis=-1)
        idx, w = choose(
            g, top_k=top_k, topk_group=topk_group,
            n_group=1 if "group_limit" in leave_out else n_group)
        if "scaling" not in leave_out:
            w = w * scale
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


def forward(params, config: dict, tokens, quant=None, leave_out=(),
            visit=None):
    """Hidden states [B, L, d] after the last layer (before the final
    norm) of tokens [B, L]. `visit(lp, u) -> lp`, where given, is
    called at each sparse layer with its parameters and its router's
    INPUT of these tokens, and returns the parameters the layer runs
    with (drivers/serving_latent.py balances the router's weights so)."""
    eps = float(config["rms_norm_eps"])
    if quant == "nope":         # a float32 forward, rotation left out
        quant, leave_out = None, tuple(leave_out) + ("rotation",)
    leave_out = tuple(sorted(set(leave_out)))
    tokens = jnp.asarray(tokens)
    cos, sin = rotary_tables(config, tokens.shape[1])
    scale = softmax_scale(config)
    if "mscale" in leave_out:
        scale = (int(config["qk_nope_head_dim"])
                 + int(config["qk_rope_head_dim"])) ** -0.5
    x = params["emb"][tokens].astype(F32)
    for lp in params["layers"]:
        x = _mla(x, lp, cos, sin, eps=eps, quant=quant,
                 rank=int(config["kv_lora_rank"]),
                 nope=int(config["qk_nope_head_dim"]), scale=scale,
                 leave_out=leave_out)
        if "moe" in lp:
            if visit is not None:
                lp = visit(lp, router_input(x, lp, eps=eps))
            x = _sparse_ffn(
                x, lp, eps=eps, quant=quant,
                top_k=int(config["num_experts_per_tok"]),
                n_group=int(config["n_group"]),
                topk_group=int(config["topk_group"]),
                scale=float(config["routed_scaling_factor"]),
                lo=int(config["experts_held"][0]), leave_out=leave_out)
        else:
            x = _dense_ffn(x, lp, eps=eps, quant=quant)
    return x


def logits(params, config: dict, tokens, quant=None, leave_out=()):
    """Every position's logits [B, L, V] (tests at a small size)."""
    x = forward(params, config, tokens, quant, leave_out)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"], float(config["rms_norm_eps"]))
        return x @ params["head"].astype(F32).T


# the float32 forward's hidden rows of the LAST requests scored: a
# control scores the same tokens again with its own picks, and need not
# pay the forward a second time
_rows_kept: dict = {}


def score(params, config: dict, tokens, rows, picks, quant=None,
          leave_out=()):
    """tokens [B, L] int32 (tail-padded; padding never reaches an
    earlier row: attention is causal), rows [B, R] the positions whose
    logits are wanted, picks [B, R] token ids. One request at a time,
    in a frame of its own length rounded up to FRAME_STEP (attention's
    cost is quadratic in it). Returns numpy (best, picked, argmax),
    each [B, R]."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    picks = np.asarray(picks, np.int32)
    eps = float(config["rms_norm_eps"])
    outs = []
    for i in range(tokens.shape[0]):
        n = int(rows[i].max()) + 1
        n = min(tokens.shape[1], n + -n % FRAME_STEP)
        key = (np.asarray(params["ln_f"][:8], np.float32).tobytes(),
               tokens[i, :n].tobytes(), rows[i].tobytes(),
               quant, tuple(sorted(leave_out)))
        x = _rows_kept.get(key)
        if x is None:
            x = forward(params, config, tokens[i:i + 1, :n], quant,
                        leave_out)
            x = jnp.take_along_axis(x, jnp.asarray(rows[i:i + 1])[..., None],
                                    axis=1)
            if quant is None and not leave_out:
                if len(_rows_kept) >= 16:
                    _rows_kept.clear()
                _rows_kept[key] = x
        outs.append(jax.device_get(_head(
            x, params["ln_f"], params["head"],
            jnp.arange(rows.shape[1])[None, :],
            jnp.asarray(picks[i:i + 1]), eps=eps,
            quant=None if quant == "nope" else quant)))
    return tuple(np.concatenate([o[j] for o in outs]) for j in range(3))


def served_gaps(params, config, requests, length, out_max, quant=None,
                leave_out=()):
    """For each served token, how far its float32-reference logit lies
    below the reference's best at that position. With `quant`, a
    CONTROL's reading instead: the gap of the token the control puts
    first at each position of the same prompts and tokens. Returns the
    gaps of all served positions, flat."""
    tokens, rows, picks, mask = pack(requests, length, out_max)
    if quant is not None:
        _, _, picks = score(params, config, tokens, rows, picks, quant)
    best, picked, _ = score(params, config, tokens, rows, picks, None,
                            leave_out=leave_out)
    return (best - picked)[mask]
