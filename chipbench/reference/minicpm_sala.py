"""The plain reference of the MiniCPM-SALA configurations (openbmb
MiniCPM-SALA, `model_type` minicpm_sala): the layer equations as a
float32 `jax.numpy` forward at matmul precision `highest`. The sparse
layers materialise every query's scores over the compressed keys, its
own choice of blocks as a mask over ALL rows, and the softmax; the
linear layers are a token-by-token scan. No kernel, no cache, no
chunkwise form, no index, nothing of hpx_tpu.

u = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w; no bias anywhere; d =
128 a head.

    x_0 = emb[token] * scale_emb
    h = x + c Mixer_l(RMSNorm_1(x));  y = h + c FFN_l(RMSNorm_2(h)),
        c = scale_depth / sqrt(PUBLISHED num_hidden_layers)
    logits = W_head (RMSNorm(x_L) * dim_model_base / hidden_size)
    FFN: W_2(SiLU(W_1 u) * W_3 u)

Sparse layer (`mixer_types` "minicpm4"; InfLLM-v2, arXiv:2506.07900,
with the released `sparse_config`: kernel_size 32, kernel_stride 16,
block_size 64, topk 64, init_blocks 1, window_size 2048, dense_len
8192), 32 query / 2 kv heads:
    q = RMSNorm_d(W_q u), k = RMSNorm_d(W_k u), v = W_v u; NO rotation
    kbar_j = mean(k[16 j : 16 j + 32])       for every complete window
    for query t and kv group g (its 16 heads):
      s_j = sum_{h in g} softmax_j(q_h . kbar_j / sqrt(d)) over the
            windows that end at or before t (16 j + 31 <= t)
      block b (rows 64 b .. 64 b + 63) scores max s_j over the windows
            that overlap it (4 b - 1 <= j <= 4 b + 3)
      chosen: block 0, the blocks that hold rows t - 2047 .. t, and the
            best others until 64 in all; if t + 1 <= 8192 every block
    o_h = softmax over the chosen blocks' rows r <= t of
          (q_h . k_r / sqrt(d)) . v;   y = W_o (sigmoid(W_g u) * o)

Linear layer ("lightning-attn"; Lightning Attention-2,
arXiv:2401.04658), 32 heads:
    q, k = RoPE_theta(RMSNorm_d(W u)) (rotate-half over the whole
    head), v = W_v u
    S_t = lam_h S_{t-1} + k_t^T v_t;  o_t = (q_t / sqrt(d)) S_t
    lam_h = exp(-s_h f_l), s_h = 2^(-8 (h + 1) / 32),
    f_l = 1 - l / 31 + 1e-5, l the PUBLISHED layer index
    y = W_o (sigmoid(W_g u) * RMSNorm_all-heads(o_t))

The ASSUMED pieces (the catalog's config gives none of `sparse_config`,
the decay's form, the gates' and the norms' shapes) are listed with
their sources in the configuration file under `assumed`.

`quant` is a CONTROL: "int8" = the same forward as a bfloat16 model
served in int8 (every weight matrix int8 per output channel, every
matmul input int8 per token, K, V and the compressed keys int8 per row,
everything between in bfloat16; scores, softmaxes, decays and the state
stay float32); "state_bf16" = the same forward as a bfloat16 model that
carries the linear state in bfloat16 (rounded after every token);
"window_only" = the float32 forward whose sparse layers read the forced
blocks alone (no learned choice).

Weights come in the program's layout (drivers/serving_sparse.py
`make_params`): {"emb", "head", "ln_f", "layers": [{"ln1", "ln2",
"sparse": {"wq" [D,H d], "wkv" [D,2 nkv d] (k heads, then v heads),
"qnorm", "knorm" [d], "wg" [D,H d], "wo" [H d,D]} or "lightning":
{"wq", "wk", "wv" [D,H d], "qnorm", "knorm" [d], "onorm" [H d], "wg",
"wo"}, "w1", "w3" [D,f], "w2" [f,D]}]}: matrices, the heads side
by side in their columns.

`leave_out` (tests only) drops one piece of the mathematics: "decay",
"qk_norm", "sparse_gate", "lightning_gate", "out_norm", "sparse_rope"
(ROTATES the sparse layer's q and k), "lightning_rope", "init_block",
"local", "topk_half", "scale_emb", "scale_depth", "scale_logit".
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.laguna import pack     # the requests' frame

F32 = jnp.float32
QUERY_ROWS = 128        # queries scored at once in a sparse layer
FFN_ROWS = 4096         # rows an FFN runs at once


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in float32
    (`reduce_precision`: a pair of converts is elided on the chip)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _lowp(quant) -> bool:
    """The controls that compute as a bfloat16 model."""
    return quant in ("int8", "state_bf16")


def _r(x, quant):
    return _bf16(x) if _lowp(quant) else x


def _mm(x, w, quant):
    """x [..., d] @ w [d, ...]."""
    w = w.astype(F32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return _r(jnp.tensordot(x, w, axes=1), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta: float):
    """Rotate-half over the whole head. x [B, L, H, d]; pos [L]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def numbers(config: dict) -> dict:
    """The configuration's numbers as the layers take them."""
    sc = config["sparse_config"]
    depth = int(config["source_values"]["num_hidden_layers"])
    return {"eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "c": float(config["scale_depth"]) / math.sqrt(depth),
            "emb": float(config["scale_emb"]),
            "logit": float(config["dim_model_base"])
            / float(config["hidden_size"]),
            "depth": depth,
            "published": tuple(int(i) for i in config["published_layers"]),
            "sparse": (int(sc["kernel_size"]), int(sc["kernel_stride"]),
                       int(sc["block_size"]), int(sc["topk"]),
                       int(sc["init_blocks"]), int(sc["window_size"]),
                       int(sc["dense_len"]))}


def _qk_norm(q, k, m, eps, leave_out):
    if "qk_norm" in leave_out:
        return q, k
    return _rms(q, m["qnorm"], eps), _rms(k, m["knorm"], eps)


def _heads(x, d: int):
    """[B, L, n d] -> [B, L, n, d]."""
    return x.reshape(x.shape[:2] + (-1, d))


def _gate(o, u, m, quant, skip: bool):
    """W_o (sigmoid(W_g u) * o): o [B, L, H d]."""
    if not skip:
        o = o * jax.nn.sigmoid(_mm(u, m["wg"], quant))
    return _mm(_r(o, quant), m["wo"], quant)


def block_scores(q, kbar, t, sparse, n_blocks: int, leave_out=(),
                 quant=None):
    """One kv group's block scores for queries at positions t [Q]: q
    [G, Q, d] float32 (normed), kbar [J, d] the compressed keys of the
    whole sequence -> [Q, n_blocks]; +inf on a forced block, -inf on a
    block the query cannot see or no complete window overlaps."""
    kernel, stride, block, _, init, local, _ = sparse
    r = block // stride
    j = jnp.arange(kbar.shape[0])
    done = (j * stride + kernel - 1)[None, :] <= t[:, None]     # [Q, J]
    s = jnp.einsum("gqd,jd->gqj", q, kbar) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(done[None], s, -jnp.inf), axis=-1)
    sw = jnp.where(done, jnp.sum(jnp.where(done[None], p, 0.0), 0),
                   -jnp.inf)                                    # [Q, J]
    # block c takes windows r c - 1 .. r c + r - 1
    lo = jnp.arange(n_blocks)[:, None] * r - 1 + jnp.arange(r + 1)
    inside = jnp.logical_and(lo >= 0, lo < kbar.shape[0])
    take = jnp.where(inside[None], sw[:, jnp.clip(lo, 0, kbar.shape[0] - 1)],
                     -jnp.inf)                                  # [Q, C, r+1]
    score = jnp.max(take, axis=-1)
    c = jnp.arange(n_blocks)[None, :]
    forced = jnp.zeros_like(score, bool)
    if "init_block" not in leave_out:
        forced = forced | (c < init)
    if "local" not in leave_out:
        forced = forced | (c >= (jnp.maximum(t - local + 1, 0)
                                 // block)[:, None])
    forced = forced | (c == (t // block)[:, None])      # its own block
    if quant == "window_only":
        score = jnp.full_like(score, -jnp.inf)
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(c <= (t // block)[:, None], score, -jnp.inf)


def choose(score, t, sparse, leave_out=()):
    """[Q, n_blocks] bool: the blocks each query reads."""
    _, _, _, topk, _, _, dense_len = sparse
    if "topk_half" in leave_out:
        topk //= 2
    k = min(topk, score.shape[-1])
    # a block's rank: how many score higher (a tie goes to the lower
    # block id)
    rank = jnp.sum((score[:, None, :] > score[:, :, None])
                   | ((score[:, None, :] == score[:, :, None])
                      & (jnp.arange(score.shape[-1])[None, None, :]
                         < jnp.arange(score.shape[-1])[None, :, None])),
                   axis=-1)
    chosen = (rank < k) & (score > -jnp.inf)
    return jnp.where((t + 1 <= dense_len)[:, None], score > -jnp.inf,
                     chosen)


@functools.partial(jax.jit, static_argnames=("nums", "quant", "leave_out"))
def _sparse(x, lp, *, nums, quant, leave_out):
    """x + c Sparse(RMSNorm_1(x)): one (sequence, kv group) and
    QUERY_ROWS queries at a time, every row's mask materialised."""
    with jax.default_matmul_precision("highest"):
        nm = dict(nums)
        eps, sparse = nm["eps"], nm["sparse"]
        kernel, stride, block = sparse[:3]
        m = lp["sparse"]
        b, n, _ = x.shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        d = m["qnorm"].shape[0]
        q = _heads(_mm(u, m["wq"], quant), d)           # [B, L, H, d]
        kv = _heads(_mm(u, m["wkv"], quant), d)
        k, v = kv[:, :, :kv.shape[2] // 2], kv[:, :, kv.shape[2] // 2:]
        q, k = _qk_norm(q, k, m, eps, leave_out)
        if "sparse_rope" in leave_out:
            q, k = (_rope(a, jnp.arange(n), nm["theta"]) for a in (q, k))
        q, k = _r(q, quant), _r(k, quant)
        if quant == "int8":                 # the cached rows, as served
            k, v = _q8(k, -1), _q8(v, -1)
        h, nkv = q.shape[2], k.shape[2]
        g = h // nkv
        n_blocks = n // block
        nq = min(QUERY_ROWS, n)

        def group(args):
            qg, kg, vg = args               # [G, L, d], [L, d], [L, d]
            mean = kg.reshape(n // stride, stride, d).mean(1)
            kbar = 0.5 * (mean[:-1] + mean[1:])
            if quant == "int8":
                kbar = _q8(kbar, -1)

            def rows(args):
                qb, t = args                # [G, nq, d], [nq]
                score = block_scores(qb, kbar, t, sparse, n_blocks,
                                     leave_out, quant)
                mask = jnp.repeat(choose(score, t, sparse, leave_out),
                                  block, axis=1)            # [nq, L]
                mask = mask & (jnp.arange(n)[None, :] <= t[:, None])
                sc = jnp.einsum("gqd,kd->gqk", qb, kg) / math.sqrt(d)
                p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
                return jnp.einsum("gqk,kd->gqd", _r(p, quant), vg)
            qs = jnp.moveaxis(qg.reshape(g, n // nq, nq, d), 1, 0)
            ts = jnp.arange(n).reshape(n // nq, nq)
            o = jax.lax.map(rows, (qs, ts))             # [n/nq, G, nq, d]
            return jnp.moveaxis(o, 0, 1).reshape(g, n, d)

        qg = jnp.moveaxis(q, 1, 2).reshape(b * nkv, g, n, d)
        kg = jnp.moveaxis(k, 1, 2).reshape(b * nkv, n, d)
        vg = jnp.moveaxis(v, 1, 2).reshape(b * nkv, n, d)
        o = jax.lax.map(group, (qg, kg, vg))            # [B nkv, G, L, d]
        o = jnp.moveaxis(o.reshape(b, h, n, d), 1, 2).reshape(b, n, h * d)
        y = _gate(_r(o, quant), u, m, quant, "sparse_gate" in leave_out)
        return _r(x + nm["c"] * y, quant)


def log_decay(heads: int, layer: int, depth: int):
    s = 2.0 ** (-8.0 * (np.arange(heads) + 1.0) / heads)
    return jnp.asarray(-s * (1.0 - layer / (depth - 1.0) + 1e-5), F32)


@functools.partial(jax.jit, static_argnames=("nums", "layer", "quant",
                                             "leave_out"))
def _lightning(x, lp, lengths=None, *, nums, layer, quant, leave_out):
    """(x + c Lightning(RMSNorm_1(x)), the state after the last token):
    the recurrence as a scan over the tokens. `lengths` [B]: each
    sequence's real tokens; the padding behind them leaves its state as
    it is (k = 0, no decay)."""
    with jax.default_matmul_precision("highest"):
        nm = dict(nums)
        eps = nm["eps"]
        m = lp["lightning"]
        b, n, _ = x.shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        d = m["qnorm"].shape[0]
        q, k, v = (_heads(_mm(u, m[n], quant), d)
                   for n in ("wq", "wk", "wv"))
        h = q.shape[2]
        q, k = _qk_norm(q, k, m, eps, leave_out)
        if "lightning_rope" not in leave_out:
            q, k = (_rope(a, jnp.arange(n), nm["theta"]) for a in (q, k))
        q, k = _r(q, quant), _r(k, quant)
        q = q / math.sqrt(d)
        g = log_decay(h, layer, nm["depth"])
        if "decay" in leave_out:
            g = jnp.zeros_like(g)
        gt = jnp.broadcast_to(g, (b, n, h))
        if lengths is not None:
            real = jnp.arange(n)[None, :] < lengths[:, None]
            gt = jnp.where(real[..., None], gt, 0.0)
            k = jnp.where(real[..., None, None], k, 0.0)

        def step(s, t):
            qt, kt, vt, g_t = t
            s = s * jnp.exp(g_t)[..., None, None] \
                + kt[..., None] * vt[..., None, :]
            if quant == "state_bf16":
                s = _bf16(s)
            return s, jnp.sum(s * qt[..., None], axis=-2)
        tm = lambda a: jnp.moveaxis(a, 1, 0)              # noqa: E731
        last, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), F32),
                               (tm(q), tm(k), tm(v), tm(gt)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, n, h * d)    # [B, L, H d]
        if "out_norm" not in leave_out:
            o = _rms(o, m["onorm"], eps)
        y = _gate(o, u, m, quant, "lightning_gate" in leave_out)
        return _r(x + nm["c"] * y, quant), last


@functools.partial(jax.jit, static_argnames=("nums", "quant"))
def _ffn(x, lp, *, nums, quant):
    with jax.default_matmul_precision("highest"):
        nm = dict(nums)
        b, n, dm = x.shape
        rows = min(FFN_ROWS, n)

        def some(xr):
            u = _r(_rms(xr, lp["ln2"], nm["eps"]), quant)
            hid = _r(jax.nn.silu(_mm(u, lp["w1"], quant))
                     * _mm(u, lp["w3"], quant), quant)
            return _r(xr + nm["c"] * _mm(hid, lp["w2"], quant), quant)
        pad = -n % rows
        xr = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        out = jax.lax.map(some, jnp.moveaxis(
            xr.reshape(b, (n + pad) // rows, rows, dm), 1, 0))
        return jnp.moveaxis(out, 0, 1).reshape(b, n + pad, dm)[:, :n]


@functools.partial(jax.jit, static_argnames=("nums", "quant"))
def _head(x, ln_f, head, rows, picks, *, nums, quant):
    """Logits of the rows asked for: their best value, the value of the
    picked token, and the token the forward itself puts first."""
    with jax.default_matmul_precision("highest"):
        nm = dict(nums)
        x = jnp.take_along_axis(x, rows[..., None], axis=1)
        x = _r(_rms(x, ln_f, nm["eps"]) * nm["logit"], quant)
        logits = _mm(x, head.astype(F32).T, quant)
        best = logits.max(-1)
        picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
        return best, picked, jnp.argmax(logits, -1)


def _nums(config, leave_out):
    nm = numbers(config)
    if "scale_emb" in leave_out:
        nm["emb"] = 1.0
    if "scale_depth" in leave_out:
        nm["c"] = 1.0
    if "scale_logit" in leave_out:
        nm["logit"] = 1.0
    return nm, tuple(sorted(nm.items()))


def forward(params, config: dict, tokens, quant=None, leave_out=(),
            lengths=None, upto=None):
    """Hidden states [B, L, d] after the last layer (before the final
    norm) of tokens [B, L]; with `upto`, (hidden states entering layer
    `upto`, that layer's state after `lengths` tokens) of a linear
    layer `upto`."""
    leave_out = tuple(sorted(leave_out))
    nm, nums = _nums(config, leave_out)
    x = _r(params["emb"][jnp.asarray(tokens)].astype(F32) * nm["emb"], quant)
    for i, lp in enumerate(params["layers"]):
        if "sparse" in lp:
            x = _sparse(x, lp, nums=nums, quant=quant, leave_out=leave_out)
        else:
            y, last = _lightning(
                x, lp, None if lengths is None else jnp.asarray(lengths),
                nums=nums, layer=nm["published"][i], quant=quant,
                leave_out=leave_out)
            if upto == i:
                return x, last
            x = y
        x = _ffn(x, lp, nums=nums, quant=quant)
    return x


def first_state(params, config: dict, tokens, lengths, quant=None):
    """The recurrent state [B, H, d, d] of the model's FIRST linear
    layer after `lengths` [B] tokens of tokens [B, L] (the layers ahead
    of it run in full)."""
    li = next(i for i, lp in enumerate(params["layers"])
              if "lightning" in lp)
    return forward(params, config, tokens, quant, (), lengths, upto=li)[1]


def _frames(seqs, multiple: int):
    n = max(len(t) for t in seqs)
    n += -n % multiple
    tokens = np.zeros((len(seqs), n), np.int32)
    for i, t in enumerate(seqs):
        tokens[i, :len(t)] = t
    return tokens, np.asarray([len(t) for t in seqs], np.int32)


def state_errors(params, config, states, quant=None, block: int = 1):
    """How far recurrent states lie from the float32 reference's, as
    |S - S_ref|_F / |S_ref|_F a sequence. states: [(token ids consumed,
    state [H, d, d] of the first linear layer)], the served program's;
    with `quant`, a CONTROL's own state of the same tokens instead."""
    tokens, lengths = _frames([t for t, _ in states], 1024)

    def run(q):
        return np.concatenate([np.asarray(first_state(
            params, config, tokens[i:i + block], lengths[i:i + block], q))
            for i in range(0, len(states), block)])
    want = run(None)
    got = np.stack([s for _, s in states]) if quant is None else run(quant)
    err = np.sqrt(((got - want) ** 2).sum((1, 2, 3)))
    return err / np.sqrt((want ** 2).sum((1, 2, 3)))


@functools.partial(jax.jit, static_argnames=("nums", "quant"))
def _first_scores(x, lp, at, *, nums, quant):
    """Block scores [B, nkv, n_blocks] of one sparse layer for the ONE
    query a sequence at position `at` [B]."""
    with jax.default_matmul_precision("highest"):
        nm = dict(nums)
        eps, sparse = nm["eps"], nm["sparse"]
        stride, block = sparse[1], sparse[2]
        m = lp["sparse"]
        b, n, _ = x.shape
        u = _r(_rms(x, lp["ln1"], eps), quant)
        ua = jnp.take_along_axis(u, at[:, None, None], axis=1)  # [B, 1, D]
        d = m["qnorm"].shape[0]
        q = _heads(_mm(ua, m["wq"], quant), d)
        kv = _heads(_mm(u, m["wkv"], quant), d)
        k = kv[:, :, :kv.shape[2] // 2]
        q, k = _qk_norm(q, k, m, eps, ())
        q, k = _r(q, quant), _r(k, quant)
        if quant == "int8":
            k = _q8(k, -1)
        h, nkv = q.shape[2], k.shape[2]
        mean = k.reshape(b, n // stride, stride, nkv, d).mean(2)
        kbar = 0.5 * (mean[:, :-1] + mean[:, 1:])           # [B, J, nkv, d]
        if quant == "int8":
            kbar = _q8(kbar, -1)
        qg = q[:, 0].reshape(b, nkv, h // nkv, 1, d)
        return jnp.stack([jnp.stack([
            block_scores(qg[i, j], kbar[i, :, j], at[i:i + 1], sparse,
                         n // block, (), quant)[0]
            for j in range(nkv)]) for i in range(b)])


def selection_numbers(params, config, picks, quant=None):
    """The program's choice of blocks held against the reference's own
    float32 scores. picks: [(token ids the query had behind it and was,
    ids [nkv, K] ascending, count [nkv])] of the model's FIRST sparse
    layer (`ContinuousServer.sparse_selection`). With `quant`, a
    CONTROL's own choice of the same queries in the program's place.
    Returns {"selection_missed": the share of the reference's chosen
    blocks the choice lacks, the worst query; "selection_score_gap":
    how far below the reference's LAST chosen score the worst chosen
    unforced block lies, as a share of that score (a near-tie flips on
    rounding; a block chosen for no reason lies far below)}."""
    nm, nums = _nums(config, ())
    sparse = nm["sparse"]
    li = next(i for i, lp in enumerate(params["layers"]) if "sparse" in lp)
    if li:
        raise NotImplementedError("the first sparse layer is not layer 0")
    tokens, lengths = _frames([t for t, _, _ in picks], 1024)
    at = jnp.asarray(lengths - 1)

    def scores(q):
        x = _r(params["emb"][jnp.asarray(tokens)].astype(F32) * nm["emb"], q)
        return np.asarray(_first_scores(x, params["layers"][0], at,
                                        nums=nums, quant=q))
    ref = scores(None)                                  # [B, nkv, C]
    own = None if quant is None else scores(quant)
    missed, gap = [], []
    for i, (_, ids, count) in enumerate(picks):
        t = np.asarray([lengths[i] - 1])
        for j in range(ref.shape[1]):
            s = ref[i, j]
            want = np.asarray(choose(jnp.asarray(s)[None], jnp.asarray(t),
                                     sparse))[0]
            if quant is None:
                got = np.zeros_like(want)
                got[np.asarray(ids[j][:count[j]])] = True
            else:
                got = np.asarray(choose(jnp.asarray(own[i, j])[None],
                                        jnp.asarray(t), sparse))[0]
            missed.append((want & ~got).sum() / want.sum())
            free = got & np.isfinite(s)
            last = s[want & np.isfinite(s)].min() if \
                (want & np.isfinite(s)).any() else 0.0
            gap.append(max(0.0, float((last - s[free]).max() / last))
                       if free.any() and last > 0 else 0.0)
            if (got & (s == -np.inf)).any():
                gap[-1] = 1.0       # a block the query cannot see
    return {"selection_missed": float(max(missed)),
            "selection_score_gap": float(max(gap))}


def logits(params, config: dict, tokens, quant=None, leave_out=()):
    """Every position's logits [B, L, V] (tests at a small size)."""
    x = forward(params, config, tokens, quant, leave_out)
    nm, _ = _nums(config, tuple(sorted(leave_out)))
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"], nm["eps"]) * nm["logit"]
        return x @ params["head"].astype(F32).T


def score(params, config: dict, tokens, rows, picks, quant=None,
          block: int = 1, leave_out=()):
    """tokens [B, L] int32 (tail-padded; padding never reaches an
    earlier row: attention is causal and the recurrence runs forward),
    rows [B, R] the positions whose logits are wanted, picks [B, R]
    token ids. Returns numpy (best, picked, argmax), each [B, R]."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    picks = np.asarray(picks, np.int32)
    _, nums = _nums(config, tuple(sorted(leave_out)))
    outs = []
    for b0 in range(0, tokens.shape[0], block):
        x = forward(params, config, tokens[b0:b0 + block], quant, leave_out)
        outs.append(jax.device_get(_head(
            x, params["ln_f"], params["head"],
            jnp.asarray(rows[b0:b0 + block]),
            jnp.asarray(picks[b0:b0 + block]), nums=nums, quant=quant)))
    return tuple(np.concatenate([o[i] for o in outs]) for i in range(3))


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
