"""Mixture-of-experts FFN with expert parallelism over a mesh axis.

The reference (HPX) has no ML layers; this is part of the mandated
model family (SURVEY.md §2.9), built GShard/Switch-style for TPU:
STATIC shapes throughout (top-k gating lowered to one-hot einsums with
a fixed per-expert capacity), experts sharded over a mesh axis, and
token exchange as ONE tiled `lax.all_to_all` each way — the same
collective substrate ulysses_attention rides (SURVEY.md §5.7).

Layout (inside shard_map; the "ep" axis may be a dedicated mesh axis or
an existing data axis — tokens must be sharded over it, expert weights
sharded over it, everything else replicated over it):

    tokens   x       [T, D]           (T = local tokens)
    gate     wg      [D, E]           replicated
    experts  w1      [E/P, D, F]      sharded over ep
             b1      [E/P, F]
             w2      [E/P, F, D]

    dispatch [T, E, C] one-hot   -> einsum -> [E, C, D]
    reshape  [P, E/P, C, D] -> all_to_all -> [E/P, P*C, D]
    expert FFN (batched einsum over the local experts)
    all_to_all back -> combine [T, E, C] -> [T, D]

Everything is differentiable (einsums + all_to_all transpose); dropped
tokens (over capacity) contribute zero output and zero gradient, the
standard Switch behavior. The auxiliary load-balance loss
(Switch §2.2: E * sum_e f_e * p_e) is returned for the trainer to add.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["MoeConfig", "init_moe_params", "moe_ffn", "moe_ffn_decode",
           "moe_ffn_serve", "moe_param_specs", "route"]


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int = 4
    top_k: int = 2                 # 1 = Switch, 2 = GShard default
    capacity_factor: float = 1.5   # C = ceil(T*k*cf / E)
    d_model: int = 64
    d_ff: int = 128                # per-expert hidden
    dtype: Any = jnp.float32
    # the expert and the router, as the model defines them. The
    # capacity path (moe_ffn, training) computes only the defaults; the
    # drop-free serving path (moe_ffn_serve) computes all of them.
    mlp: str = "gelu"              # | "swiglu": w2(silu(w1 x) * w3 x)
    router: str = "softmax"        # | "sigmoid" scores
    renorm: bool = False           # weights / their sum over the chosen
    scale: float = 1.0             # routed_scaling_factor
    shared_d_ff: int = 0           # one shared expert every token takes
    bias: bool = False             # a selection bias params["bias"] [E]
    # (lo, hi): the share of the experts these parameters hold (empty:
    # all). The router keeps its n_experts columns (moe_ffn_serve).
    held: Tuple[int, ...] = ()
    # device-limited routing: n_group equal groups of experts, of which
    # a token keeps its topk_group best (1 / 1: one flat top-k)
    n_group: int = 1
    topk_group: int = 1


# what a group-limited router's statistics vector carries behind the
# per-expert occupancy (moe_ffn_serve)
STATS_HERE = 2

# the leaves of `init_moe_params` with an expert axis: a row multiplies
# its top_k experts' part of them, and all of every other leaf
ROUTED_LEAVES = ("w1", "w2", "w3", "b1")


def init_moe_params(cfg: MoeConfig, key: jax.Array) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(d)
    wg = (jax.random.normal(k1, (d, e)) * s).astype(cfg.dtype)
    if cfg.held:
        e = cfg.held[1] - cfg.held[0]      # the router stays full width
    out = {
        "wg": wg,
        "w1": (jax.random.normal(k2, (e, d, f)) * s).astype(cfg.dtype),
        "w2": (jax.random.normal(k3, (e, f, d)) / math.sqrt(f)
               ).astype(cfg.dtype),
    }
    if cfg.bias:
        out["bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 2), (cfg.n_experts,), jnp.float32)
    if cfg.mlp != "swiglu":
        out["b1"] = jnp.zeros((e, f), cfg.dtype)
        return out
    k4, k5 = jax.random.split(jax.random.fold_in(key, 1))
    out["w3"] = (jax.random.normal(k4, (e, d, f)) * s).astype(cfg.dtype)
    if cfg.shared_d_ff:
        sf = cfg.shared_d_ff
        ka, kb, kc = jax.random.split(k5, 3)
        out["shared"] = {
            "w1": (jax.random.normal(ka, (d, sf)) * s).astype(cfg.dtype),
            "w3": (jax.random.normal(kb, (d, sf)) * s).astype(cfg.dtype),
            "w2": (jax.random.normal(kc, (sf, d)) / math.sqrt(sf)
                   ).astype(cfg.dtype)}
    return out


def moe_param_specs(axis: str = "ep",
                    tp_axis: Any = None) -> Dict[str, Any]:
    """PartitionSpecs: experts sharded over `axis`; with tp_axis set,
    each expert's d_ff additionally shards Megatron-style over it (the
    caller must psum the MoE output over tp_axis, exactly like the
    dense MLP's row-parallel close)."""
    from jax.sharding import PartitionSpec as P
    return {"wg": P(),
            "w1": P(axis, None, tp_axis),
            "b1": P(axis, tp_axis),
            "w2": P(axis, tp_axis, None)}


def _top_k_dispatch(gates: jax.Array, k: int, capacity: int,
                    token_mask: Any = None):
    """One-hot dispatch/combine tensors for top-k routing.

    gates [T, E] (softmax rows). Returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] weighted, aux_loss scalar). GShard order: the
    k-th choice claims capacity AFTER all earlier choices, so first
    choices are never bumped by second choices.

    token_mask [T] (optional; truthy = real token): masked rows claim
    NO capacity and get all-zero dispatch/combine rows — the decode
    path's padding rows route nowhere and contribute exact-zero output.

    Overflow is the paged-splice trash-row idiom: positions clip into a
    [.., C+1] one-hot whose last (trash) column is sliced off, so an
    over-capacity claim writes through the trash row and contributes
    exact-zero output and gradient.
    """
    t, e = gates.shape
    masks = []
    g = gates
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)
        m = jax.nn.one_hot(idx, e, dtype=gates.dtype)      # [T, E]
        if token_mask is not None:
            m = m * token_mask.astype(gates.dtype)[:, None]
        masks.append(m)
        g = g * (1.0 - m)                  # mask out the chosen expert

    # capacity positions: later choices rank after every earlier
    # choice's claims (GShard's cumsum-with-offset)
    dispatch = jnp.zeros((t, e, capacity), gates.dtype)
    combine = jnp.zeros((t, e, capacity), gates.dtype)
    used = jnp.zeros((1, e), gates.dtype)  # tokens claimed per expert
    for m in masks:
        pos = jnp.cumsum(m, axis=0) - m + used             # [T, E]
        slot = jnp.minimum(pos, capacity).astype(jnp.int32)
        oh = (jax.nn.one_hot(slot, capacity + 1, dtype=gates.dtype)
              * m[..., None])[..., :capacity]
        dispatch = dispatch + oh
        combine = combine + oh * jnp.sum(gates * m, axis=-1,
                                         keepdims=True)[..., None]
        used = used + jnp.sum(m, axis=0, keepdims=True)

    # Switch load-balance loss on FIRST choices: E * sum_e f_e * p_e
    f_e = jnp.mean(masks[0], axis=0)
    p_e = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return dispatch, combine, aux


def moe_ffn(x: jax.Array, params: Dict[str, Any], cfg: MoeConfig,
            axis: str = "", axis_size: int = 1,
            token_mask: Any = None,
            return_stats: bool = False) -> Tuple[jax.Array, ...]:
    """MoE feed-forward on a [T, D] token block.

    axis: mesh axis the experts are sharded over ("" = single shard —
    all experts local, no collective). Call from INSIDE shard_map when
    axis != "". token_mask [T]: rows with a falsy mask claim no
    capacity and produce exact-zero output (decode padding rows).
    Returns (out [T, D], aux_load_balance_loss); with return_stats
    also a psum-complete f32 stats vector [2 + E]:
    [claims routed, claims dropped over capacity,
    per-expert occupancy fraction of capacity].
    """
    t, d = x.shape
    e = cfg.n_experts
    p = max(axis_size, 1)
    if e % p:
        raise ValueError(f"n_experts ({e}) not divisible by ep={p}")
    if cfg.top_k > e:
        # an all-masked gate row would silently re-route to expert 0
        raise ValueError(f"top_k ({cfg.top_k}) > n_experts ({e})")
    if (cfg.mlp, cfg.router, cfg.renorm, cfg.shared_d_ff, cfg.bias,
            tuple(cfg.held), cfg.n_group) != (
                "gelu", "softmax", False, 0, False, (), 1) \
            or cfg.scale != 1.0:
        raise NotImplementedError(
            "models/moe.moe_ffn (GShard capacity dispatch: training, "
            "expert-parallel decode, a finite hpx.serving.moe."
            "capacity_factor) computes softmax-gated GELU experts only; "
            f"this model's experts ({cfg.mlp}, {cfg.router} router, "
            f"shared width {cfg.shared_d_ff}) run drop-free through "
            "moe_ffn_serve on one shard")
    e_loc = e // p
    capacity = max(1, math.ceil(t * cfg.top_k
                                * cfg.capacity_factor / e))

    xf = x.astype(jnp.float32)
    gates = jax.nn.softmax(xf @ params["wg"].astype(jnp.float32),
                           axis=-1)
    dispatch, combine, aux = _top_k_dispatch(
        gates, cfg.top_k, capacity, token_mask=token_mask)

    # [T, E, C] x [T, D] -> [E, C, D] in the compute dtype
    xd = x.astype(cfg.dtype)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype), xd)

    if p > 1:
        # exchange over the ep axis: [P, E/P, C, D] -> [E/P, P*C, D]
        ei = expert_in.reshape(p, e_loc, capacity, d)
        ei = jax.lax.all_to_all(ei, axis, split_axis=0, concat_axis=2,
                                tiled=True)
        ei = ei.reshape(e_loc, p * capacity, d)
    else:
        ei = expert_in                                 # [E, C, D]

    # expert weights may arrive int8-quantized for serving
    # (models/quant.QTensor); dequantization happens AT USE so XLA
    # fuses the convert into the matmul operand read
    from .quant import dequant
    h = jnp.einsum("ecd,edf->ecf", ei, dequant(params["w1"], cfg.dtype))
    h = jax.nn.gelu(h + params["b1"][:, None, :])
    eo = jnp.einsum("ecf,efd->ecd", h, dequant(params["w2"], cfg.dtype))

    if p > 1:
        eo = eo.reshape(1, e_loc, p * capacity, d)
        eo = jax.lax.all_to_all(eo, axis, split_axis=2, concat_axis=0,
                                tiled=True)            # [P, E/P, C, D]
        eo = eo.reshape(e, capacity, d)

    out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), eo)
    if not return_stats:
        return out.astype(x.dtype), aux
    # every gate row claims exactly top_k slots (argmax always picks
    # an expert), masked rows none — a static count
    claims = (jnp.float32(t * cfg.top_k) if token_mask is None
              else cfg.top_k * jnp.sum(token_mask.astype(jnp.float32)))
    kept = jnp.sum(dispatch)
    occ = jnp.sum(dispatch, axis=(0, 2)) / capacity        # [E]
    if axis and p > 1:
        kept = jax.lax.psum(kept, axis)
        claims = jax.lax.psum(claims, axis)
        # each rank claims up to `capacity` rows per expert, so the
        # global occupancy fraction is the mean of the rank fractions
        occ = jax.lax.psum(occ, axis) / p
    stats = jnp.concatenate(
        [jnp.stack([kept, claims - kept]), occ]).astype(jnp.float32)
    return out.astype(x.dtype), aux, stats


def moe_ffn_decode(x: jax.Array, params: Dict[str, Any],
                   cfg: MoeConfig, axis: str = "", axis_size: int = 1
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-parallel MoE FFN for DECODE shard_map bodies, where the
    token block x [T, D] arrives REPLICATED over the expert axis
    (decode shards batch over dp and heads over tp; experts ride the
    tp — or a dedicated ep — axis). Each rank takes an equal slice of
    the tokens (padded up to a multiple of axis_size; pad rows carry a
    zero token_mask, so they claim no capacity and contribute
    exact-zero output), routes it through :func:`moe_ffn`'s tiled
    all_to_all exchange, and the rank-local outputs close with a psum
    over the axis — the same row-parallel close as the dense MLP —
    yielding the replicated [T, D] block the decode body expects.

    Returns (out [T, D], aux, stats [2 + E]); stats are psum-complete
    (see moe_ffn). axis_size == 1 degenerates to the single-shard
    moe_ffn (no collective)."""
    t, d = x.shape
    p = max(axis_size, 1)
    if p == 1:
        return moe_ffn(x, params, cfg, return_stats=True)
    tl = -(-t // p)                        # ceil(T / P) tokens per rank
    xp = jnp.pad(x, ((0, p * tl - t), (0, 0)))
    start = jax.lax.axis_index(axis) * tl
    xl = jax.lax.dynamic_slice_in_dim(xp, start, tl, axis=0)
    mask = (start + jnp.arange(tl)) < t
    out_l, aux, stats = moe_ffn(xl, params, cfg, axis=axis,
                                axis_size=p, token_mask=mask,
                                return_stats=True)
    full = jnp.zeros((p * tl, d), out_l.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, out_l, start,
                                               axis=0)
    out = jax.lax.psum(full, axis)[:t]
    return out, jax.lax.pmean(aux, axis), stats


def route(x: jax.Array, wg: jax.Array, cfg: MoeConfig, bias=None,
          groups: bool = False):
    """Scores -> the top_k experts of every token and their weights:
    (idx [T, k] int32, w [T, k] f32). Scores in float32 (`softmax` over
    the experts, or element-wise `sigmoid`); the k largest win, ties to
    the lower expert id; `bias` [E] (a selection bias) is added for the
    CHOICE only, the weights are the chosen experts' plain scores; with
    `renorm` the weights are divided by their sum over the chosen k;
    then scaled.

    With `n_group` > 1 the choice is GROUP-LIMITED (device-limited
    routing): experts e * n_group // E share group, a group's score is
    the largest selection score of its experts, the `topk_group` best
    groups stay (ties to the lower group), every other group's scores
    are set to 0 ahead of the top-k. `groups=True` also returns the
    groups kept, [T, n_group] bool."""
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if cfg.router == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    sel = scores if bias is None else scores + bias.astype(jnp.float32)
    t, e = scores.shape
    kept = jnp.ones((t, cfg.n_group), bool)
    if cfg.n_group > 1:
        per = e // cfg.n_group
        best = jnp.max(sel.reshape(t, cfg.n_group, per), axis=-1)
        _, gi = jax.lax.top_k(best, cfg.topk_group)
        kept = jnp.any(gi[..., None] == jnp.arange(cfg.n_group), axis=1)
        sel = jnp.where(jnp.repeat(kept, per, axis=1), sel, 0.0)
    if bias is None and cfg.n_group == 1:
        w, idx = jax.lax.top_k(scores, cfg.top_k)
    else:
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return (idx, w * cfg.scale, kept) if groups else (idx, w * cfg.scale)


def _experts_xla(xa, sizes, params, cfg, dt):
    """The grouped expert FFN as XLA sees it (`lax.ragged_dot` over the
    expert-sorted rows): the oracle of the Pallas kernel, and the path
    off the TPU. xa [A, D] sorted by expert, sizes [E]."""
    from .quant import dequant
    rd = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                           preferred_element_type=jnp.float32)
    h = rd(xa, dequant(params["w1"], dt))
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(h) * rd(xa, dequant(params["w3"], dt))
    else:
        eid = jnp.repeat(jnp.arange(sizes.shape[0]), sizes,
                         total_repeat_length=xa.shape[0])
        h = jax.nn.gelu(h + params["b1"].astype(jnp.float32)[eid])
    return rd(h.astype(dt), dequant(params["w2"], dt)).astype(dt)


def moe_ffn_serve(x: jax.Array, params: Dict[str, Any], cfg: MoeConfig,
                  kernel: Any = None, held: Any = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """DROP-FREE sparse FFN for serving on one shard: every token's
    top_k experts compute it, whatever the routing's skew.

    x [T, D] -> (out [T, D], stats [2 + E] f32). Static shapes: the
    T * k assignments are sorted by expert (`argsort`), the group sizes
    counted, and ONE grouped matrix product runs over the experts that
    were hit: `kernel="pallas"` the `hpx_moe_gmm` kernel
    (ops/moe_gmm.py; rows padded per expert to its row tile, an expert
    no token chose is never read), `kernel="xla"` `lax.ragged_dot`;
    None picks pallas on a TPU for SiLU-gated experts and xla
    elsewhere. Each token then gathers its k rows back and sums them
    under its weights (a gather, no scatter-add: deterministic), plus
    the shared expert where the model has one.

    `held=(lo, hi)` (default: `cfg.held`): this shard's SHARE of the
    experts. `params` then
    hold experts lo..hi-1 only ([hi - lo, ...] matrices) and, on the
    one shard that is to count it, the shared expert; the router keeps
    its published width, every token is routed over all the experts,
    and the result is the part that the held experts give (what the
    absent ones would add is left out, with no stand-in for them). The
    shares of a layer add up to the whole layer.

    stats: [claims routed (T * k), claims dropped (0 by construction),
    per-expert occupancy]. With no capacity an expert's occupancy
    reads 1.0 where at least one token chose it and 0.0 where none
    did, so the vector's tail sums to the distinct experts hit. A
    group-limited router (`cfg.n_group` > 1) appends STATS_HERE more
    numbers: the assignments that fell to the held experts, and the
    tokens whose kept groups include a held one."""
    from .quant import dequant
    t, d = x.shape
    e, k, dt = cfg.n_experts, cfg.top_k, cfg.dtype
    if k > e:
        raise ValueError(f"top_k ({k}) > n_experts ({e})")
    idx, w, kept = route(x, params["wg"], cfg, params.get("bias"),
                         groups=True)
    a = t * k
    flat = idx.reshape(a)
    mine = None
    held = held or cfg.held
    if held:
        lo, hi = held
        mine = jnp.logical_and(flat >= lo, flat < hi)
        e = hi - lo
        flat = jnp.where(mine, flat - lo, e)         # absent: sorted last
        w = jnp.where(mine.reshape(t, k), w, 0.0)
    order = jnp.argsort(flat, stable=True)           # by expert
    sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    xd = x.astype(dt)
    if kernel is None:
        kernel = ("pallas" if jax.default_backend() == "tpu"
                  and cfg.mlp == "swiglu" else "xla")
    if kernel == "pallas":
        if cfg.mlp != "swiglu":
            raise NotImplementedError(
                "ops/moe_gmm.hpx_moe_gmm computes SiLU-gated experts; "
                "GELU experts with a bias take kernel='xla'")
        from ..ops.moe_gmm import grouped_swiglu, row_tile
        tm = row_tile(dt)
        n_tiles = -(-a // tm) + e
        tiles = -(-sizes // tm)                      # row tiles an expert
        tile_end = jnp.cumsum(tiles)
        n_used = tile_end[-1:]
        # sorted assignment j sits at rank j - start[e_j] of its group
        es = jnp.minimum(flat[order], e - 1)
        start = jnp.cumsum(sizes) - sizes
        dest_sorted = jnp.minimum(
            (tile_end - tiles)[es] * tm + (jnp.arange(a) - start[es]),
            n_tiles * tm - 1)
        dest = jnp.zeros((a,), jnp.int32).at[order].set(
            dest_sorted.astype(jnp.int32))           # by (token, choice)
        x_pad = jnp.zeros((n_tiles * tm, d), dt).at[dest].set(
            jnp.repeat(xd, k, axis=0))
        tile_e = jnp.searchsorted(
            tile_end, jnp.minimum(jnp.arange(n_tiles), n_used[0] - 1),
            side="right").astype(jnp.int32)
        y = grouped_swiglu(
            x_pad, jnp.minimum(tile_e, e - 1), n_used,
            dequant(params["w1"], dt), dequant(params["w3"], dt),
            dequant(params["w2"], dt))[dest]
    else:
        ys = _experts_xla(xd[order // k], sizes, params, cfg, dt)
        y = jnp.zeros_like(ys).at[order].set(ys)     # by (token, choice)
    if mine is not None:        # an absent expert's row is nobody's
        y = jnp.where(mine[:, None], y, 0)
    out = jnp.sum(y.reshape(t, k, d).astype(jnp.float32)
                  * w[..., None], axis=1)
    if "shared" in params:
        sp = params["shared"]
        hs = (jax.nn.silu(xd @ dequant(sp["w1"], dt))
              * (xd @ dequant(sp["w3"], dt))) @ dequant(sp["w2"], dt)
        out = out + hs.astype(jnp.float32)
    stats = [jnp.asarray([a, 0.0], jnp.float32),
             (sizes > 0).astype(jnp.float32)]
    if cfg.n_group > 1:
        lo, hi = held or (0, cfg.n_experts)
        per = cfg.n_experts // cfg.n_group
        stats.append(jnp.stack([
            jnp.float32(a) if mine is None
            else jnp.sum(mine, dtype=jnp.float32),
            jnp.sum(jnp.any(kept[:, lo // per:(hi - 1) // per + 1], axis=1),
                    dtype=jnp.float32)]))
    return out.astype(x.dtype), jnp.concatenate(stats)
