"""Decoder-only transformer with a fully sharded training step.

The reference (HPX) ships no ML models; this is the model family the
driver mandates for the TPU rebuild, built on the framework's own
substrate: ring attention (ops/attention.py — the halo-exchange ring of
SURVEY.md §5.7) for sequence parallelism, XLA collectives over ICI for
tensor/data parallelism.

Parallelism layout over a Mesh(("dp","sp","tp")):
  dp — batch sharded; grads psum over dp (+sp for the sequence split)
  sp — sequence sharded; attention walks the ring (ring_attention_
       sharded), everything else is token-local
  tp — Megatron-style: attention heads and MLP hidden dim sharded;
       wo/w2 contractions end in a psum over tp
  (collective axis names inside shard_map bodies are machine-checked
  against the mesh declaration by hpxlint HPX021)

Everything (forward, loss, backward through the ring, optimizer) runs
inside ONE shard_map-jitted program — the whole training step is a
single XLA executable per device.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops.attention import auto_attention, ring_attention_sharded

__all__ = ["RopeSpec", "TransformerConfig", "init_params", "make_train_step",
           "make_mesh_3d", "shard_params", "shard_batch", "sample_batch",
           "make_opt_state", "generate", "make_pipelined_train_step",
           "stack_pipeline_params", "shard_pipeline_params",
           "pipelined_param_specs", "interleave_pipeline_params",
           "speculative_generate", "speculative_sample",
           "deinterleave_pipeline_params", "prepare_pipeline_params",
           "beam_search"]


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One layer kind's rotary embedding (rotate-half). `rotary_dim`
    0 rotates the whole head, else the head's first `rotary_dim` dims.
    `factor` > 1 is YaRN as Hugging Face's `rope_type: "yarn"` computes
    it: inverse frequencies blended between theta-spaced and those /
    factor by the linear ramp between the two correction dims of
    (beta_fast, beta_slow) over `original_max`, and cos/sin multiplied
    by `attention_factor`."""
    theta: float = 10000.0
    rotary_dim: int = 0
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, rot: int):
        """[rot // 2] float32 inverse frequencies."""
        half = rot // 2
        if self.factor == 1.0:
            return self.theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                                  / half)
        import numpy as np
        pos_freqs = self.theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                   / rot)

        def corr(n_rot):
            return (rot * math.log(self.original_max
                                   / (n_rot * 2 * math.pi))
                    / (2 * math.log(self.theta)))
        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), rot - 1)
        ramp = np.clip((np.arange(half) - low)
                       / ((high - low) or 0.001), 0, 1)
        inv = (1 / (self.factor * pos_freqs) * ramp
               + 1 / pos_freqs * (1 - ramp))
        return jnp.asarray(inv, jnp.float32)


# the mixer kinds that keep a per-slot state which is a function of the
# tokens consumed (reset at admission, recomputed at a restore)
RECURRENT_KINDS = ("kda", "lightning", "mamba")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    head_dim: int = 16
    n_layers: int = 2
    d_ff: int = 128
    dtype: Any = jnp.float32
    lr: float = 1e-2
    # mixture-of-experts: n_experts > 0 replaces every block's MLP with
    # a MoE FFN (models/moe.py); experts shard over the dp axis —
    # tokens are batch-sharded there, so the MoE all_to_all exchanges
    # tokens within data-parallel groups (the GShard layout) — giving
    # the dp x sp x tp x EP parallelism combination in one train step
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity: float = 2.0
    moe_aux_weight: float = 0.01
    # grouped-query attention: 0 < n_kv_heads < n_heads shares each
    # K/V head across a group of n_heads/n_kv_heads query heads
    # (GQA; n_kv_heads=1 is MQA). 0 means n_heads (standard MHA).
    # The KV cache — the serving memory bill — shrinks by the same
    # factor; the flash kernels read shared tiles via BlockSpec index
    # remaps, never a materialized repeat.
    n_kv_heads: int = 0
    # rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory drops from O(n_layers * S * D) residuals to one
    # block's, for one extra forward — the standard long-context trade
    remat: bool = False
    # rotary position embeddings (RoPE, GPT-NeoX rotate-half form)
    # applied to q/k before attention. Off by default (the original
    # position-free model stays the baseline); under sequence
    # parallelism each shard rotates by its GLOBAL positions
    # (axis_index * S_local offset), so the ring sees one coherent
    # position space.
    rope: bool = False
    rope_theta: float = 10000.0
    # striped sequence parallelism (Striped Attention): shard r of the
    # sp ring holds tokens r, r+sp, ... instead of a contiguous chunk,
    # so causal ring steps do balanced half-work (~2x wall clock on
    # causal rings; see ops/attention.stripe_sequence). make_train_step
    # stripes the batch itself (one all_to_all each way per step);
    # positions stay GLOBAL so weights are layout-independent — decode
    # and checkpoints are unaffected.
    striped_ring: bool = False
    # -- what describes a LAYER: read by `_layer`, the one definition
    # every forward body takes. The defaults are the block this file
    # always built (LayerNorm, tanh-GELU with a first bias, tied head).
    norm: str = "layernorm"         # | "rmsnorm" (no mean, no bias)
    norm_eps: float = 1e-5
    mlp: str = "gelu"               # | "swiglu": w2(silu(w1 h) * w3 h)
    tied: bool = True               # False: params["head"] [V, D]
    attn_gate: bool = False         # o_h *= sigmoid(h @ wgate)[h]
    # per-layer tuples; empty = every layer as the scalars say
    layer_heads: Tuple[int, ...] = ()    # q heads of layer i
    layer_window: Tuple[int, ...] = ()   # 0 full; W: i - W < j <= i
    layer_rope: Tuple[Any, ...] = ()     # RopeSpec (or None) of layer i
    layer_sparse: Tuple[bool, ...] = ()  # MoE FFN on layer i
    # the experts as served drop-free (moe.moe_ffn_serve)
    moe_d_ff: int = 0               # expert width; 0 = d_ff
    moe_shared_d_ff: int = 0        # one shared expert of this width
    moe_router: str = "softmax"     # | "sigmoid"
    moe_renorm: bool = False        # weights / their sum over the top k
    moe_scale: float = 1.0
    moe_bias: bool = False          # a selection bias: the choice only
    # the SHARE of the experts this program holds, (lo, hi); empty =
    # all n_experts. The router keeps its published width either way.
    moe_held: Tuple[int, ...] = ()
    # the MIXER of layer i: "attn" (softmax attention over K/V pairs,
    # what the fields above describe), "kda" (a gated delta rule over a
    # per-slot recurrent state, ops/kda.py), "mla" (latent attention:
    # one cached row of mla_rank + mla_rope_dim values a token),
    # "sparse" (softmax attention over K/V pairs of which the QUERY
    # chooses the blocks it reads, by an index of compressed keys:
    # ops/sparse_attention.py), "lightning" (decayed linear attention
    # over a per-slot recurrent state, ops/lightning.py) or "mamba" (a
    # selective scan: a diagonal state-space recurrence over a per-slot
    # recurrent state behind a short convolution, ops/mamba.py) or "eva"
    # (softmax attention over K/V rows at TWO grains: the exact rows of
    # the query's own aligned window of `eva_window` positions, and one
    # learned-pooled K/V row for every `eva_chunk` positions of every
    # window behind it, under one softmax: ops/eva.py).
    # Empty = every layer "attn".
    layer_mixer: Tuple[str, ...] = ()
    kda_heads: int = 0              # heads of kda_head_dim x kda_head_dim
    kda_head_dim: int = 0
    kda_conv: int = 4               # taps of the short convolution
    kda_rank: int = 0               # low-rank width of the two gates
    mla_rank: int = 0               # the compressed K/V row (kv_lora_rank)
    mla_nope_dim: int = 0           # a head's q/k dims against the latent
    mla_rope_dim: int = 0           # a head's dims against the shared r
    mla_v_dim: int = 0              # a head's value dims
    # the query's low rank (q_lora_rank: W_uq RMSNorm(W_dq h)); 0 = one
    # full W_q. The mla_rope_dim dims of q and of the cached row are
    # rotated by the layer's RopeSpec (`layer_rope`; None = NoPE), and
    # the softmax scale is (nope + rope dims)^-1/2 * mla_mscale^2 (the
    # YaRN mscale a model trained under a stretched rotation carries).
    mla_q_rank: int = 0
    mla_mscale: float = 1.0
    # device-limited routing: the experts lie in moe_n_group equal
    # groups, a token keeps its moe_topk_group best groups (a group's
    # score: its largest expert score) and picks its top_k inside them
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # a "sparse" layer's numbers (ops/sparse_attention.SparseSpec):
    # compressed keys of sparse_kernel rows every sparse_stride, blocks
    # of sparse_block rows, sparse_topk of them a query and kv group
    # (sparse_init leading and those of the last sparse_local rows
    # among them), every block up to sparse_dense_len rows
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init: int = 1
    sparse_local: int = 2048
    sparse_dense_len: int = 8192
    # a "lightning" layer's heads: lightning_heads states of
    # lightning_head_dim x lightning_head_dim a slot
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    # a "mamba" layer's numbers: mamba_d_inner channels, each a state of
    # mamba_d_state values; mamba_d_conv taps of the short convolution
    # (with a bias where mamba_conv_bias); dt through a low rank of
    # mamba_dt_rank
    mamba_d_inner: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    mamba_conv_bias: bool = True
    # an "eva" layer's two grains (eva_chunk divides eva_window)
    eva_chunk: int = 16
    eva_window: int = 2048
    # the head holds pred_heads x vocab rows: head m (rows vocab m ..
    # vocab m + vocab - 1) predicts the token m + 1 ahead; decoding
    # picks from head 0's
    pred_heads: int = 1
    # the head's product is taken in float32 (else in `dtype`)
    logits_f32: bool = False
    # RMSNorm's scale is 1 + the parameter (the parameter as published)
    norm_unit_offset: bool = False
    # RMSNorm over each head of q and k (one learned scale a layer):
    # the "sparse" and "lightning" mixers' (their parameters name it)
    qk_norm: bool = False
    # x = emb[token] * emb_scale; x += residual_scale * branch; the
    # head reads RMSNorm(x) * logit_scale
    emb_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # the PUBLISHED index of layer i and the published depth, where a
    # cut in depth keeps a run of layers (a lightning head's decay is a
    # function of both); empty / 0 = i, n_layers
    layer_published: Tuple[int, ...] = ()
    published_layers: int = 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def heads(self, i: int) -> int:
        return self.layer_heads[i] if self.layer_heads else self.n_heads

    def window(self, i: int) -> int:
        return self.layer_window[i] if self.layer_window else 0

    def rope_of(self, i: int) -> Optional[RopeSpec]:
        if self.layer_rope:
            return self.layer_rope[i]
        return RopeSpec(self.rope_theta) if self.rope else None

    def sparse(self, i: int) -> bool:
        if self.layer_sparse:
            return bool(self.layer_sparse[i])
        return self.n_experts > 0

    def mixer(self, i: int) -> str:
        return self.layer_mixer[i] if self.layer_mixer else "attn"

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a per-slot state that is a function of the
        tokens consumed, not rows addressed by position."""
        return any(k in RECURRENT_KINDS for k in self.layer_mixer)

    @property
    def sparse_spec(self):
        """A "sparse" layer's numbers as its ops take them."""
        from ..ops.sparse_attention import SparseSpec
        return SparseSpec(self.sparse_kernel, self.sparse_stride,
                          self.sparse_block, self.sparse_topk,
                          self.sparse_init, self.sparse_local,
                          self.sparse_dense_len)

    def lightning_decay(self, i: int):
        """[lightning_heads] float32 log decay of layer i's heads, a
        constant of (head, PUBLISHED layer, published depth)."""
        from ..ops.lightning import lightning_log_decay
        return lightning_log_decay(
            self.lightning_heads,
            self.layer_published[i] if self.layer_published else i,
            self.published_layers or self.n_layers)

    @property
    def mla_row(self) -> int:
        """Width of a cached latent row: rank + rope dims, rounded up
        to whole 128-lane rows (the chip pads the minor dim to that
        anyway; the pad columns are written as zeros)."""
        return -(-(self.mla_rank + self.mla_rope_dim) // 128) * 128

    @property
    def mla_scale(self) -> float:
        """The softmax scale of a latent-attention layer's scores."""
        return (self.mla_nope_dim + self.mla_rope_dim) ** -0.5 \
            * self.mla_mscale ** 2

    @property
    def experts_held(self) -> int:
        return (self.moe_held[1] - self.moe_held[0] if self.moe_held
                else self.n_experts)

    def kv_pairs_only(self, body: str, module: str) -> None:
        """Refuse a body whose caches hold K/V pairs addressed by
        position a model some of whose mixers cache something else."""
        kinds = sorted(set(self.layer_mixer) - {"attn"})
        if kinds:
            raise NotImplementedError(
                f"{body} ({module}) cannot compute this model: its "
                f"caches hold K/V pairs, `layer_mixer` has {kinds}; "
                "ContinuousServer holds the recurrent state, the latent "
                "rows, a sparse layer's index and an eva layer's two "
                "grains of rows (models/serving.py _init_paged)")

    def only(self, body: str, module: str, *allowed: str) -> None:
        """Refuse, by mechanism and module, a model whose layers `body`
        cannot compute: every layer-describing field outside `allowed`
        must be at its default."""
        for f in ("norm", "mlp", "tied", "attn_gate", "layer_heads",
                  "layer_window", "layer_rope", "layer_sparse",
                  "moe_shared_d_ff", "moe_router", "moe_renorm",
                  "moe_bias", "moe_held", "layer_mixer", "moe_n_group",
                  "moe_topk_group", "qk_norm", "emb_scale",
                  "residual_scale", "logit_scale", "pred_heads",
                  "logits_f32", "norm_unit_offset"):
            if f not in allowed and getattr(self, f) != getattr(
                    TransformerConfig, f):
                raise NotImplementedError(
                    f"{body} ({module}) cannot compute this model: "
                    f"`{f}` = {getattr(self, f)!r} has no path there")


def make_mesh_3d(n_devices: int, devices=None):
    """Factor n into (dp, sp, tp) — prefer sp and tp first (they
    exercise the interesting collectives), then dp."""
    import numpy as np
    import jax as _j
    devs = list(devices) if devices is not None else _j.devices()
    devs = devs[:n_devices]

    def take(n, want):
        f = math.gcd(n, want)
        while f < want and n % (f * 2) == 0 and f * 2 <= want:
            f *= 2
        return (f if n % f == 0 else 1)

    tp = 2 if n_devices % 2 == 0 else 1
    rest = n_devices // tp
    sp = 2 if rest % 2 == 0 else 1
    dp = rest // sp
    from jax.sharding import Mesh
    return Mesh(np.array(devs).reshape(dp, sp, tp), ("dp", "sp", "tp"))


def _moe_cfg(cfg: TransformerConfig):
    from .moe import MoeConfig
    return MoeConfig(n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                     capacity_factor=cfg.moe_capacity,
                     d_model=cfg.d_model, d_ff=cfg.moe_d_ff or cfg.d_ff,
                     dtype=cfg.dtype, mlp=cfg.mlp,
                     router=cfg.moe_router, renorm=cfg.moe_renorm,
                     scale=cfg.moe_scale,
                     shared_d_ff=cfg.moe_shared_d_ff,
                     bias=cfg.moe_bias, held=cfg.moe_held,
                     n_group=cfg.moe_n_group,
                     topk_group=cfg.moe_topk_group)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Weight pytree. tp-sharded leaves carry their FULL logical shape
    here; shard_params() places them."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_ff
    keys = jax.random.split(key, 2 + cfg.n_layers)
    s = 1.0 / math.sqrt(d)

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(cfg.dtype)

    def mixer(k, kind):
        """The leaves of a "kda" / "mla" mixer (its own `wo` among
        them); the small gate and decay parameters stay float32."""
        ks = jax.random.split(k, 12)
        if kind in ("sparse", "lightning"):
            return _init_gated_mixer(cfg, kind, ks, nrm)
        if kind == "mamba":
            return _init_mamba_mixer(cfg, ks, nrm)
        if kind == "eva":
            return _init_eva_mixer(cfg, ks, nrm)
        if kind == "kda":
            h, hd, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank
            return {"kda": {
                "wqkv": nrm(ks[0], (d, 3, h, hd), s),
                "conv": nrm(ks[1], (cfg.kda_conv, 3, h, hd),
                            1.0 / math.sqrt(cfg.kda_conv)),
                "wf1": nrm(ks[2], (d, r), s),
                "wf2": nrm(ks[3], (r, h, hd), 1.0 / math.sqrt(r)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": jax.random.uniform(
                    ks[5], (h, hd), jnp.float32, -4.0, -1.0),
                "wb": nrm(ks[6], (d, h), s),
                "wg1": nrm(ks[7], (d, r), s),
                "wg2": nrm(ks[8], (r, h, hd), 1.0 / math.sqrt(r)),
                "bg": jnp.zeros((h, hd), jnp.float32),
                "onorm": jnp.ones((hd,), cfg.dtype),
                "wo": nrm(ks[9], (h, hd, d), 1.0 / math.sqrt(h * hd))}}
        h, r = cfg.n_heads, cfg.mla_rank
        dq, qr = cfg.mla_nope_dim + cfg.mla_rope_dim, cfg.mla_q_rank
        wq = {"wq": nrm(ks[0], (d, h, dq), s)} if not qr else {
            "wdq": nrm(ks[0], (d, qr), s),
            "qnorm": jnp.ones((qr,), cfg.dtype),
            "wuq": nrm(ks[5], (qr, h, dq), 1.0 / math.sqrt(qr))}
        return {"mla": {
            **wq,
            "wdkv": nrm(ks[1], (d, r + cfg.mla_rope_dim), s),
            "kvnorm": jnp.ones((r,), cfg.dtype),
            "wuk": nrm(ks[2], (r, h, cfg.mla_nope_dim),
                       1.0 / math.sqrt(r)),
            "wuv": nrm(ks[3], (r, h, cfg.mla_v_dim), 1.0 / math.sqrt(r)),
            "wo": nrm(ks[4], (h, cfg.mla_v_dim, d),
                      1.0 / math.sqrt(h * cfg.mla_v_dim))}}

    def scale1():
        """A norm's parameter at the scale 1 it starts from."""
        return (jnp.zeros if cfg.norm_unit_offset else jnp.ones)(
            (d,), cfg.dtype)

    def layer(k, i):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        if cfg.mixer(i) != "attn":
            return ffn({"ln1": scale1(), **mixer(k1, cfg.mixer(i)),
                        "ln2": scale1()}, i, k3, k4)
        nh, nkv = cfg.heads(i), cfg.kv_heads
        if nh % nkv:
            raise ValueError(f"n_heads={nh} not a multiple of "
                             f"n_kv_heads={nkv}")
        if nkv == nh:
            qkv = {"wqkv": (jax.random.normal(k1, (3, d, nh, hd)) * s
                            ).astype(cfg.dtype)}
        else:
            kq, kkv = jax.random.split(k1)
            qkv = {"wq": (jax.random.normal(kq, (d, nh, hd)) * s
                          ).astype(cfg.dtype),
                   "wkv": (jax.random.normal(kkv, (2, d, nkv, hd)) * s
                           ).astype(cfg.dtype)}
        out = {
            "ln1": scale1(),
            **qkv,
            "wo": (jax.random.normal(k2, (nh, hd, d)) * s
                   ).astype(cfg.dtype),
            "ln2": scale1(),
        }
        if cfg.attn_gate:
            out["wgate"] = (jax.random.normal(
                jax.random.fold_in(k2, 1), (d, nh)) * s).astype(cfg.dtype)
        return ffn(out, i, k3, k4)

    def ffn(out, i, k3, k4):
        if cfg.sparse(i):
            from .moe import init_moe_params
            out["moe"] = init_moe_params(_moe_cfg(cfg), k3)
            return out
        out.update({
            "w1": (jax.random.normal(k3, (d, f)) * s).astype(cfg.dtype),
            "w2": (jax.random.normal(k4, (f, d)) / math.sqrt(f)
                   ).astype(cfg.dtype),
        })
        if cfg.mlp == "swiglu":
            out["w3"] = (jax.random.normal(
                jax.random.fold_in(k3, 1), (d, f)) * s).astype(cfg.dtype)
        else:
            out["b1"] = jnp.zeros((f,), cfg.dtype)
        return out

    params = {
        "emb": (jax.random.normal(keys[0], (cfg.vocab, d)) * s
                ).astype(cfg.dtype),
        "ln_f": scale1(),
        "layers": [layer(keys[2 + i], i) for i in range(cfg.n_layers)],
    }
    if cfg.pred_heads > 1 and cfg.tied:
        raise ValueError(f"pred_heads={cfg.pred_heads} needs an untied "
                         "head of pred_heads x vocab rows (tied=False)")
    if not cfg.tied:
        params["head"] = (jax.random.normal(
            keys[1], (cfg.pred_heads * cfg.vocab, d)) * s).astype(cfg.dtype)
    return params


def _init_gated_mixer(cfg: TransformerConfig, kind: str, ks, nrm):
    """The leaves of a "sparse" / "lightning" mixer: projections, the
    q/k norm scales (`cfg.qk_norm`), an ELEMENTWISE output gate and, on
    a lightning layer, the output norm's scale over all heads. Every
    projection is a MATRIX [in, out] with the heads side by side in
    its columns (a sparse layer's "wkv": k heads, then v heads): a
    [D, H, hd] leaf is tiled over (H, hd) on the chip, and the chip's
    compiler then copies it whole into the matmul's tiling in every
    step (33 MB a leaf at 4096 x 32 x 128), as it transposes a fused
    [D, 3 H hd] one (100 MB); the activation is what is reshaped."""
    d = cfg.d_model
    s = 1.0 / math.sqrt(d)
    if kind == "sparse":
        h, hd, nkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
        out = {"wq": nrm(ks[0], (d, h * hd), s),
               "wkv": nrm(ks[1], (d, 2 * nkv * hd), s)}
    else:
        h, hd = cfg.lightning_heads, cfg.lightning_head_dim
        out = {"wq": nrm(ks[0], (d, h * hd), s),
               "wk": nrm(ks[4], (d, h * hd), s),
               "wv": nrm(ks[5], (d, h * hd), s),
               "onorm": jnp.ones((h * hd,), cfg.dtype)}
    if cfg.qk_norm:
        out.update(qnorm=jnp.ones((hd,), cfg.dtype),
                   knorm=jnp.ones((hd,), cfg.dtype))
    out.update(wg=nrm(ks[2], (d, h * hd), s),
               wo=nrm(ks[3], (h * hd, d), 1.0 / math.sqrt(h * hd)))
    return {kind: out}


def _init_mamba_mixer(cfg: TransformerConfig, ks, nrm):
    """The leaves of a "mamba" mixer. Projections are MATRICES [in,
    out] ("win": the u stream's columns, then the gate z's). "A_log" is
    [d_state, d_inner], the published [d_inner, d_state] with the
    channels on the minor axis, as the cached state lies
    (ops/mamba.py); A_log = log(1..d_state) and dt_bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1] (the published
    initialisation: decays in the trained range); A_log, dt_bias and D
    stay float32."""
    d, c, n = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state
    r, k = cfg.mamba_dt_rank, cfg.mamba_d_conv
    step = jnp.exp(jax.random.uniform(
        ks[5], (c,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    out = {"win": nrm(ks[0], (d, 2 * c), 1.0 / math.sqrt(d)),
           "conv": nrm(ks[1], (k, c), 1.0 / math.sqrt(k)),
           "wx": nrm(ks[2], (c, r + 2 * n), 1.0 / math.sqrt(c)),
           "dt_norm": jnp.ones((r,), cfg.dtype),
           "b_norm": jnp.ones((n,), cfg.dtype),
           "c_norm": jnp.ones((n,), cfg.dtype),
           "wdt": nrm(ks[3], (r, c), 1.0 / math.sqrt(r)),
           "dt_bias": step + jnp.log(-jnp.expm1(-step)),
           "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
               1, n + 1, dtype=jnp.float32))[:, None], (n, c)),
           "D": jnp.ones((c,), jnp.float32),
           "wo": nrm(ks[4], (c, d), 1.0 / math.sqrt(c))}
    if cfg.mamba_conv_bias:
        out["conv_b"] = nrm(ks[6], (c,), 0.02)
    return {"mamba": out}


def _init_eva_mixer(cfg: TransformerConfig, ks, nrm):
    """The leaves of an "eva" mixer: four projection MATRICES [in, out]
    with the heads side by side in their columns (plain multi-head: as
    many K/V heads as query heads) and the two learned vectors a head
    that pool a chunk's rows into its summary, "phi" (the pooling
    weights' query) and "mu" (added to the pooled key), float32. Drawn
    at unit scale here so that random weights pool unevenly."""
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    if cfg.kv_heads != h:
        raise ValueError(f"an eva layer is plain multi-head: n_kv_heads="
                         f"{cfg.kv_heads} != n_heads={h}")
    s = 1.0 / math.sqrt(d)
    return {"eva": {
        "wq": nrm(ks[0], (d, h * hd), s), "wk": nrm(ks[1], (d, h * hd), s),
        "wv": nrm(ks[2], (d, h * hd), s),
        "phi": jax.random.normal(ks[3], (h, hd), jnp.float32),
        "mu": jax.random.normal(ks[4], (h, hd), jnp.float32),
        "wo": nrm(ks[5], (h * hd, d), 1.0 / math.sqrt(h * hd))}}


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs: heads/ffn over tp; MoE experts over dp (the ep
    layout — see TransformerConfig); everything else replicated."""
    cfg.only("param_specs: the (dp, sp, tp) placement",
             "models/transformer.py")
    if cfg.kv_heads == cfg.n_heads:
        qkv = {"wqkv": P(None, None, "tp", None)}
    else:
        qkv = {"wq": P(None, "tp", None),
               "wkv": P(None, None, "tp", None)}
    layer = {
        "ln1": P(), **qkv,
        "wo": P("tp", None, None), "ln2": P(),
    }
    if cfg.n_experts > 0:
        from .moe import moe_param_specs
        # experts over dp (ep layout) AND each expert's d_ff over tp —
        # the MoE output closes with a tp psum like the dense MLP
        layer["moe"] = moe_param_specs("dp", tp_axis="tp")
    else:
        layer.update({"w1": P(None, "tp"), "b1": P("tp"),
                      "w2": P("tp", None)})
    return {"emb": P(), "ln_f": P(),
            "layers": [dict(layer) for _ in range(cfg.n_layers)]}


def _place(tree, specs, mesh):
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, specs)


def shard_params(params, cfg: TransformerConfig, mesh):
    return _place(params, param_specs(cfg), mesh)


def shard_batch(tokens, targets, mesh):
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, P("dp", "sp"))
    return jax.device_put(tokens, sh), jax.device_put(targets, sh)


def sample_batch(cfg: TransformerConfig, batch: int, seq: int,
                 key: jax.Array):
    toks = jax.random.randint(key, (batch, seq + 1), 0, cfg.vocab)
    return toks[:, :-1], toks[:, 1:]


# ---------------------------------------------------------------------------
# per-shard forward / loss
# ---------------------------------------------------------------------------

def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale


def _norm(x, scale, cfg: TransformerConfig):
    """The model's norm: LayerNorm (scale, no bias) or RMSNorm; under
    `norm_unit_offset` the scale is 1 + the parameter."""
    if cfg.norm == "rmsnorm":
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
        if cfg.norm_unit_offset:
            # 1 + g in float32: in bfloat16 the sum would round g away
            return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
        return y.astype(x.dtype) * scale
    return _ln(x, scale + 1 if cfg.norm_unit_offset else scale)


def _dq(w, like):
    """Dequantize int8 serving weights at use (models/quant.QTensor);
    dense weights pass through untouched."""
    from .quant import dequant
    return dequant(w, like.dtype)


def _qkv_proj(h, lp):
    """Project to (q, k, v); GQA layouts ("wq"+"wkv") give k/v their
    smaller head count."""
    if "wqkv" in lp:
        q, k, v = jnp.einsum("bsd,cdnh->cbsnh", h, _dq(lp["wqkv"], h))
        return q, k, v
    q = jnp.einsum("bsd,dnh->bsnh", h, _dq(lp["wq"], h))
    k, v = jnp.einsum("bsd,cdnh->cbsnh", h, _dq(lp["wkv"], h))
    return q, k, v


def _rope(x, pos, spec: RopeSpec):
    """Rotate q/k by position (GPT-NeoX rotate-half). x: [B, S, N, H];
    pos: [S] positions shared by the batch (global under sp), or a
    [B, S] grid where every (row, column) sits at its own."""
    hd = x.shape[-1]
    rot = spec.rotary_dim or hd
    if rot % 2:
        raise ValueError(f"rope needs an even head_dim (or rotary_dim); "
                         f"got {rot}")
    half = rot // 2
    ang = pos.astype(jnp.float32)[..., None] * spec.inv_freq(rot)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if spec.attention_factor != 1.0:
        cos, sin = cos * spec.attention_factor, sin * spec.attention_factor
    if pos.ndim == 1:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos]
                           + ([x[..., rot:]] if rot < hd else []),
                           axis=-1)


def _layer(x, lp, cfg: TransformerConfig, li: int, pos, attend,
           tp_axis: Optional[str] = None, moe=None):
    """THE decoder layer, the one definition every forward body takes:
    norm, mixer, residual, norm, FFN, residual. What differs between
    the bodies is where a mixer's cached state lives, and that is
    `attend(*operands) -> (out, carry)`: the body hands the mixer its
    cache state and takes the new one back (`carry`, returned beside
    x), whatever its kind. For attention the operands are (q, k, v)
    and the body's own cache write and attention read answer (dense
    cache, paged pools, the sp ring); for "kda" (pre, g, beta) and the
    body's state and conv tail (`_kda_mixer`), for "mamba" the u stream
    and the same (`_mamba_mixer`); for "mla" (q, row) and
    the body's latent rows (`_mla_mixer`). `pos`: the positions of x's
    columns, [S] or [B, S]. `moe(h) -> out` is the body's sparse FFN
    (it closes its own collectives). What differs between LAYERS is in
    `cfg` (norm, per-layer rope, window via `attend`) and in the
    parameters themselves: a "kda" or "mla" names the mixer's kind,
    head counts are read off the arrays, a "wgate" gates the heads, a
    "w3" makes the MLP SiLU-gated, a "moe" makes it sparse."""
    h = _norm(x, lp["ln1"], cfg)

    def add(x, branch):         # the residual, under the model's scale
        rs = cfg.residual_scale
        return x + (branch if rs == 1.0 else branch * rs)
    if "kda" in lp:
        o, carry = _kda_mixer(h, lp["kda"], cfg, attend)
    elif "sparse" in lp:
        o, carry = _sparse_mixer(h, lp["sparse"], cfg, attend)
    elif "lightning" in lp:
        o, carry = _lightning_mixer(h, lp["lightning"], cfg, attend, pos,
                                    cfg.rope_of(li))
    elif "mamba" in lp:
        o, carry = _mamba_mixer(h, lp["mamba"], attend)
    elif "eva" in lp:
        o, carry = _eva_mixer(h, lp["eva"], cfg, attend, pos,
                              cfg.rope_of(li))
    elif "mla" in lp:
        o, carry = _mla_mixer(h, lp["mla"], cfg, attend, pos,
                              cfg.rope_of(li))
    else:
        q, k, v = _qkv_proj(h, lp)
        rope = cfg.rope_of(li)
        if rope is not None:
            q, k = _rope(q, pos, rope), _rope(k, pos, rope)
        att, carry = attend(q, k, v)
        if "wgate" in lp:
            gate = jax.nn.sigmoid(jnp.einsum("bsd,dn->bsn", h,
                                             _dq(lp["wgate"], h)))
            att = att * gate[..., None]
        o = jnp.einsum("bsnh,nhd->bsd", att, _dq(lp["wo"], att))
    if tp_axis:
        o = jax.lax.psum(o, tp_axis)       # Megatron row-parallel close
    x = add(x, o)
    h = _norm(x, lp["ln2"], cfg)
    if "moe" in lp:
        return add(x, moe(h)), carry
    if "w3" in lp:
        h = (jax.nn.silu(h @ _dq(lp["w1"], h)) * (h @ _dq(lp["w3"], h))
             ) @ _dq(lp["w2"], h)
    else:
        h = jax.nn.gelu(h @ _dq(lp["w1"], h) + lp["b1"]) \
            @ _dq(lp["w2"], h)
    if tp_axis:
        h = jax.lax.psum(h, tp_axis)
    return add(x, h), carry


def _head_rms(x, scale, eps: float):
    """RMSNorm over the last axis in float32, back in x's type."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + eps)).astype(x.dtype) * scale


def _gated_out(h, o, m):
    """W_o (sigmoid(W_g h) * o): the ELEMENTWISE output gate of the
    "sparse" and "lightning" mixers. o [B, W, H * hd]."""
    gate = jax.nn.sigmoid((h @ _dq(m["wg"], h)).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate).astype(h.dtype)
    return o @ _dq(m["wo"], o)


def _sparse_mixer(h, m, cfg: TransformerConfig, attend):
    """A learned-sparse attention mixer (InfLLM-v2) around the body's
    cache. h [B, W, D] -> (y, carry). q = RMSNorm_head(W_q h), k =
    RMSNorm_head(W_k h), v = W_v h, NO rotation; `attend(q, k, v)`
    writes the rows (and the index of compressed keys) and attends the
    blocks each query chooses (ops/sparse_attention.py); then the
    elementwise sigmoid gate and W_o."""
    b, w, _ = h.shape
    hd = cfg.head_dim
    q = (h @ _dq(m["wq"], h)).reshape(b, w, -1, hd)
    k, v = jnp.moveaxis(
        (h @ _dq(m["wkv"], h)).reshape(b, w, 2, -1, hd), 2, 0)
    if "qnorm" in m:
        q = _head_rms(q, m["qnorm"], cfg.norm_eps)
        k = _head_rms(k, m["knorm"], cfg.norm_eps)
    att, carry = attend(q, k, v)
    return _gated_out(h, att.reshape(b, w, -1), m), carry


def _lightning_mixer(h, m, cfg: TransformerConfig, attend, pos,
                     rope: Optional[RopeSpec]):
    """A lightning-attention mixer around the body's recurrent state.
    h [B, W, D] -> (y, carry). q, k = RoPE(RMSNorm_head(W h)) at the
    rows' positions, v = W_v h; the core (`attend(q, k, v)` =
    ops/lightning.lightning_mix over the body's state, float32, q
    scaled by d^-1/2) is S = lam S + k^T v, o = q S; then RMSNorm over
    ALL heads' outputs, the elementwise sigmoid gate, W_o."""
    f32 = jnp.float32
    b, w, _ = h.shape
    q, k, v = ((h @ _dq(m[n], h)).reshape(
        b, w, -1, cfg.lightning_head_dim) for n in ("wq", "wk", "wv"))
    if "qnorm" in m:
        q = _head_rms(q, m["qnorm"], cfg.norm_eps)
        k = _head_rms(k, m["knorm"], cfg.norm_eps)
    if rope is not None:
        q, k = _rope(q, pos, rope), _rope(k, pos, rope)
    o, carry = attend(q.astype(f32) * q.shape[-1] ** -0.5, k.astype(f32),
                      v.astype(f32))
    o = o.reshape(b, w, -1)
    if "onorm" in m:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * m["onorm"].astype(f32)
    return _gated_out(h, o, m), carry


def _eva_mixer(h, m, cfg: TransformerConfig, attend, pos,
               rope: Optional[RopeSpec]):
    """An EVA mixer around the body's two-grain cache. h [B, W, D] ->
    (y, carry). q, k, v = W h a head; q and k rotated at the rows'
    positions (a key is rotated BEFORE it is pooled, so a cached row,
    exact or summary, never depends on who reads it); `attend(q, k, v)`
    writes the exact rows, pools the chunks these rows complete
    (ops/eva.eva_pool: learned softmax weights over a chunk's rows
    against "phi", the weighted sums of K and V, "mu" added to the
    key) and attends the query's own aligned window of exact rows
    beside the summaries of every window behind it under ONE float32
    softmax; then W_o."""
    b, w, _ = h.shape
    q, k, v = ((h @ _dq(m[n], h)).reshape(b, w, -1, cfg.head_dim)
               for n in ("wq", "wk", "wv"))
    if rope is not None:
        q, k = _rope(q, pos, rope), _rope(k, pos, rope)
    att, carry = attend(q, k, v)
    att = att.reshape(b, w, -1)
    return att @ _dq(m["wo"], att), carry


def _mamba_mixer(h, m, attend):
    """A Mamba-1 mixer around the body's recurrent core. h [B, W, D] ->
    (y [B, W, D], carry). Here: [u; z] = W_in h; after the core the
    gate y * silu(z) in float32 and W_out. The core (`attend(u)` =
    ops/mamba.mamba_mix over the body's state and conv tail:
    convolution with its bias, SiLU, W_x, the three RMSNorms, W_dt,
    softplus, the selective scan, the skip D u) has no positions."""
    c = m["wo"].shape[0]
    uz = h @ _dq(m["win"], h)
    y, carry = attend(uz[..., :c])
    y = (y * jax.nn.silu(uz[..., c:].astype(jnp.float32))).astype(h.dtype)
    return y @ _dq(m["wo"], y), carry


def _kda_mixer(h, m, cfg: TransformerConfig, attend):
    """A Kimi-Delta-Attention mixer around the body's recurrent core.
    h [B, W, D] -> (y [B, W, D], carry). Here: the three streams ahead
    of the convolution, the per-channel log decay g = -exp(A_log) *
    softplus(W_f2 (W_f1 h) + dt_bias) and beta = sigmoid(w_b . h), both
    float32; after the core, RMSNorm over each head with one learned
    scale, the sigmoid output gate, W_o. The core (`attend(pre, g,
    beta)` = ops/kda.kda_mix over the body's state and conv tail:
    convolution, SiLU, L2 norms, the delta rule) has no positions."""
    f32 = jnp.float32
    pre = jnp.einsum("bsd,dchk->bschk", h, _dq(m["wqkv"], h))
    low = lambda w1, w2: jnp.einsum(                        # noqa: E731
        "bsr,rhk->bshk", h @ _dq(w1, h), _dq(w2, h)).astype(f32)
    g = -jnp.exp(m["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        low(m["wf1"], m["wf2"]) + m["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, _dq(m["wb"], h)
                                     ).astype(f32))
    o, carry = attend(pre, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.norm_eps) * m["onorm"].astype(f32)
    o = o * jax.nn.sigmoid(low(m["wg1"], m["wg2"]) + m["bg"].astype(f32))
    o = o.astype(h.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, _dq(m["wo"], o)), carry


def _mla_mixer(h, m, cfg: TransformerConfig, attend, pos=None,
               rope: Optional[RopeSpec] = None):
    """A latent-attention (MLA) mixer in the ABSORBED form. h [B, W, D]
    -> (y, carry). The query is W_q h, or W_uq RMSNorm(W_dq h) where
    the parameters hold a low-rank pair ("wdq"). The cached row of a
    token is [RMSNorm(W_dkv h); k^R; zero pad] (`cfg.mla_row` wide),
    k^R the mla_rope_dim dims ONE rotary key shares between the heads:
    rotated by `rope` at the token's position `pos` BEFORE the row is
    written (a cached row never depends on who reads it), as the
    query's q^R dims are; `rope` None leaves both plain (NoPE). A
    head's query against the row is [W_uk^T q^C; q^R; 0], its score
    q . row * `cfg.mla_scale`, its value the row's first mla_rank
    columns: `attend(q, row)` writes the rows and returns sum_j p_j c_j
    [B, W, H, rank], which W_uv takes to the head's value dims ahead of
    W_o. One form for a decode step and a prefill chunk."""
    r, dn = cfg.mla_rank, cfg.mla_nope_dim
    rms = functools.partial(_head_rms, eps=cfg.norm_eps)
    ckr = h @ _dq(m["wdkv"], h)                             # [B, W, r+dr]
    c = rms(ckr[..., :r], m["kvnorm"])
    if "wdq" in m:
        q = jnp.einsum("bsr,rhk->bshk",
                       rms(h @ _dq(m["wdq"], h), m["qnorm"]),
                       _dq(m["wuq"], h))
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, _dq(m["wq"], h))
    qr, kr = q[..., dn:], ckr[..., r:]
    if rope is not None:
        qr = _rope(qr, pos, rope)
        kr = _rope(kr[:, :, None, :], pos, rope)[:, :, 0]
    qa = jnp.einsum("bshn,rhn->bshr", q[..., :dn], _dq(m["wuk"], h))
    pad = cfg.mla_row - r - cfg.mla_rope_dim
    row = jnp.concatenate(
        [c, kr, jnp.zeros(c.shape[:-1] + (pad,), h.dtype)], -1)
    qf = jnp.concatenate(
        [qa, qr, jnp.zeros(qa.shape[:-1] + (pad,), h.dtype)], -1)
    ol, carry = attend(qf, row)
    o = jnp.einsum("bshr,rhv->bshv", ol, _dq(m["wuv"], ol))
    return jnp.einsum("bshv,hvd->bsd", o, _dq(m["wo"], o)), carry


LATENT_ROWS_A_BLOCK = 512
# the most (batch x head x query) pairs ONE walk of the scratch carries.
# The walk's float32 accumulator [B, H, Q, rank] stays in the chip's
# fast memory up to 16,384 pairs of a 512-wide rank; past it the
# compiler keeps it in HBM and every block reads and writes it whole
# (PR 47: a 256-wide chunk of 128 heads cost three 128-wide ones)
LATENT_PAIRS_A_GROUP = 16384


def latent_groups(batch: int, queries: int, heads: int) -> int:
    """The walks `_latent_attention` makes over its scratch for a query
    of this shape: groups of whole heads, `LATENT_PAIRS_A_GROUP` pairs
    each at most (one head a group where a head alone is more)."""
    return -(-heads // max(1, LATENT_PAIRS_A_GROUP // (batch * queries)))


def _latent_attention(q, lat, qpos, rank: int, scale: float):
    """Absorbed latent attention of q [B, Q, H, R] over a DENSE cache
    of latent rows lat [B, S, R] (this window's rows already written):
    the query at qpos[i] sees rows <= it. Scores and softmax float32,
    the value a row's first `rank` columns (`ops/paged_attention.
    paged_latent_attention` is the same over a paged pool).

    `_latent_walk` over the scratch, a GROUP of heads at a time where
    the query holds more than LATENT_PAIRS_A_GROUP pairs
    (`latent_groups`): each group with its own running state, the
    outputs joined on the head axis (on the chip faster than groups of
    query rows: 64 heads x 256 rows walk in 0.8 of the time of 128
    heads x 128 rows). A head's softmax never reads another head, so
    the result is the one walk's, bit for bit; with one group the
    program is the one walk's too."""
    b, nq, h, _ = q.shape
    qp = jnp.broadcast_to(qpos, (b, nq)) if qpos.ndim == 1 else qpos
    groups = latent_groups(b, nq, h)
    if groups == 1:
        return _latent_walk(q, lat, qp, rank, scale)
    heads = -(-h // groups)
    return jnp.concatenate(
        [_latent_walk(q[:, :, i:i + heads], lat, qp, rank, scale)
         for i in range(0, h, heads)], axis=2)


def _latent_walk(q, lat, qp, rank: int, scale: float):
    """One walk of `_latent_attention`: q [B, Q, H, R] at positions qp
    [B, Q] over lat [B, S, R] in blocks of LATENT_ROWS_A_BLOCK rows
    under an online softmax (running max, sum and a float32 accumulator
    [B, H, Q, rank]) and BOUNDED by the last query's position: no [H,
    Q, S] array exists and no row past the window is scored, so a
    chunk's cost follows the rows it can see, not the scratch's
    length."""
    b, nq, h, _ = q.shape
    s_len = lat.shape[1]
    blk = min(LATENT_ROWS_A_BLOCK, s_len)
    n_blk = jnp.minimum(jnp.max(qp) // blk + 1, -(-s_len // blk))

    def body(j, carry):
        m, l, acc = carry
        # the last block of a scratch that is no whole number of them
        # starts early; its rows below j * blk were the last block's
        start = jnp.minimum(j * blk, s_len - blk)
        rows = jax.lax.dynamic_slice_in_dim(lat, start, blk, axis=1)
        kpos = start + jnp.arange(blk)
        live = jnp.logical_and(kpos[None, None, :] <= qp[..., None],
                               kpos >= j * blk)             # [B, Q, blk]
        s = jnp.einsum("bqhr,bkr->bhqk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhqk,bkr->bhqr", p.astype(q.dtype), rows[..., :rank],
            preferred_element_type=jnp.float32)
        return m_new, l * fade + jnp.sum(p, axis=-1), acc

    # row 0 is live for every query, so the running max is finite from
    # the first block on
    m, l, acc = jax.lax.fori_loop(
        0, n_blk, body,
        (jnp.full((b, h, nq), -jnp.inf, jnp.float32),
         jnp.zeros((b, h, nq), jnp.float32),
         jnp.zeros((b, h, nq, rank), jnp.float32)))
    return jnp.transpose(acc / l[..., None], (0, 2, 1, 3)).astype(q.dtype)


def _cached_attention(q, kc, vc, qpos, window: int = 0):
    """Attention of q [B, Q, Nq, H] over dense caches kc/vc [B, S, Nkv,
    H] (this step's rows already written): the query at position
    qpos[.., i] ([Q], or [B, Q] per row) sees cache positions <= it,
    and on a window layer > it - window. GQA by the grouped reshape;
    masked scores are -inf, softmax in f32."""
    b, sq, nq, hd = q.shape
    nkv = kc.shape[2]
    qg = q.reshape(b, sq, nkv, nq // nkv, hd)
    s = jnp.einsum("bqngh,bknh->bngqk", qg, kc) / math.sqrt(hd)
    kpos = jnp.arange(kc.shape[1])
    live = kpos <= qpos[..., None]                     # [(B,) Q, S]
    if window:
        live = jnp.logical_and(live, kpos > qpos[..., None] - window)
    live = live[None] if live.ndim == 2 else live
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(b, sq, nq, hd)


def _logits(params, x, cfg: TransformerConfig):
    """Final norm and the head (the embedding's transpose when tied):
    [B, S, pred_heads x vocab], every prediction head's logits side by
    side (`_next_logits` keeps the next token's)."""
    x = _norm(x, params["ln_f"], cfg)
    if cfg.logit_scale != 1.0:
        x = x * cfg.logit_scale
    return jnp.einsum(
        "bsd,vd->bsv", x, params.get("head", params["emb"]),
        preferred_element_type=jnp.float32 if cfg.logits_f32 else None)


def _next_logits(logits, cfg: TransformerConfig):
    """Head 0's columns of `_logits`' rows: the NEXT token's logits,
    what a decoder picks from. All of them where the model has one
    prediction head."""
    return logits if cfg.pred_heads == 1 else logits[..., :cfg.vocab]


def _embed(params, toks, cfg: TransformerConfig):
    """The tokens' embedding rows, times the model's `emb_scale`."""
    x = params["emb"][toks]
    return x if cfg.emb_scale == 1.0 else x * cfg.emb_scale


def _block(x, lp, cfg: TransformerConfig, sp_size: int, dp_size: int):
    """One decoder block on a [B/dp, S/sp, D] shard; heads already
    tp-local. The Megatron f/g conjugate pair is implicit: with vma
    tracking on, jax transposes the closing psums and reduces the
    mixed replicated/partial cotangents itself. Returns (x, moe_aux).
    `_layer` with the sp ring as its mixer and the capacity MoE."""
    pos = None
    if cfg.rope:
        # GLOBAL positions from THE layout definition the ring uses
        from ..ops.attention import ring_positions
        pos = ring_positions(jax.lax.axis_index("sp"), sp_size,
                             x.shape[1], cfg.striped_ring)

    def attend(q, k, v):
        # GQA layouts pass straight through: ring_attention_sharded
        # broadcasts grouped K/V itself on the paths that need it
        return ring_attention_sharded(
            q, k, v, "sp", sp_size, causal=True,
            striped=cfg.striped_ring), None

    aux = [jnp.float32(0.0)]

    def moe(h):
        from .moe import moe_ffn
        b, s, d = h.shape
        out, aux[0] = moe_ffn(h.reshape(b * s, d), lp["moe"],
                              _moe_cfg(cfg), axis="dp",
                              axis_size=dp_size)
        # experts' d_ff is tp-sharded
        return jax.lax.psum(out, "tp").reshape(b, s, d)

    x, _ = _layer(x, lp, cfg, 0, pos, attend, "tp", moe)
    return x, aux[0]


def _nll_head(params, x, targets):
    """ln_f + tied-embedding loss head on a [B, S, D] shard; returns
    (nll_sum, count).

    -log p[target] = logsumexp(row) - logits[target]. The target
    logit is recomputed as a row-wise dot against the gathered
    embedding instead of take_along_axis over the [B,S,V] tensor —
    the full-vocab array feeds ONLY the logsumexp reduction (which
    XLA fuses into the matmul consumer), saving a GB-scale gather
    read per step at V=32k. The dot runs in the logits' dtype so both
    terms see the same rounding (a f32 recompute against bf16 logits
    would make near-deterministic tokens go slightly negative)."""
    x = _ln(x, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["emb"])
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.einsum("bsd,bsd->bs", x, params["emb"][targets]
                     ).astype(jnp.float32)
    nll = lse - tgt
    return nll.sum(), nll.size


def _local_loss(params, tokens, targets, cfg: TransformerConfig,
                sp_size: int, dp_size: int = 1):
    """Shard-local token loss SUM, count, and MoE aux sum (psum'd by
    the caller)."""
    x = params["emb"][tokens]              # [B/dp, S/sp, D]
    aux = jnp.float32(0.0)
    block = functools.partial(_block, cfg=cfg, sp_size=sp_size,
                              dp_size=dp_size)
    if cfg.remat:
        block = jax.checkpoint(block)
    for lp in params["layers"]:
        x, a = block(x, lp)
        aux = aux + a
    s, n = _nll_head(params, x, targets)
    return s, n, aux


# ---------------------------------------------------------------------------
# the training step (one sharded XLA program)
# ---------------------------------------------------------------------------

def make_train_step(cfg: TransformerConfig, mesh, optimizer: Any = None):
    """Returns a jitted train step over the (dp, sp, tp) mesh.

    optimizer=None: plain SGD — step(params, tokens, targets) ->
    (params, loss).

    optimizer=<optax GradientTransformation>: step(params, opt_state,
    tokens, targets) -> (params, opt_state, loss); the opt state is
    sharded LIKE the params (tp-sharded moments for tp-sharded weights),
    initialize it with `optimizer.init` on the sharded params OUTSIDE
    the step (its sharding follows the params') — see
    make_opt_state().
    """
    cfg.only("make_train_step: the sp ring and the capacity MoE",
             "models/transformer.py")
    sp_size = mesh.shape["sp"]
    dp_size = mesh.shape["dp"]
    tp_size = mesh.shape["tp"]
    if cfg.n_heads % tp_size or cfg.kv_heads % tp_size:
        raise ValueError(
            f"heads (q={cfg.n_heads}, kv={cfg.kv_heads}) must divide by "
            f"tp={tp_size} (MQA under tp needs n_kv_heads >= tp)")
    pspecs = param_specs(cfg)
    data_spec = P("dp", "sp")

    def loss_of(params, tokens, targets):
        s, n, aux = _local_loss(params, tokens, targets, cfg, sp_size,
                                dp_size)
        total = jax.lax.psum(s, ("dp", "sp"))
        count = jax.lax.psum(jnp.float32(n), ("dp", "sp"))
        loss = total / count
        if cfg.n_experts > 0:
            # mean the router load-balance term the same way as the nll
            aux_m = jax.lax.psum(aux, ("dp", "sp")) / (
                dp_size * sp_size * cfg.n_layers)
            loss = loss + cfg.moe_aux_weight * aux_m
        return loss

    # vma (varying-manual-axes) tracking is ON: jax's AD knows each
    # param enters invariant (replicated) over the axes its spec omits,
    # and automatically psums cotangents over exactly the axes they
    # vary on — dp/sp data partials AND the Megatron tp mixed-
    # replication case (residual replicated, attention/MLP partial)
    # come out correctly reduced with no manual psums.
    if optimizer is None:
        def step(params, tokens, targets):
            loss, grads = jax.value_and_grad(loss_of)(
                params, tokens, targets)
            new_params = jax.tree.map(
                lambda p, g: p - cfg.lr * g.astype(p.dtype),
                params, grads)
            return new_params, loss

        prog = shard_map(step, mesh=mesh,
                         in_specs=(pspecs, data_spec, data_spec),
                         out_specs=(pspecs, P()))
        return _jit_maybe_striped(prog, cfg, sp_size)

    ospecs = _opt_state_specs(cfg, optimizer)

    def step_opt(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        new_params = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params, updates)
        return new_params, opt_state, loss

    prog_opt = shard_map(
        step_opt, mesh=mesh,
        in_specs=(pspecs, ospecs, data_spec, data_spec),
        out_specs=(pspecs, ospecs, P()))
    return _jit_maybe_striped(prog_opt, cfg, sp_size)


def _jit_maybe_striped(prog, cfg: TransformerConfig, sp_size: int):
    """jit `prog`, striping the LAST TWO args (tokens, targets) over
    the sp ring first when cfg.striped_ring — one wrapper for the SGD
    and optimizer step shapes so the two paths cannot diverge."""
    if not (cfg.striped_ring and sp_size > 1):
        return jax.jit(prog)
    from ..ops.attention import stripe_sequence

    def outer(*args):
        head, (tokens, targets) = args[:-2], args[-2:]
        return prog(*head, stripe_sequence(tokens, sp_size),
                    stripe_sequence(targets, sp_size))

    return jax.jit(outer)


def _opt_state_specs(cfg: TransformerConfig, optimizer: Any):
    """PartitionSpecs for an optax state: param-shaped subtrees
    (momentum/second moment) take the param's spec; scalar bookkeeping
    (step counts) is replicated. optax.tree_map_params knows which
    state leaves are param-like — shape matching would be ambiguous
    (e.g. w1/w2 share a shape when d_model == d_ff but have transposed
    tp specs)."""
    import optax
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    state_shape = jax.eval_shape(lambda p: optimizer.init(p), params)
    pspecs = param_specs(cfg)
    return optax.tree_map_params(
        optimizer, lambda _leaf, spec: spec, state_shape, pspecs,
        transform_non_params=lambda _leaf: P())


# ---------------------------------------------------------------------------
# pipeline-parallel training step (the pp axis, in one sharded program)
# ---------------------------------------------------------------------------

def stack_pipeline_params(params) -> Dict[str, Any]:
    """Restack the per-layer param list into leading-axis arrays so the
    layer dimension can shard over the "pp" mesh axis (each stage holds
    n_layers/pp layers and scans over them locally)."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return {"emb": params["emb"], "ln_f": params["ln_f"],
            "layers": stacked}


def _interleave_order(n_layers: int, pp: int, v: int):
    """Layer permutation for the interleaved schedule: device d's
    contiguous pp-slab holds its round-robin stage chunks
    [d, d+pp, d+2*pp, ...] (stage s = chunk*pp + d, chunk-major within
    the slab)."""
    if v < 1 or n_layers % (pp * v):
        raise ValueError(
            f"n_layers={n_layers} not divisible by pp*interleave="
            f"{pp}*{v}")
    ls = n_layers // (pp * v)
    order = []
    for d in range(pp):
        for chunk in range(v):
            s = chunk * pp + d
            order.extend(range(s * ls, (s + 1) * ls))
    return order


def interleave_pipeline_params(stacked, pp: int, v: int):
    """Reorder the stacked layer axis for make_pipelined_train_step's
    interleave=v schedule (identity when v == 1)."""
    if v == 1:
        return stacked
    order = jnp.asarray(_interleave_order(
        jax.tree.leaves(stacked["layers"])[0].shape[0], pp, v))
    return {**stacked,
            "layers": jax.tree.map(lambda a: a[order],
                                   stacked["layers"])}


def deinterleave_pipeline_params(stacked, pp: int, v: int):
    """Inverse of interleave_pipeline_params (back to layer order)."""
    if v == 1:
        return stacked
    n = jax.tree.leaves(stacked["layers"])[0].shape[0]
    order = _interleave_order(n, pp, v)
    inv = [0] * n
    for i, o in enumerate(order):
        inv[o] = i
    inv = jnp.asarray(inv)
    return {**stacked,
            "layers": jax.tree.map(lambda a: a[inv], stacked["layers"])}


def pipelined_param_specs(tp_axis: Optional[str] = None, *,
                          gqa: bool = False) -> Dict[str, Any]:
    """Specs for stacked params: layer axis over "pp", heads/ffn over
    tp (when present), embedding/final-norm replicated. (Dense blocks
    only — make_pipelined_train_step rejects MoE configs.)"""
    t = tp_axis
    if gqa:
        qkv = {"wq": P("pp", None, t, None),
               "wkv": P("pp", None, None, t, None)}
    else:
        qkv = {"wqkv": P("pp", None, None, t, None)}
    layer = {
        "ln1": P("pp", None),
        **qkv,
        "wo": P("pp", t, None, None),
        "ln2": P("pp", None),
        "w1": P("pp", None, t),
        "b1": P("pp", t),
        "w2": P("pp", t, None),
    }
    return {"emb": P(), "ln_f": P(), "layers": layer}


def shard_pipeline_params(stacked, mesh):
    tp_axis = "tp" if "tp" in mesh.axis_names else None
    gqa = "wq" in stacked["layers"]
    return _place(stacked, pipelined_param_specs(tp_axis, gqa=gqa), mesh)


def prepare_pipeline_params(params, mesh, interleave: int = 1):
    """One-stop: stack the per-layer list, apply the interleaved layer
    permutation when interleave > 1, and place on the mesh. Use this
    with make_pipelined_train_step(..., interleave=V) — the layer
    LAYOUT must match the step's interleave or training silently runs
    a layer-permuted network (nothing in the arrays records the
    layout, so the pairing is the API's job; this helper makes the
    pairing a single argument)."""
    pp = mesh.shape["pp"]
    stacked = interleave_pipeline_params(
        stack_pipeline_params(params), pp, interleave)
    return shard_pipeline_params(stacked, mesh)


def _pp_block(x, lp, cfg: TransformerConfig, tp_axis: Optional[str]):
    """One decoder block on a [mb, S, D] microbatch shard inside the
    pipeline: attention is sequence-LOCAL (auto_attention — flash on
    TPU; the sp ring belongs to the dp x sp x tp step), heads/ffn
    tp-sharded when a tp axis exists. `_layer` with flash as mixer."""
    return _layer(
        x, lp, cfg, 0, jnp.arange(x.shape[1]),   # sequence is pp-local
        lambda q, k, v: (auto_attention(q, k, v, causal=True), None),
        tp_axis)[0]


def _pipelined_opt_state_specs(cfg: TransformerConfig, optimizer: Any,
                               tp_axis: Optional[str]):
    """Opt-state specs for the STACKED layout (mirrors
    _opt_state_specs: param-shaped moments take the param's spec)."""
    import optax
    stacked = jax.eval_shape(
        lambda: stack_pipeline_params(
            init_params(cfg, jax.random.PRNGKey(0))))
    state_shape = jax.eval_shape(lambda p: optimizer.init(p), stacked)
    pspecs = pipelined_param_specs(
        tp_axis, gqa=cfg.kv_heads != cfg.n_heads)
    return optax.tree_map_params(
        optimizer, lambda _leaf, spec: spec, state_shape, pspecs,
        transform_non_params=lambda _leaf: P())


def make_pipelined_opt_state(stacked, cfg: TransformerConfig, mesh,
                             optimizer: Any):
    """optimizer.init under jit with shardings matching the stacked
    layout (moments pp/tp-sharded like their weights)."""
    from jax.sharding import NamedSharding
    tp_axis = "tp" if "tp" in mesh.axis_names else None
    ospecs = _pipelined_opt_state_specs(cfg, optimizer, tp_axis)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(optimizer.init, out_shardings=shardings)(stacked)


def make_pipelined_train_step(cfg: TransformerConfig, mesh,
                              n_microbatches: int,
                              optimizer: Any = None,
                              interleave: int = 1):
    """Train step with pipeline parallelism INSIDE the jitted program:
    layers shard over the mesh's "pp" axis (stacked leading dim),
    microbatches hand off stage-to-stage via one lax.ppermute hop per
    scan step (parallel/pipeline_spmd.pipeline_run), batch shards over
    "dp", heads/ffn over "tp" when present. AD through the scan IS the
    backward pipeline (ppermute transposes to the inverse rotation).

    Params must be in the STACKED layout (stack_pipeline_params +
    shard_pipeline_params). step(params, tokens, targets) ->
    (params, loss) with plain-SGD update, matching make_train_step's
    optimizer=None contract.

    striped_ring is not wired here (no sp axis to stripe) and raises.

    The schedule stashes final-stage outputs into an [M, ...] buffer
    and runs the loss head ONCE per device after the scan; the only
    dead head work is that single post-scan pass on the pp-1 non-last
    devices (their buffers are zeros, masked out of the psum). MoE
    configs take the dp/ep step instead (expert all_to_all inside a
    pipeline stage would deadlock against the pp ppermute schedule if
    capacity buffers ever shard over dp x pp jointly).

    interleave=V > 1 runs the INTERLEAVED schedule (virtual stages,
    pipeline_run_interleaved): pp*V stages round-robin over devices,
    each scan step computing one 1/(pp*V) layer chunk — bubble
    (pp-1)/(M*V + pp-1) instead of (pp-1)/(M + pp-1); M must divide by
    pp. Params must be in the MATCHING interleaved layout — build them
    with prepare_pipeline_params(params, mesh, interleave=V) (updates
    come back in that layout; invert with
    deinterleave_pipeline_params).
    """
    cfg.only("make_pipelined_train_step: stacked identical layers",
             "models/transformer.py")
    if cfg.striped_ring:
        raise NotImplementedError(
            "striped_ring is wired for make_train_step's sp ring; the "
            "pipelined step has no sp axis to stripe")
    if cfg.n_experts > 0:
        raise NotImplementedError(
            "pipeline-parallel MoE is not supported; use make_train_step "
            "with the dp/ep layout")
    from ..parallel.pipeline_spmd import (pipeline_run,
                                          pipeline_run_interleaved)
    from ..ops.attention import _pvary

    axes = mesh.axis_names
    if "pp" not in axes or "dp" not in axes:
        raise ValueError(f"mesh must carry ('dp', 'pp'); has {axes}")
    tp_axis = "tp" if "tp" in axes else None
    pp, dp = mesh.shape["pp"], mesh.shape["dp"]
    V = interleave
    if V < 1 or cfg.n_layers % (pp * V):
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"pp*interleave={pp}*{V}")
    if tp_axis:
        tp_size = mesh.shape["tp"]
        if cfg.n_heads % tp_size or cfg.kv_heads % tp_size:
            raise ValueError(
                f"heads (q={cfg.n_heads}, kv={cfg.kv_heads}) must "
                f"divide by tp={tp_size}")
    M = n_microbatches
    pspecs = pipelined_param_specs(
        tp_axis, gqa=cfg.kv_heads != cfg.n_heads)
    data_spec = P("dp", None)

    def loss_of(params, tokens, targets):
        bl, s = tokens.shape
        if bl % M:
            raise ValueError(f"per-dp-shard batch {bl} not divisible "
                             f"by n_microbatches={M}")
        mb = bl // M
        toks = tokens.reshape(M, mb, s)
        tgts = targets.reshape(M, mb, s)

        block = jax.checkpoint(
            lambda x, lp: _pp_block(x, lp, cfg, tp_axis))

        def chunk_apply(lg, x):
            x, _ = jax.lax.scan(
                lambda x, lp: (block(x, lp), None), x, lg)
            return x

        def feed(t):
            return params["emb"][toks[t]].astype(cfg.dtype)

        # collect STASHES the final-stage outputs into an [M, ...]
        # buffer; the loss head (a full [*, vocab] matmul + logsumexp)
        # then runs ONCE per device after the scan instead of at every
        # schedule step — in-scan heads would multiply dead masked
        # work by the step count (x V^2 relative to useful compute on
        # the interleaved schedule)
        def collect(buf, y, t_out, valid):
            upd = jax.lax.dynamic_update_index_in_dim(
                buf, y.astype(buf.dtype), t_out, 0)
            return jnp.where(valid, upd, buf)

        vary = ("dp", "pp")
        buf0 = _pvary(jnp.zeros((M, mb, s, cfg.d_model), cfg.dtype),
                      vary)
        if V == 1:
            x0 = _pvary(jnp.zeros((mb, s, cfg.d_model), cfg.dtype), vary)
            buf = pipeline_run(
                "pp", pp, M, lambda x: chunk_apply(params["layers"], x),
                feed, collect, buf0, x0)
        else:
            ls_per = cfg.n_layers // (pp * V)
            lgroups = jax.tree.map(
                lambda a: a.reshape((V, ls_per) + a.shape[1:]),
                params["layers"])

            def stage_fn(v, x):
                # v is a traced per-device chunk index: dynamic_index
                # (not lax.switch — SPMD would run all V branches)
                lg = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, v, 0, keepdims=False), lgroups)
                return chunk_apply(lg, x)

            x0 = _pvary(jnp.zeros((V, mb, s, cfg.d_model), cfg.dtype),
                        vary)
            buf = pipeline_run_interleaved(
                "pp", pp, V, M, stage_fn, feed, collect, buf0, x0)
        ssum, n = _nll_head(params, buf.reshape(M * mb, s, cfg.d_model),
                            tgts.reshape(M * mb, s))
        w = (jax.lax.axis_index("pp") == pp - 1).astype(jnp.float32)
        # n is a static size: w*n varies over pp only — add the missing
        # dp variance before the joint psum (w*ssum already has both:
        # ssum derives from the dp-sharded targets)
        cnt = _pvary(w * jnp.float32(n), ("dp",))
        return jax.lax.psum(w * ssum, ("dp", "pp")) \
            / jax.lax.psum(cnt, ("dp", "pp"))

    if optimizer is None:
        def step(params, tokens, targets):
            loss, grads = jax.value_and_grad(loss_of)(
                params, tokens, targets)
            new_params = jax.tree.map(
                lambda p, g: p - cfg.lr * g.astype(p.dtype), params, grads)
            return new_params, loss

        return jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, data_spec, data_spec),
            out_specs=(pspecs, P())))

    ospecs = _pipelined_opt_state_specs(cfg, optimizer, tp_axis)

    def step_opt(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        new_params = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params, updates)
        return new_params, opt_state, loss

    return jax.jit(shard_map(
        step_opt, mesh=mesh,
        in_specs=(pspecs, ospecs, data_spec, data_spec),
        out_specs=(pspecs, ospecs, P())))


def _block_decode(x, lp, kv, write_at, cfg: TransformerConfig,
                  tp_axis: Optional[str] = None,
                  ep_axis: Optional[str] = None, ep_size: int = 1,
                  li: int = 0, valid=None):
    """Layer `li` for a WINDOW of new token positions with a dense KV
    cache: `_layer` with the cache as its mixer. x: [B, W, D] (W = 1
    plain decode; W > 1 speculative verification / chunked prefill:
    token i of the window sits at write_at + i); kv: (k_cache,
    v_cache) each [B, Smax, N, H] (N = the tp-LOCAL head count under
    sharded decode; GQA caches hold only the kv heads); write_at:
    scalar index. The cache stores POST-rope k, so cached entries never
    need re-rotation. With tp_axis set, the wo/w2 contractions close
    with a psum, so the KV cache shards over heads and never
    replicates. A window layer masks what lies behind its window; the
    dense cache keeps every row all the same. A "kda" layer's `kv` is
    (state, conv tail) and `valid` the count of the window's real
    columns (the rest is bucket padding, which a recurrent state must
    not consume; None = all); an "mla" layer's is (latent rows [B,
    Smax, 1, R],); a "sparse" layer's the K/V pair (its index is the
    means of K's rows: nothing more is kept here), each row choosing
    the blocks it attends; a "lightning" layer's (state,), `valid` as
    for "kda"; a "mamba" layer's (state [B, N, C], conv tail [B, (K -
    1) C]), `valid` alike; an "eva" layer's (exact K, exact V [B,
    eva_window, N, H]: a ring, row r the newest position = r mod the
    window; summary K, summary V [B, chunks, N, H]), `valid` the
    columns that may be WRITTEN (padding must not wrap onto live
    rows)."""
    qpos = jnp.asarray(write_at) + jnp.arange(x.shape[1])

    def attend(q, k, v):
        kc = jax.lax.dynamic_update_slice_in_dim(kv[0], k, write_at,
                                                 axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(kv[1], v, write_at,
                                                 axis=1)
        return _cached_attention(q, kc, vc, qpos, cfg.window(li)), \
            (kc, vc)

    if "kda" in lp:
        from ..ops.kda import kda_mix

        def attend(pre, g, beta):                           # noqa: F811
            return kda_mix(pre, g, beta, lp["kda"]["conv"], *kv,
                           valid=valid)
    elif "sparse" in lp:
        from ..ops.sparse_attention import chunk_attention

        def attend(q, k, v):                                # noqa: F811
            kc = jax.lax.dynamic_update_slice_in_dim(kv[0], k, write_at,
                                                     axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(kv[1], v, write_at,
                                                     axis=1)
            return chunk_attention(q, kc, vc, qpos, cfg.sparse_spec)[0], \
                (kc, vc)
    elif "lightning" in lp:
        from ..ops.lightning import lightning_mix

        def attend(q, k, v):                                # noqa: F811
            return lightning_mix(q, k, v, cfg.lightning_decay(li), *kv,
                                 valid=valid)
    elif "mamba" in lp:
        from ..ops.mamba import mamba_mix

        def attend(u):                                      # noqa: F811
            return mamba_mix(u, lp["mamba"], *kv, valid=valid,
                             eps=cfg.norm_eps)
    elif "eva" in lp:
        from ..ops.eva import eva_window_attend

        def attend(q, k, v):                                # noqa: F811
            return eva_window_attend(
                q, k, v, kv, write_at, valid, lp["eva"]["phi"],
                lp["eva"]["mu"], cfg.eva_chunk, cfg.eva_window)
    elif "mla" in lp:
        def attend(q, row):                                 # noqa: F811
            lat = jax.lax.dynamic_update_slice_in_dim(
                kv[0], row[:, :, None, :], write_at, axis=1)
            return _latent_attention(q, lat[:, :, 0], qpos, cfg.mla_rank,
                                     cfg.mla_scale), (lat,)

    def moe(h):
        # serving routes DROP-FREE: with no drops, each token's output
        # is independent of the rest of the batch — generating a prompt
        # alone or inside a batch yields identical tokens, and the
        # serving path never silently zeroes a token the way
        # capacity-limited training legitimately does
        from .moe import moe_ffn_decode, moe_ffn_serve
        b, s, d = h.shape
        if ep_axis is not None:
            # expert-parallel decode: experts shard over ep_axis; the
            # replicated token block splits across it and the outputs
            # close with a psum (moe_ffn_decode) — capacity dispatch
            # made drop-free by capacity_factor = n_experts
            mcfg = dataclasses.replace(
                _moe_cfg(cfg), capacity_factor=float(cfg.n_experts))
            out = moe_ffn_decode(h.reshape(b * s, d), lp["moe"], mcfg,
                                 ep_axis, ep_size)[0]
        else:
            out = moe_ffn_serve(h.reshape(b * s, d), lp["moe"],
                                _moe_cfg(cfg))[0]
        return out.reshape(b, s, d)

    return _layer(x, lp, cfg, li, qpos, attend, tp_axis, moe)


def _decode_forward(params, caches, tok, pos, cfg, tp_axis=None,
                    ep_axis=None, ep_size=1):
    """One decode token through every block: the W == 1 case of
    _decode_window, so there is exactly ONE copy of the cached forward
    — any change to it lands in generate(), beam_search(), and both
    phases of speculative_generate(). Returns (caches, f32 logits
    [B, V])."""
    caches, logits = _decode_window(params, caches, tok[:, None], pos,
                                    cfg, tp_axis=tp_axis,
                                    ep_axis=ep_axis, ep_size=ep_size)
    return caches, logits[:, 0, :]


def _decode_window(params, caches, toks, pos0, cfg, tp_axis=None,
                   ep_axis=None, ep_size=1, need_logits=True, valid=None):
    """A WINDOW of new tokens through the cached blocks in one pass:
    toks [B, W] at positions pos0..pos0+W-1. Returns (caches, f32
    logits [B, W, V]). One MXU-batched forward where a scan would run
    W sequential steps — the speculative-verification / chunked-prefill
    fast path (every weight is read once per window instead of once per
    token, which is the whole memory-bandwidth case for speculative
    decoding). `valid`: how many of the W columns are real, for the
    layers whose state must not consume padding (see `_block_decode`).

    need_logits=False is the cache-only prefill: no final ln, no [B, W,
    V] unembedding, and the compiler drops the LAST layer behind its
    cache write (its `wo` and FFN are no arguments of the program).
    Returns (caches, row): the hidden row [B, 1, D] of the last real
    column (`valid - 1`; the last where `valid` is None) as it ENTERS
    the last layer, which `_window_tail` takes on to that column's
    logits: taken ahead of the layer, it keeps nothing of it alive. A
    last layer of a recurrent kind has consumed the column and cannot
    run it again: there the row is the one BEHIND the layer, which then
    runs whole."""
    x = _embed(params, toks, cfg)
    new_caches = []
    for li, (lp, kv) in enumerate(zip(params["layers"], caches)):
        ahead = x
        x, kv = _block_decode(x, lp, kv, pos0, cfg, tp_axis=tp_axis,
                              ep_axis=ep_axis, ep_size=ep_size, li=li,
                              valid=valid)
        new_caches.append(kv)
    if need_logits:
        return new_caches, _logits(params, x, cfg).astype(jnp.float32)
    if cfg.mixer(cfg.n_layers - 1) not in RECURRENT_KINDS:
        x = ahead
    col = x.shape[1] - 1 if valid is None else valid - 1
    return new_caches, jax.lax.dynamic_slice_in_dim(x, col, 1, axis=1)


def _window_tail(params, row, kv, pos, cfg):
    """The rest of the forward for the row a cache-only `_decode_window`
    handed back, the column at position `pos`: the LAST layer on that
    one row over its cache entry `kv` (an idempotent rewrite of the
    entry's row `pos`: the window wrote it already), final ln and the
    head. `params["layers"]` may hold the last layer alone. A last
    layer of a recurrent kind is behind the row: ln and head alone, `kv`
    as it came. Returns (kv, f32 logits [B, V])."""
    last = cfg.n_layers - 1
    if cfg.mixer(last) not in RECURRENT_KINDS:
        row, kv = _block_decode(row, params["layers"][-1], kv, pos, cfg,
                                li=last)
    return kv, _logits(params, row, cfg)[:, 0].astype(jnp.float32)


# CHUNK tokens per prefill window: large enough that every weight read
# amortizes over a full MXU tile of tokens, small enough that the
# transient per-chunk [B, CHUNK, V] logits (last chunk only) and [B,
# CHUNK, S] attention scores stay modest at long prompts
_PREFILL_CHUNK = 128


def _prefill_window(params, cfg, caches, prompt, tp_axis=None,
                    ep_axis=None, ep_size=1,
                    chunk: int = _PREFILL_CHUNK, need_logits=True,
                    logits0=None):
    """Feed the prompt into the caches in windowed one-pass chunks
    (chunked prefill): each chunk of up to `chunk` tokens is ONE
    _decode_window forward — every weight is read once per chunk
    instead of once per token, the classic prefill-vs-decode
    distinction. Returns (caches, logits after the LAST prompt token);
    intermediate chunks run cache-only, as does everything when
    need_logits=False (a draft model's prefill never reads logits).
    `logits0` is the empty-prompt fallback result (callers build it
    with the right sharding/vma). Shared by generate(), beam_search(),
    and speculative_generate()."""
    plen = prompt.shape[1]
    last = logits0[:, None] if logits0 is not None else None
    for s in range(0, plen, chunk):
        e = min(plen, s + chunk)
        caches, lg = _decode_window(params, caches, prompt[:, s:e], s,
                                    cfg, tp_axis=tp_axis,
                                    ep_axis=ep_axis, ep_size=ep_size,
                                    need_logits=need_logits
                                    and e == plen)
        if need_logits and e == plen:
            last = lg
    return caches, (last[:, -1] if need_logits else None)


def generate(params, cfg: TransformerConfig, prompt: jax.Array,
             max_new: int = 32, mesh=None, temperature: float = 0.0,
             top_k: int = 0, eos_id: Optional[int] = None,
             key: Optional[jax.Array] = None) -> jax.Array:
    """Decode: prefill the prompt token-by-token into KV caches, then
    emit max_new tokens. Static shapes throughout (lax.scan over cache
    positions) — one compile per (prompt_len, max_new).

    temperature=0 (default): greedy argmax. temperature>0: sample from
    softmax(logits/temperature), truncated to the top_k logits when
    top_k>0 (pass `key`). Sampling keys fold in the GLOBAL batch row
    and position, so sharded and single-device runs draw identical
    tokens. eos_id: rows that emit it keep emitting it (done rows
    still compute — static shapes — but their output is pinned).

    mesh=None: single device. Otherwise a Mesh with axes ("dp", "tp")
    (either size may be 1) runs SHARDED serving as one program: batch
    over dp, attention heads + ffn + KV caches over tp (Megatron decode
    — caches never replicate), params placed by shard_params, prompt
    sharded [dp, None]. MoE models decode EXPERT-PARALLEL: experts
    shard over tp (or a dedicated "ep" mesh axis), routing drop-free
    through moe_ffn_decode's all_to_all exchange — token-identical to
    the single-device MoE path."""
    cfg.kv_pairs_only("generate: the dense K/V caches",
                      "models/transformer.py")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 needs a PRNG key")
    if temperature <= 0.0 and (top_k > 0 or key is not None):
        raise ValueError(
            "top_k/key have no effect at temperature=0 (greedy); pass "
            "temperature > 0 to sample")
    from ..ops.attention import _pvary

    b, plen = prompt.shape
    smax = plen + max_new
    nh, hd = cfg.n_heads, cfg.head_dim
    tp = dp = 1
    tp_axis = None
    ep_axis, ep_size = None, 1
    if mesh is not None:
        dp, tp = _decode_mesh_check(cfg, mesh, b)
        tp_axis = "tp"       # size-1 tp: the psums are no-ops
        ep_axis, ep_size = _decode_ep(cfg, mesh)

    def fresh_cache(b_local, nh_local):
        caches = [(jnp.zeros((b_local, smax, nh_local, hd), cfg.dtype),
                   jnp.zeros((b_local, smax, nh_local, hd), cfg.dtype))
                  for _ in range(cfg.n_layers)]
        if mesh is not None:
            # zeros are axis-invariant; the scanned k/v updates vary
            # over dp (batch) and tp (heads) — match the carry's vma
            caches = jax.tree.map(lambda z: _pvary(z, ("dp", "tp")),
                                  caches)
        return caches

    def select(logits, pos, b_local, karg):
        """Next token from [B_local, V] logits at position `pos`.
        `karg` is the PRNG key as a TRACED argument — baking it into
        the closure would force a recompile per key."""
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        raw = logits.astype(jnp.float32)
        if top_k > 0:
            # top-k set is scale-invariant: mask the raw logits, the
            # shared sampler scales after
            thr = jax.lax.top_k(raw, top_k)[0][..., -1:]
            raw = jnp.where(raw < thr, -jnp.inf, raw)
        # keys fold in (position, GLOBAL row): sharded == single-device
        base = (jax.lax.axis_index("dp") * b_local if mesh is not None
                else 0)
        return jax.vmap(
            lambda row_logits, r: _sample_row(row_logits, temperature,
                                              karg, pos, r))(
            raw, base + jnp.arange(b_local))

    def forward_token(params, caches, tok, pos):
        return _decode_forward(params, caches, tok, pos, cfg,
                               tp_axis=tp_axis, ep_axis=ep_axis,
                               ep_size=ep_size)

    def step_token(params, karg, carry, inp):
        caches, _prev = carry
        tok, pos = inp
        caches, logits = forward_token(params, caches, tok, pos)
        nxt = select(logits, pos, tok.shape[0], karg)
        return (caches, nxt), nxt

    def run(params, prompt, karg):
        b_local = prompt.shape[0]
        caches = fresh_cache(b_local, cfg.kv_heads // tp)
        # chunked prefill: windowed one-pass forwards at positions
        # 0..plen-1; selection happens once afterwards on the last
        # position's logits. logits0 covers the empty-prompt edge
        # (unconditional generation: argmax/sample over zeros).
        logits0 = jnp.zeros((b_local, cfg.vocab), jnp.float32)
        if mesh is not None:
            logits0 = _pvary(logits0, ("dp",))
        caches, last_logits = _prefill_window(params, cfg, caches,
                                              prompt, tp_axis=tp_axis,
                                              ep_axis=ep_axis,
                                              ep_size=ep_size,
                                              logits0=logits0)
        # t0 = the prediction following the last prompt token, drawn at
        # position plen-1 (same key fold the in-scan path would use)
        tok0 = select(last_logits, plen - 1, b_local, karg)
        step = functools.partial(step_token, params, karg)
        # decode: feed back the selected token; each step emits the
        # token it FEEDS — emitting the step's own prediction instead
        # would drop t0 and shift the whole output by one.
        done0 = jnp.zeros((b_local,), jnp.bool_)
        if mesh is not None:
            done0 = _pvary(done0, ("dp",))

        def gen(carry, pos):
            caches, tok, done = carry
            if eos_id is not None:
                tok = jnp.where(done, jnp.int32(eos_id),
                                tok.astype(jnp.int32))
            (caches, nxt), _ = step((caches, tok), (tok, pos))
            if eos_id is not None:
                done = jnp.logical_or(done, tok == eos_id)
            return (caches, nxt, done), tok

        _carry, toks = jax.lax.scan(
            gen, (caches, tok0, done0), jnp.arange(plen, smax))
        return toks.T                                  # [B_local, max_new]

    karg = key if key is not None else jax.random.PRNGKey(0)
    ck = ("generate", cfg, b, plen, max_new, temperature, top_k,
          eos_id, mesh, _tree_key(params))
    if mesh is None:
        prog = _cached_program(ck, lambda: jax.jit(run))
        return prog(params, prompt, karg)

    from jax.sharding import NamedSharding
    data_spec = P("dp", None)

    def build():
        # scales follow channels; experts take the decode layout
        pspecs = _decode_pspecs(params, cfg, mesh)
        return jax.jit(shard_map(
            run, mesh=mesh,
            in_specs=(pspecs, data_spec, P()),
            out_specs=data_spec))

    prog = _cached_program(ck, build)
    prompt = jax.device_put(prompt, NamedSharding(mesh, data_spec))
    return prog(params, prompt, karg)


# Compiled serving programs, keyed by everything the traced closures
# BAKE IN (config, shapes, decode options, mesh, param-tree structure).
# Without this, every generate()/beam_search()/speculative_* call
# builds a fresh closure and jit RETRACES — repeated serving calls pay
# a full compile each time. jit still retraces internally if the traced
# ARG shapes change under one cache key, so the key only needs the
# closure constants.
_PROGRAMS: Dict[Any, Any] = {}


def _cached_program(key_, build):
    from ..core.programs import cached_program
    return cached_program(_PROGRAMS, key_, build)


def _tree_key(tree) -> Any:
    return jax.tree_util.tree_structure(tree)



def _sample_row(logits_row, temperature, key, pos, row):
    """THE per-row sampling contract every decoder shares (generate's
    select, the continuous-batching server's step and admission):
    temperature-scale, fold (position, row) into the key, categorical.
    Keeping one copy is what makes 'batched == solo' token equality a
    theorem rather than a hope."""
    k = jax.random.fold_in(jax.random.fold_in(key, pos), row)
    return jax.random.categorical(
        k, logits_row.astype(jnp.float32) / temperature)


def _pick_row(logits_row, key, temperature, pos):
    """Greedy-or-sampled next token for ONE batch row — the serving
    wrapper of the `_sample_row` contract: argmax at temperature 0,
    the shared categorical draw otherwise (row index pinned to 0: the
    server keys are folded per slot, so the batch row carries no
    entropy). The speculative-verify window picks its targets with
    this exact function at each window position, which is what makes
    acceptance collapse to exact token match: the window's position-p
    pick IS the token the sequential step program would have emitted
    at p."""
    sampled = _sample_row(logits_row, jnp.maximum(temperature, 1e-6),
                          key, pos, 0)
    return jnp.where(temperature > 0, sampled,
                     jnp.argmax(logits_row))


def _decode_ep(cfg: TransformerConfig, mesh):
    """Expert axis for sharded MoE decode: the dedicated "ep" mesh
    axis when the mesh declares one, otherwise experts ride "tp".
    Returns (axis_name, axis_size); (None, 1) for dense models or no
    mesh."""
    if mesh is None or cfg.n_experts <= 0:
        return None, 1
    name = "ep" if "ep" in mesh.axis_names else "tp"
    return name, mesh.shape[name]


def _decode_mesh_check(cfg: TransformerConfig, mesh, batch: int):
    """Shared decode-mesh contract for generate()/
    speculative_generate, and for ContinuousServer — dense AND paged
    (slots play the batch role there): ("dp","tp") axes, heads/batch
    divisible. MoE models decode EXPERT-PARALLEL: experts shard over
    "tp" (or a dedicated "ep" axis when the mesh declares one), token
    routing rides moe_ffn's tiled all_to_all, and n_experts must
    divide the expert axis. Returns (dp, tp)."""
    cfg.only("sharded decode: the (dp, tp) placement and the "
             "expert-parallel capacity MoE", "models/transformer.py")
    names = mesh.axis_names
    if "dp" not in names or "tp" not in names:
        raise ValueError(f"decode mesh needs ('dp','tp'); has {names}")
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    if cfg.n_heads % tp or cfg.kv_heads % tp:
        raise ValueError(
            f"heads (q={cfg.n_heads}, kv={cfg.kv_heads}) not divisible "
            f"by tp={tp}")
    if batch % dp:
        raise ValueError(f"batch {batch} not divisible by dp={dp}")
    if cfg.n_experts > 0:
        ep_axis, ep = _decode_ep(cfg, mesh)
        if cfg.n_experts % ep:
            raise ValueError(
                f"n_experts ({cfg.n_experts}) not divisible by "
                f"{ep_axis}={ep}; shrink {ep_axis} to a divisor of "
                f"n_experts, or declare a dedicated 'ep' mesh axis "
                f"that divides it")
    return dp, tp


def _decode_pspecs(params, cfg: TransformerConfig, mesh=None):
    """Param specs for sharded decode; quantized targets (int8 or
    packed int4) place scales with their channels. MoE experts take
    the DECODE layout — experts over the expert axis (_decode_ep),
    each expert's d_ff UNSHARDED: the training layout's tp split of
    d_ff can't compose with experts occupying tp, and the decode close
    is already the psum over the expert axis."""
    from .quant import QTensor, QTensor4, quantized_bits
    quant = any(isinstance(x, (QTensor, QTensor4))
                for x in jax.tree.leaves(
                    params,
                    is_leaf=lambda x: isinstance(x, (QTensor,
                                                     QTensor4))))
    if quant:
        from .quant import quantized_param_specs
        bits = quantized_bits(params)
        specs = quantized_param_specs(cfg, bits)
    else:
        specs = param_specs(cfg)
    if cfg.n_experts > 0:
        from .moe import moe_param_specs
        ep_axis = _decode_ep(cfg, mesh)[0] or "tp"
        m = moe_param_specs(ep_axis, tp_axis=None)
        if quant:
            # scales keep size-1 contract axes (already unsharded in
            # the decode layout), so their spec matches the weight's
            from .quant import _MOE_CONTRACT_AXES, _MOE_PACK_AXES
            for mn in _MOE_CONTRACT_AXES:
                m[mn] = (QTensor4(m[mn], m[mn], _MOE_PACK_AXES[mn])
                         if bits == 4 else QTensor(q=m[mn], s=m[mn]))
        for lp in specs["layers"]:
            lp["moe"] = dict(m)
    return specs




def _pin_after_eos(out, eos_id):
    """Pin every position AFTER a row's first eos to eos — the same
    observable behavior as generate()'s done-row pinning (a finished
    row keeps emitting eos), applied as a post-pass so the speculative
    loops stay eos-free inside."""
    hit = (out == eos_id)
    after = jnp.cumsum(hit.astype(jnp.int32), axis=1) >= 1
    prev = jnp.concatenate(
        [jnp.zeros_like(after[:, :1]), after[:, :-1]], axis=1)
    return jnp.where(prev, jnp.int32(eos_id), out)


def _accept_scatter(out, m, a, emis, k, max_new):
    """Shared accept-and-emit step for both speculative decoders: write
    emissions 0..a at columns m..m+a of `out` (the max_new sentinel
    index + mode='drop' is the out-of-bounds clamp), return the new
    cursor token and advanced count. emis: [B, k+1]."""
    idx = m + jnp.arange(k + 1)
    valid = (jnp.arange(k + 1) <= a) & (idx < max_new)
    idx_safe = jnp.where(valid, idx, max_new)      # max_new: dropped
    out = out.at[:, idx_safe].set(
        jnp.where(valid[None, :], emis, 0), mode="drop")
    cur = jnp.take(emis, a, axis=1)
    return out, cur, jnp.minimum(m + a + 1, max_new)


def speculative_generate(params, cfg: TransformerConfig,
                         draft_params, draft_cfg: TransformerConfig,
                         prompt: jax.Array, max_new: int = 32,
                         k: int = 4, mesh=None,
                         eos_id: Optional[int] = None,
                         return_stats: bool = False) -> jax.Array:
    """Greedy speculative decoding (Leviathan et al. shape, greedy
    acceptance): a small DRAFT model proposes k tokens autoregressively,
    the target model scores all k+1 positions in ONE window forward
    (_decode_window — each target weight is read once per window instead
    of once per token, which is the whole memory-bandwidth win), and the
    longest agreeing prefix is accepted plus the target's own token at
    the first disagreement. Every emitted token comes from the TARGET's
    argmax, so the output matches generate(temperature=0) up to
    floating-point argmax ties: the window and sequential forwards
    reassociate sums (~1e-4 logit difference), so a position whose
    top-2 target logits are closer than that can resolve either way —
    the draft still never changes which DISTRIBUTION tokens come from.

    Batches accept the MINIMUM agreement count across rows each round
    (per-row counts would need per-row cache positions): correct for
    every row — tokens below the minimum agree everywhere, and the
    bonus token equals the draft token on rows that agreed further —
    at reduced speedup for large batches. Greedy only; models must
    share the vocab (sizes may differ otherwise).

    mesh=None runs single-device. A Mesh(("dp","tp")) runs the same
    sharded-serving layout as generate() (MoE targets run
    expert-parallel over tp/ep; the draft is replicated); the
    row-agreement minimum is then PER dp SHARD, and
    each shard's decode loop runs its own trip count — with
    return_stats the per-row rounds report their shard's count.

    Cache staleness note: rejected draft entries stay in the caches
    PAST the accepted position; they are harmless because the next
    round rewrites positions sequentially from the rewound cursor and
    the causal mask never lets a query see beyond its own position."""
    for c in (cfg, draft_cfg):
        c.kv_pairs_only("speculative_generate: the dense K/V caches",
                        "models/transformer.py")
    if k < 1:
        raise ValueError(f"speculative_generate: k must be >= 1, got {k}")
    if draft_cfg.vocab != cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}")
    if max_new <= 0:
        empty = prompt[:, :0].astype(jnp.int32)
        return (empty, 0) if return_stats else empty

    from ..ops.attention import _pvary

    b, plen = prompt.shape
    # target windows start at plen+m-1 (m <= max_new-1) and span k+1
    smax = plen + max_new + k

    tp_size = 1
    tp_axis = None
    ep_axis, ep_size = None, 1
    if mesh is not None:
        # same mesh contract as generate() (dp x tp; MoE targets run
        # expert-parallel over tp or a dedicated ep axis). The DRAFT
        # is replicated (small by construction; each tp rank drafts
        # redundantly and identically). Acceptance is per-dp-shard
        # local, so the while_loop trip counts legitimately DIVERGE
        # across dp shards — no collective crosses dp inside the loop,
        # and tp groups stay in lockstep because their logits are
        # psum-complete (expert psums included).
        _dp_size, tp_size = _decode_mesh_check(cfg, mesh, b)
        tp_axis = "tp"
        ep_axis, ep_size = _decode_ep(cfg, mesh)

    def fresh(c: TransformerConfig, b_local, nh_local, axes):
        caches = [(jnp.zeros((b_local, smax, nh_local, c.head_dim),
                             c.dtype),
                   jnp.zeros((b_local, smax, nh_local, c.head_dim),
                             c.dtype))
                  for _ in range(c.n_layers)]
        if mesh is not None:
            caches = jax.tree.map(lambda z: _pvary(z, axes), caches)
        return caches

    def run(tgt, dft, prompt):
        b_local = prompt.shape[0]
        logits0 = jnp.zeros((b_local, cfg.vocab), jnp.float32)
        if mesh is not None:
            logits0 = _pvary(logits0, ("dp",))
        t_caches = fresh(cfg, b_local, cfg.kv_heads // tp_size,
                         ("dp", "tp"))
        d_caches = fresh(draft_cfg, b_local, draft_cfg.kv_heads,
                         ("dp",))
        t_caches, t_last = _prefill_window(tgt, cfg, t_caches, prompt,
                                           tp_axis=tp_axis,
                                           ep_axis=ep_axis,
                                           ep_size=ep_size,
                                           logits0=logits0)
        # draft prefill is cache-only: its prompt logits are never read
        d_caches, _ = _prefill_window(dft, draft_cfg, d_caches,
                                      prompt, need_logits=False)
        tok0 = jnp.argmax(t_last, axis=-1).astype(jnp.int32)
        out = jnp.zeros((b_local, max_new),
                        jnp.int32).at[:, 0].set(tok0)

        def cond(carry):
            return carry[0] < max_new

        def body(carry):
            m, cur, out, t_caches, d_caches, rounds = carry
            pos0 = plen + m - 1          # cur's sequence position

            def dstep(c, j):
                dc, tok = c
                dc, lg = _decode_forward(dft, dc, tok, pos0 + j,
                                         draft_cfg)
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (dc, nxt), nxt

            # k+1 steps, not k: the extra step feeds d_{k-1} so ITS KV
            # lands at pos0+k — on a fully-accepted round the next
            # round resumes past that slot, and a skipped write would
            # leave a permanent zero-KV hole every later draft query
            # attends (silently collapsing acceptance rates; outputs
            # would stay correct, which is why only this comment and
            # the hole test notice). The k+1-th PROPOSAL is discarded.
            (d_caches, _), d = jax.lax.scan(
                dstep, (d_caches, cur), jnp.arange(k + 1))
            d = d.T[:, :k]                             # [B, k]
            window = jnp.concatenate([cur[:, None], d], axis=1)
            t_caches, lg = _decode_window(tgt, t_caches, window, pos0,
                                          cfg, tp_axis=tp_axis,
                                          ep_axis=ep_axis,
                                          ep_size=ep_size)
            t = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [B, k+1]
            # longest all-rows-agree prefix; +1 bonus from the target.
            # Every EMITTED token is t[:, j]: for j < a the draft
            # agreed (d == t there by definition of a), at j == a it is
            # the target's correction — so the scatter writes t itself.
            matches = (d == t[:, :k]).astype(jnp.int32)
            a = jnp.cumprod(matches, axis=1).sum(axis=1).min()
            out, cur, m = _accept_scatter(out, m, a, t, k, max_new)
            return (m, cur, out, t_caches, d_caches, rounds + 1)

        m0, r0 = jnp.asarray(1), jnp.asarray(0)
        if mesh is not None:
            # per-dp-shard loop state (trip counts may diverge)
            m0, r0 = _pvary(m0, ("dp",)), _pvary(r0, ("dp",))
        carry = (m0, tok0, out, t_caches, d_caches, r0)
        fin = jax.lax.while_loop(cond, body, carry)
        toks = fin[2] if eos_id is None else _pin_after_eos(fin[2],
                                                            eos_id)
        # rounds = target window forwards run: the efficiency metric —
        # a healthy draft takes ~ceil((max_new-1)/(k+1)), a degraded
        # one (e.g. a KV hole) collapses toward max_new-1. Sharded:
        # reported per ROW (each row carries its dp shard's count).
        if not return_stats:
            return toks
        rounds = fin[5]
        if mesh is not None:
            rounds = jnp.broadcast_to(rounds, (b_local,))
        return toks, rounds

    ck = ("spec_gen", cfg, draft_cfg, b, plen, max_new, k, mesh,
          eos_id, return_stats, _tree_key(params),
          _tree_key(draft_params))
    if mesh is None:
        prog = _cached_program(ck, lambda: jax.jit(run))
        return prog(params, draft_params, prompt)

    from jax.sharding import NamedSharding
    data_spec = P("dp", None)

    def build():
        pspecs = _decode_pspecs(params, cfg, mesh)
        dspecs = jax.tree.map(lambda _: P(), draft_params)
        out_spec = (data_spec, P("dp")) if return_stats else data_spec
        return jax.jit(shard_map(
            run, mesh=mesh,
            in_specs=(pspecs, dspecs, data_spec),
            out_specs=out_spec))

    prog = _cached_program(ck, build)
    prompt = jax.device_put(prompt, NamedSharding(mesh, data_spec))
    return prog(params, draft_params, prompt)



def speculative_sample(params, cfg: TransformerConfig,
                       draft_params, draft_cfg: TransformerConfig,
                       prompt: jax.Array, max_new: int = 32,
                       k: int = 4, temperature: float = 1.0,
                       key: Optional[jax.Array] = None,
                       eos_id: Optional[int] = None,
                       return_stats: bool = False) -> jax.Array:
    """SAMPLED speculative decoding — the exact acceptance-rejection
    algorithm (speculative sampling): draft j proposes d_j ~ q_j, the
    target scores the window in one forward, d_j is accepted with
    probability min(1, p_j(d_j)/q_j(d_j)), and the first rejection
    resamples from norm(relu(p_a - q_a)). The emitted sequence is
    distributed EXACTLY as sampling the target alone (the residual
    construction cancels the draft's bias; with q padded to zero past
    the proposals, the all-accepted bonus draw from p_k is the same
    formula). Each round folds its round index into the PRNG key, so a
    position redrafted after a rejection gets FRESH randomness — key
    reuse across rounds would correlate draws and break exactness.

    Single device, batch == 1 (the latency-sensitive single-stream
    case: per-row acceptance counts would need per-row cache
    positions). Greedy/batched/sharded speculation: see
    speculative_generate."""
    for c in (cfg, draft_cfg):
        c.kv_pairs_only("speculative_sample: the dense K/V caches",
                        "models/transformer.py")
    if key is None:
        raise ValueError("speculative_sample needs a PRNG key")
    if temperature <= 0.0:
        raise ValueError(
            "speculative_sample is the sampled algorithm; temperature "
            "must be > 0 (greedy: speculative_generate)")
    if prompt.shape[0] != 1:
        raise ValueError(
            f"speculative_sample is single-stream (batch == 1); got "
            f"batch {prompt.shape[0]}")
    if k < 1:
        raise ValueError(f"speculative_sample: k must be >= 1, got {k}")
    if draft_cfg.vocab != cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}")
    if max_new <= 0:
        empty = prompt[:, :0].astype(jnp.int32)
        return (empty, 0) if return_stats else empty

    plen = prompt.shape[1]
    smax = plen + max_new + k
    V = cfg.vocab

    def fresh(c: TransformerConfig):
        return [(jnp.zeros((1, smax, c.kv_heads, c.head_dim), c.dtype),
                 jnp.zeros((1, smax, c.kv_heads, c.head_dim), c.dtype))
                for _ in range(c.n_layers)]

    def probs(logits):
        return jax.nn.softmax(logits.astype(jnp.float32) / temperature,
                              axis=-1)

    def run(tgt, dft, prompt, karg):
        t_caches, t_last = _prefill_window(
            tgt, cfg, fresh(cfg), prompt,
            logits0=jnp.zeros((1, V), jnp.float32))
        d_caches, _ = _prefill_window(dft, draft_cfg, fresh(draft_cfg),
                                      prompt, need_logits=False)
        tok0 = jax.random.categorical(
            jax.random.fold_in(karg, 0),
            t_last[0] / temperature).astype(jnp.int32)[None]
        out = jnp.zeros((1, max_new), jnp.int32).at[:, 0].set(tok0)

        def cond(carry):
            return carry[0] < max_new

        def body(carry):
            m, cur, out, t_caches, d_caches, rounds = carry
            pos0 = plen + m - 1
            kr = jax.random.fold_in(karg, rounds + 1)  # fresh per round

            def dstep(c, j):
                dc, tok = c
                dc, lg = _decode_forward(dft, dc, tok, pos0 + j,
                                         draft_cfg)
                nxt = jax.random.categorical(
                    jax.random.fold_in(jax.random.fold_in(kr, 1), j),
                    lg[0] / temperature).astype(jnp.int32)[None]
                return (dc, nxt), (nxt, lg)

            # k+1 steps: the extra one lands d_{k-1}'s KV (see
            # speculative_generate's KV-hole note); its proposal and
            # logits are discarded
            (d_caches, _), (dtoks, dlogits) = jax.lax.scan(
                dstep, (d_caches, cur), jnp.arange(k + 1))
            d = dtoks[:k, 0]                           # [k]
            q = probs(dlogits[:k, 0])                  # [k, V]

            window = jnp.concatenate([cur[:, None], d[None, :]], axis=1)
            t_caches, lg = _decode_window(tgt, t_caches, window, pos0,
                                          cfg)
            p = probs(lg[0])                           # [k+1, V]

            pd = p[jnp.arange(k), d]
            qd = q[jnp.arange(k), d]
            u = jax.random.uniform(jax.random.fold_in(kr, 2), (k,))
            accept = u < jnp.minimum(1.0, pd / qd)
            a = jnp.where(accept.all(), k,
                          jnp.argmin(accept))         # first rejection
            # rejection resample from norm(relu(p_a - q_a)); with q
            # padded to a zero row at k, a == k (all accepted) makes
            # the SAME formula the bonus draw from p_k
            q_pad = jnp.concatenate([q, jnp.zeros((1, V))], axis=0)
            resid = jnp.maximum(p[a] - q_pad[a], 0.0)
            z = resid.sum()
            dist = jnp.where(z > 0, resid / jnp.maximum(z, 1e-30), p[a])
            e_a = jax.random.categorical(
                jax.random.fold_in(kr, 3),
                jnp.log(dist)).astype(jnp.int32)
            d_pad = jnp.concatenate([d, jnp.zeros(1, jnp.int32)])
            emis = jnp.where(jnp.arange(k + 1) < a, d_pad, e_a)
            out, cur, m = _accept_scatter(out, m, a, emis[None, :], k,
                                          max_new)
            return (m, cur, out, t_caches, d_caches, rounds + 1)

        carry = (jnp.asarray(1), tok0, out, t_caches, d_caches,
                 jnp.asarray(0))
        fin = jax.lax.while_loop(cond, body, carry)
        toks = fin[2] if eos_id is None else _pin_after_eos(fin[2],
                                                            eos_id)
        return (toks, fin[5]) if return_stats else toks

    ck = ("spec_sample", cfg, draft_cfg, plen, max_new, k, temperature,
          eos_id, return_stats, _tree_key(params),
          _tree_key(draft_params))
    prog = _cached_program(ck, lambda: jax.jit(run))
    return prog(params, draft_params, prompt, key)


def beam_search(params, cfg: TransformerConfig, prompt: jax.Array,
                max_new: int = 32, beam_width: int = 4,
                return_all: bool = False):
    """Beam-search decode (single device): keep the beam_width highest
    total-log-probability continuations per row. Static shapes: the
    prompt prefills once at batch B, then beams run flat at B*W with
    per-step cache reordering (gather by surviving parent). Returns
    the best [B, max_new] sequences, or (tokens [B, W, max_new],
    scores [B, W]) sorted best-first when return_all.

    beam_width=1 reproduces greedy decode exactly. No eos handling —
    beams run to max_new (finished-hypothesis freezing composes with
    this scheme but is not wired)."""
    cfg.kv_pairs_only("beam_search: the dense K/V caches",
                      "models/transformer.py")
    if beam_width < 1:
        raise ValueError("beam_width >= 1")
    b, plen = prompt.shape
    w = beam_width
    smax = plen + max_new
    hd = cfg.head_dim

    def run(params, prompt):
        nkv = cfg.kv_heads
        caches = [(jnp.zeros((b, smax, nkv, hd), cfg.dtype),
                   jnp.zeros((b, smax, nkv, hd), cfg.dtype))
                  for _ in range(cfg.n_layers)]

        caches, logits = _prefill_window(
            params, cfg, caches, prompt,
            logits0=jnp.zeros((b, cfg.vocab), jnp.float32))

        # tile beams: all start identical; only beam 0 is live so the
        # duplicates can't multiply into the topk
        caches = jax.tree.map(lambda a: jnp.repeat(a, w, axis=0), caches)
        scores = jnp.full((b, w), -jnp.inf).at[:, 0].set(0.0)
        logits = jnp.repeat(logits, w, axis=0)          # [B*W, V]
        hist = jnp.zeros((b, w, max_new), jnp.int32)

        def step(carry, t):
            caches, scores, hist, logits = carry
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32)).reshape(b, w, cfg.vocab)
            cand = scores[:, :, None] + logp            # [B, W, V]
            top, idx = jax.lax.top_k(cand.reshape(b, -1), w)
            parent = idx // cfg.vocab                   # [B, W]
            tok = (idx % cfg.vocab).astype(jnp.int32)
            flat_parent = (jnp.arange(b)[:, None] * w + parent
                           ).reshape(-1)
            caches = jax.tree.map(lambda a: a[flat_parent], caches)
            hist = jnp.take_along_axis(hist, parent[..., None], axis=1)
            hist = jax.lax.dynamic_update_index_in_dim(
                hist, tok, t, axis=2)
            caches, logits = _decode_forward(
                params, caches, tok.reshape(-1), plen + t, cfg)
            return (caches, top, hist, logits), None

        (caches, scores, hist, _), _ = jax.lax.scan(
            step, (caches, scores, hist, logits), jnp.arange(max_new))
        order = jnp.argsort(-scores, axis=1)
        hist = jnp.take_along_axis(hist, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        return hist, scores

    ck = ("beam", cfg, b, plen, max_new, w, _tree_key(params))
    prog = _cached_program(ck, lambda: jax.jit(run))
    hist, scores = prog(params, prompt)
    if return_all:
        return hist, scores
    return hist[:, 0, :]


def make_opt_state(params, cfg: TransformerConfig, mesh, optimizer: Any):
    """optimizer.init under jit with sharded outputs matching
    _opt_state_specs (so moments are tp-sharded like their weights)."""
    from jax.sharding import NamedSharding
    ospecs = _opt_state_specs(cfg, optimizer)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(optimizer.init, out_shardings=shardings)(params)
