"""Decoder-only language models — the family behind ``map_score_lm``.

One file for the family, not one a checkpoint (ROADMAP.md D1): a block is
described by its LAYER TYPE — pre-norm residual block, RMS norm, rotary
positions, a ``mixer`` that mixes along the sequence and returns what enters
the residual (its projections and out-projection are its own), a SwiGLU
feed-forward — and the
``mixer`` key names which sequence mixer the type runs (``MIXERS``), and the
feed-forward is chosen by the layer's place (``n_dense_layers`` leading SwiGLU
layers, then expert layers when ``n_experts`` is set). A document longer than
one program runs as fixed-shape SEGMENTS with the mixer's state handed from
one to the next (:func:`forward_segment`):

- ``power_retention`` (gated power retention of degree 2,
  ``kernels/power_retention.py``) carries a FIXED-SIZE state along the
  sequence (``None`` before the first segment);
- ``sparse_mla`` (latent attention under a learned top-k key selection,
  ``kernels/sparse_mla.py``) carries a CACHE that grows with position: one
  latent vector (``kv_lora_rank + qk_rope_head_dim`` numbers) and one index
  key (``index_head_dim``) a token a layer, allocated at the document's
  padded length (:func:`init_state`), written in place, read up to the
  segment's last token;
- ``hybrid_ssm`` (a Mamba-2 state-space scan, ``kernels/ssd.py``, and causal
  grouped-query softmax attention, ``kernels/causal_attention.py``, side by
  side: both read the one normed input and their outputs are summed into
  the residual, each through its own out-projection) carries TWO KINDS of
  state a layer: a key and value cache that grows with position (2 x
  ``n_kv_heads x d_head`` numbers a token, allocated as ``sparse_mla``'s
  is) and a fixed-size float32 scan state with the convolution's last
  ``ssm_d_conv - 1`` inputs. The block's muP multipliers (``*_multiplier``)
  are applied where the published forward pass applies them;
- ``dense_mla`` (latent attention over EVERY causal key: ``sparse_mla``'s
  projections, norms and rotary positions through the one
  :func:`_latent_projections`, no indexer and no selection; a query scaled
  by its own position, ``query_scale_beta``) carries a cache of latents
  ONLY, ``kv_lora_rank + qk_rope_head_dim`` numbers a token a layer; a
  segment expands the latents it can see into per-head joined keys ``[c W_UK
  | kR]`` and values for one layer at a time
  (``kernels/sparse_mla.py: expand_latents``) and attends them with
  ``kernels/causal_attention.py`` at one query head a key head;
- ``window_gqa`` (plain grouped-query softmax attention with a per-head RMS
  norm on queries and keys) is the one mixer whose layers come in KINDS
  (:func:`layer_kinds`): of every ``full_attention_every`` layers the last
  attends every causal key under YaRN's rotary table and factor, the others
  the last ``sliding_window`` keys under the plain table. Both kinds hold
  the same leaves, stacked as every layer is; the layer scan steps over
  PERIODS and its body runs a period's layers one after another. The state
  has two shapes side by side: a full layer's keys and values at the
  document's padded length, a window layer's LAST ``sliding_window`` only
  (a segment attends ``[the carried tail | its own keys]`` and hands on the
  last ``sliding_window`` of them).

- ``hybrid_kda`` (of every ``layer_group_size`` layers the last is latent
  attention over every causal key, ``dense_mla``'s without a query rank and
  with an output gate a head; the others are LINEAR attention, a gated delta
  rule with a decay a channel behind a short causal convolution,
  ``kernels/kda.py``) is the mixer whose kinds hold DIFFERENT LEAVES: a
  group's mixer leaves are stacked BY KIND (``params[group]["mixers"][kind]``)
  beside the feed-forward's, which every layer holds alike, and the period
  scan's body takes each layer's mixer leaves from its kind's stack. The
  state is two unlike kinds too: a linear layer's float32 ``[H, d, d]`` state
  and its convolution's last inputs, of fixed size; a latent layer's cache at
  the document's padded length. Leading dense layers are linear layers.
- ``conv_gqa`` (double-gated SHORT CONVOLUTIONS, ``[B | C | z] = h W_in``,
  ``C x conv(B x z)`` over ``conv_taps`` taps a channel, and among them
  grouped-query attention layers, ``window_gqa``'s full kind under the plain
  rotary table, the one function) is the mixer whose pattern of kinds is the
  MODEL'S OWN (``layer_types``, one kind a layer): a stacked group may begin
  anywhere in a period and the leading dense layers have the kind the
  pattern gives them (:func:`kinds_by_layer`). Two unlike states: a ``conv``
  layer's is the last ``conv_taps - 1`` rows of ``B x z`` and nothing that
  grows; an attention layer's key and value cache is the scan's carry, and
  at heads of HALF a lane tile (64) it holds two key-value heads side by
  side on a row's lanes (``kernels/causal_attention.py: cache_rows``).

Layers are stacked by group (the leading dense layers, then the expert
layers) and each group is scanned; embedding and output head are untied. A
key and value cache that grows with the document (``hybrid_ssm``'s, the full
layers' of ``window_gqa``: ``MIXER_CACHES``) is the scan's CARRY, all its
layers in one stack: the mixer writes a segment's keys and values into the
stack at the layer's number and the attention kernel reads that layer's
tiles out of it, so no layer's cache is ever sliced out of the state or
copied back (:func:`_caches_apart`). An
expert layer routes over all ``n_experts`` and computes the experts it HOLDS
(``n_experts_held`` from ``expert_first``: one chip's share of an
expert-parallel deployment, ``models/moe.py``).

Weights are STORED in the compute dtype (bf16) and made ON THE DEVICE, leaf
by leaf, by one jitted initializer from the model id (:func:`init_params`):
at the published widths eight layers and the vocabulary are 4.2 G parameters
— 8.4 GB in bf16, and 16.8 GB (more than a chip holds, and minutes of host
time) if built on the host in float32 as the encoder families are.

The weight rule (also written, independently, in the benchmark's reference):
root key = ``layers.seed_from(model_id)``; leaf ``j`` of ``LEAVES`` draws
from ``fold_in(root, j)``, a per-layer leaf for layer ``i`` (counted over
both groups) from ``fold_in(fold_in(root, j), i)``, expert ``e`` (its id among
all ``n_experts``) of that layer from one more ``fold_in(., e)``; a standard
normal in float32 times ``1/sqrt(fan_in)`` (embedding: 1), rounded once to the
stored dtype. Norm weights are 1 (``window_gqa``'s per-head query norm:
``QUERY_NORM_GAIN``), the router's bias and the index keys' LayerNorm bias 0;
``hybrid_kda``'s ``A_log`` and ``dt_bias`` give head ``j`` of ``H`` a memory of
``16 x 128^(j / (H - 1))`` tokens where the input says nothing
(:func:`kda_gate_constants`). ``hybrid_ssm`` multiplies a leaf's ``1/sqrt(fan_in)`` by a
gain (:func:`_leaf_gains`: the inverse of the multipliers on the leaf's
output, 4 on the queries) and sets the scan's ``A_log``, ``D`` and ``dt_bias``
by rule (:func:`_layer_constants`). New leaves are APPENDED to ``LEAVES``: a
model made before keeps its keys. The gate's bias gives key-value head ``b`` a memory of
``16 * 2**b`` tokens: ``log(16 * 2**b - 1)`` (see ``GATE_TAU0``).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.models import layers
from agent_tpu.models.layers import Params
from agent_tpu.obs.trace import part, record_caches_in_place

# Leaves that draw random numbers, in the order that keys them.
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down",
          # sparse_mla: query down / up, latent down / up, the indexer's
          "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          # expert layers: router, shared expert, the routed experts held
          "w_router", "ws_gate", "ws_up", "ws_down",
          "we_gate", "we_up", "we_down",
          # hybrid_ssm: the scan's in / out projections, the convolution
          "w_ssm_in", "w_ssm_out", "conv_w", "conv_b",
          # hybrid_kda: a linear layer's beta, decay and output-gate maps
          "w_beta", "w_a", "w_og",
          # conv_gqa: a conv layer's in-projection [B | C | z]
          "w_conv_in")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
# The layer leaves a quantized mode replaces (``models.quant``): projections
# (the router's among them), feed-forwards and experts; the retention gate
# the indexer's head weights and the convolution stay.
LINEAR_LEAVES = ("wq", "wk", "wv", "wo", "w_conv_in", "w_gate", "w_up",
                 "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k",
                 "w_router", "ws_gate", "ws_up", "ws_down") + EXPERT_LEAVES + (
                     "w_ssm_in", "w_ssm_out", "w_a")
# Which leaves a layer holds, by its mixer (and, where a mixer's kinds hold
# different leaves, by the layer's kind) and by its feed-forward.
MIXER_LEAVES = {
    "power_retention": ("wq", "wk", "wv", "wo", "wg"),
    "sparse_mla": ("wo", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k",
                   "wi_w"),
    "dense_mla": ("wo", "w_dq", "w_uq", "w_dkv", "w_ukv"),
    "hybrid_ssm": ("wq", "wk", "wv", "wo", "w_ssm_in", "w_ssm_out", "conv_w",
                   "conv_b"),
    "window_gqa": ("wq", "wk", "wv", "wo"),
    "hybrid_kda": {
        "linear": ("wq", "wk", "wv", "wo", "conv_w", "w_beta", "w_a", "w_og"),
        "latent": ("wq", "wo", "w_dkv", "w_ukv", "w_og")},
    "conv_gqa": {
        "conv": ("w_conv_in", "wo", "conv_w"),
        "full": ("wq", "wk", "wv", "wo")},
}
FFN_LEAVES = {
    "dense": ("w_gate", "w_up", "w_down"),
    "experts": ("w_router", "ws_gate", "ws_up", "ws_down") + EXPERT_LEAVES,
}
# sigmoid(log(tau - 1)) = 1 - 1/tau: head b forgets over tau0 * 2**b tokens.
GATE_TAU0 = 16.0
# hybrid_ssm's weight rule: a query's scores spread over this many standard
# deviations (see the module docstring), and the steps the heads' ``dt_bias``
# is spaced over.
QUERY_GAIN = 4.0
SSM_DT_RANGE = (0.001, 0.1)
# window_gqa's weight rule: the weight of the per-head RMS norm on the
# queries (every other norm's is 1). Normed queries and keys score with this
# spread (times YaRN's factor squared, 1.63, on a full layer): at 1 a softmax
# over a thousand keys averages them and attention enters the residual at a
# twentieth; at ``QUERY_GAIN``'s 4 one key takes a query's weight, bf16's
# rounding of a score decides WHICH, and twelve such layers in bf16 leave the
# float32 reference by 0.02 nats a token a block, as far as a layer that
# dropped its window does (PERF.md section 6, PR 42).
QUERY_NORM_GAIN = 2.0
# Tokens a loss block: the op reports the log-probability summed a block.
LOSS_BLOCK = 1024
# Vocabulary rows a block of the loss head: [segment, VOCAB_BLOCK] float32
# logits are the only logits that ever exist.
VOCAB_BLOCK = 4096


@dataclass(frozen=True)
class DecoderLMConfig:
    """Defaults are a tiny model for tests; the published sizes come in as
    ``model_config`` (benchmarks/configs/brumby-14b-base.json)."""

    vocab_size: int = 512
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 2
    d_head: int = 32
    d_ff: int = 256
    n_layers: int = 2
    max_len: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    mixer: str = "power_retention"
    dtype: str = "bfloat16"
    quant: str = "none"
    # sparse_mla (``n_heads`` heads; ``d_head`` / ``n_kv_heads`` unused):
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    index_n_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 16
    # YaRN (``rope_factor`` 1: plain rotary positions).
    rope_factor: float = 1.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    # ``dense_mla``: the query at position t is scaled by ``1 + beta x ln(1 +
    # floor(t / rope_original_max_len))`` (0: no such scale).
    query_scale_beta: float = 0.0
    # Expert layers (``n_experts`` 0: every layer's FFN is the dense SwiGLU).
    n_dense_layers: int = 1
    n_experts: int = 0
    n_experts_held: int = 0
    expert_first: int = 0
    n_experts_per_token: int = 8
    n_expert_groups: int = 8
    n_groups_per_token: int = 4
    d_expert: int = 64
    n_shared_experts: int = 1
    routed_scale: float = 2.5
    # How the router scores: ``sigmoid`` (group-limited, a choice bias) or
    # ``softmax`` (over all experts, no groups): ``models/moe.py``.
    scoring_func: str = "sigmoid"
    # hybrid_ssm (``n_heads`` query over ``n_kv_heads`` key-value heads of
    # ``d_head``, and beside them a Mamba-2 scan: ``ssm_n_heads`` heads of
    # ``ssm_d_head`` in ``ssm_n_groups`` groups that share B and C):
    ssm_n_heads: int = 4
    ssm_d_head: int = 16
    ssm_d_state: int = 16
    ssm_n_groups: int = 2
    ssm_d_conv: int = 4
    ssm_chunk: int = 128
    # The multipliers of a muP-parametrized forward pass, each applied where
    # the published pass applies it (1: none). ``ssm_multipliers`` and
    # ``mlp_multipliers`` are lists where published: seven scalars here,
    # because every field goes into hashed keys (``_model_common.cfg_key``).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_z_multiplier: float = 1.0
    ssm_x_multiplier: float = 1.0
    ssm_b_multiplier: float = 1.0
    ssm_c_multiplier: float = 1.0
    ssm_dt_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    # window_gqa: of every ``full_attention_every`` layers the last attends
    # every causal key (rotary positions under YaRN: the ``rope_*`` keys
    # above, its factor on queries AND keys), the others the last
    # ``sliding_window`` keys (the plain table of ``rope_theta``).
    sliding_window: int = 1024
    full_attention_every: int = 4
    # hybrid_kda: of every ``layer_group_size`` layers the last is latent
    # attention (``n_heads`` heads, the latent sizes above, NO query rank),
    # the others linear attention (``n_heads`` heads of ``d_head``, a causal
    # convolution of ``kda_conv`` on q, k and v, a log-decay a channel a
    # token never under ``kda_lower_bound``).
    layer_group_size: int = 6
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # The clamp on a SwiGLU's two inputs, a layer (over ``n_layers``; ``()``:
    # none anywhere, 0: none on that layer): ``silu(min(gate, L)) x
    # clip(up, -L, L)``, for the routed experts and for the shared expert.
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    # conv_gqa: the kind of EVERY layer, the model's own pattern (``conv``: a
    # double-gated causal convolution of ``conv_taps`` taps a channel;
    # ``full``, or the published ``full_attention``: ``n_heads`` query over
    # ``n_kv_heads`` key-value heads of ``d_head``, every causal key).
    layer_types: Tuple[str, ...] = ()
    conv_taps: int = 3

    def __post_init__(self):
        # A payload's JSON lists: every field goes into hashed keys.
        for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
            object.__setattr__(self, name, tuple(
                float(v) for v in getattr(self, name)))
        object.__setattr__(self, "layer_types", tuple(
            "full" if kind == "full_attention" else str(kind)
            for kind in self.layer_types))

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def ssm_parts(self) -> Tuple[Tuple[int, float], ...]:
        """``(columns, multiplier)`` of the scan's in-projection, in its
        order: z, x, B, C, dt."""
        d_ssm = self.ssm_n_heads * self.ssm_d_head
        bc = self.ssm_n_groups * self.ssm_d_state
        return ((d_ssm, self.ssm_z_multiplier), (d_ssm, self.ssm_x_multiplier),
                (bc, self.ssm_b_multiplier), (bc, self.ssm_c_multiplier),
                (self.ssm_n_heads, self.ssm_dt_multiplier))

    @property
    def ssm_in_dim(self) -> int:
        """Columns of the scan's in-projection: [z | x | B | C | dt]."""
        return sum(n for n, _ in self.ssm_parts)

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the scan's convolution runs over: [x | B | C]."""
        return sum(n for n, _ in self.ssm_parts[1:4])

    @property
    def layer_groups(self) -> Tuple[Tuple[str, str, int, int], ...]:
        """``(params key, feed-forward, first layer, layers)`` per stacked
        group: the leading dense layers, then the expert layers."""
        if not self.n_experts:
            return (("layers", "dense", 0, self.n_layers),)
        nd = self.n_dense_layers
        return tuple(g for g in (
            ("layers", "dense", 0, nd),
            ("expert_layers", "experts", nd, self.n_layers - nd)) if g[3])


def _holds(cfg: DecoderLMConfig, leaf: str) -> bool:
    """Whether the config's layers hold ``leaf``: ``w_dkv`` says the mixer's
    queries, keys and values come through latents, ``wi_k`` that it has an
    indexer, ``wg`` that it has a gate; ``ws_gate`` that an expert layer has
    a shared expert. What a layer IS is asked of its leaves, not of a name."""
    if leaf in FFN_LEAVES["experts"]:
        return bool(cfg.n_experts and (cfg.n_shared_experts
                                       or not leaf.startswith("ws_")))
    held = MIXER_LEAVES[cfg.mixer]
    if isinstance(held, dict):
        return any(leaf in of_kind for of_kind in held.values())
    return leaf in held


def _shortest_period(kinds: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest run of ``kinds`` that, repeated, is all of them."""
    return next((kinds[:n] for n in range(1, len(kinds))
                 if len(kinds) % n == 0
                 and kinds[:n] * (len(kinds) // n) == kinds), kinds)


# mixer name → fn(cfg) → the kinds of one PERIOD's layers, in order, for the
# mixers whose layers come in kinds.
MIXER_KINDS: Dict[str, Callable] = {
    "window_gqa": lambda cfg: ("window",) * (cfg.full_attention_every - 1) + (
        "full",),
    "hybrid_kda": lambda cfg: ("linear",) * (cfg.layer_group_size - 1) + (
        "latent",),
    # The model's own pattern: the period of the layers BEHIND the leading
    # dense ones, wherever in it an attention layer stands.
    "conv_gqa": lambda cfg: _shortest_period(
        cfg.layer_types[cfg.n_dense_layers if cfg.n_experts else 0:]),
}


def layer_kinds(cfg: DecoderLMConfig) -> Tuple[str, ...]:
    """The kinds of one PERIOD's layers, in order, where a model's layers
    come in kinds (``MIXER_KINDS``: ``window_gqa``'s window layers and then a
    full one, ``hybrid_kda``'s linear layers and then a latent one,
    ``conv_gqa``'s as its ``layer_types`` repeat behind the leading dense
    layers); ``()`` where every layer is alike."""
    kinds = MIXER_KINDS.get(cfg.mixer)
    return kinds(cfg) if kinds else ()


def kinds_by_layer(cfg: DecoderLMConfig) -> Tuple[str, ...]:
    """The kind of EVERY layer of a model whose layers come in kinds: the
    model's own pattern where its config states one (``layer_types``); else
    whole periods (:func:`layer_kinds`) behind leading dense layers of the
    period's first kind."""
    if cfg.layer_types:
        return cfg.layer_types
    kinds = layer_kinds(cfg)
    leading = cfg.n_dense_layers if cfg.n_experts else 0
    return kinds[:1] * leading + kinds * (
        (cfg.n_layers - leading) // len(kinds))


def group_kinds(cfg: DecoderLMConfig, ffn: str) -> Tuple[str, ...]:
    """The period a stacked group's layer scan steps over: the shortest that
    its layers' kinds (:func:`kinds_by_layer`) repeat. A group may begin
    anywhere in the model's period (its period is then that one, turned), and
    leading dense layers in front of expert layers, no whole period of the
    model's, are each a period of their own where they are of one kind."""
    first, n = next((first, n) for _, kind, first, n in cfg.layer_groups
                    if kind == ffn)
    return _shortest_period(kinds_by_layer(cfg)[first:first + n])


def layers_of_kinds(cfg: DecoderLMConfig) -> Dict[str, Dict[str, Tuple]]:
    """``{group: {kind: the numbers (over both groups) of the group's layers
    of that kind}}`` of a model whose layers come in kinds."""
    by_layer = kinds_by_layer(cfg)
    return {group: {kind: tuple(i for i in range(first, first + n)
                                if by_layer[i] == kind)
                    for kind in sorted(set(by_layer[first:first + n]))}
            for group, _, first, n in cfg.layer_groups}


def validate(cfg: DecoderLMConfig) -> None:
    """ValueError (a caller's error) on a config no program can run."""
    if cfg.mixer not in MIXERS:
        raise ValueError(f"mixer must be one of {sorted(MIXERS)}, "
                         f"got {cfg.mixer!r}")
    if cfg.mixer in ("power_retention", "hybrid_ssm", "window_gqa",
                     "conv_gqa"):
        if cfg.n_kv_heads <= 0 or cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if cfg.d_head % 2:
            raise ValueError("d_head must be even (rotary pairs)")
    positive = ["vocab_size", "d_model", "d_ff", "n_layers", "max_len"]
    if _holds(cfg, "w_dkv"):
        positive += ["kv_lora_rank", "qk_nope_head_dim", "v_head_dim"] + (
            ["q_lora_rank"] if _holds(cfg, "w_dq") else [])
        if cfg.qk_rope_head_dim % 2 or cfg.qk_rope_head_dim <= 0:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if cfg.query_scale_beta < 0:
            raise ValueError("query_scale_beta must not be negative")
    if _holds(cfg, "wi_k"):
        positive += ["index_n_heads", "index_topk"]
        if cfg.index_head_dim < cfg.qk_rope_head_dim:
            raise ValueError("index_head_dim must hold the rotary part")
    if cfg.mixer == "hybrid_ssm":
        positive += ["ssm_n_heads", "ssm_d_head", "ssm_d_state",
                     "ssm_n_groups", "ssm_d_conv", "ssm_chunk"]
        positive += [f.name for f in fields(cfg)
                     if f.name.endswith("_multiplier")]
        if cfg.ssm_n_heads % max(1, cfg.ssm_n_groups):
            raise ValueError("ssm_n_heads must be whole ssm_n_groups")
    if cfg.mixer == "window_gqa":
        positive += ["sliding_window", "full_attention_every"]
        if cfg.n_layers % max(1, cfg.full_attention_every):
            raise ValueError("n_layers must be whole periods of "
                             "full_attention_every layers")
        if cfg.n_experts and cfg.n_dense_layers:
            raise ValueError("layers in kinds are stacked as one group: "
                             "n_dense_layers must be 0")
    if cfg.mixer == "hybrid_kda":
        from agent_tpu.kernels.kda import SUB

        positive += ["n_heads", "d_head", "layer_group_size", "kda_conv"]
        if cfg.layer_group_size < 2:
            raise ValueError("layer_group_size must hold a linear layer "
                             "and the latent one")
        leading = cfg.n_dense_layers if cfg.n_experts else 0
        if leading >= cfg.layer_group_size or (
                cfg.n_layers - leading) % cfg.layer_group_size:
            raise ValueError("the layers behind the leading dense ones (all "
                             "of them linear) must be whole periods of "
                             "layer_group_size layers")
        if not -80.0 / SUB <= cfg.kda_lower_bound < 0:
            raise ValueError("kda_lower_bound must lie in [-5, 0): a "
                             "sub-block's decay has to stay inside float32")
    if cfg.mixer == "conv_gqa":
        positive += ["conv_taps"]
        if len(cfg.layer_types) != cfg.n_layers or not set(
                cfg.layer_types) <= set(MIXER_LEAVES["conv_gqa"]):
            raise ValueError("layer_types must name every layer's kind, "
                             "'conv' or 'full_attention'")
    elif cfg.layer_types:
        raise ValueError("layer_types is conv_gqa's: this mixer's kinds "
                         "follow from its own keys")
    for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
        limits = getattr(cfg, name)
        if limits and (len(limits) != cfg.n_layers or min(limits) < 0):
            raise ValueError(f"{name} must hold one limit >= 0 a layer")
    if cfg.n_experts:
        positive += ["n_experts_held", "d_expert", "n_experts_per_token"]
        if cfg.n_shared_experts < 0:
            raise ValueError("n_shared_experts must not be negative")
        if cfg.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError("scoring_func must be 'sigmoid' or 'softmax', "
                             f"got {cfg.scoring_func!r}")
        if cfg.scoring_func == "softmax" and cfg.n_expert_groups != 1:
            raise ValueError("a softmax router has no groups")
        if not 0 <= cfg.n_dense_layers <= cfg.n_layers:
            raise ValueError("n_dense_layers must lie in 0 .. n_layers")
        if (cfg.expert_first < 0
                or cfg.expert_first + cfg.n_experts_held > cfg.n_experts):
            raise ValueError("the experts held must be ids of n_experts")
        if cfg.n_experts % max(1, cfg.n_expert_groups):
            raise ValueError("n_experts must be whole groups")
        size = cfg.n_experts // max(1, cfg.n_expert_groups)
        if (cfg.n_groups_per_token > cfg.n_expert_groups
                or cfg.n_experts_per_token > cfg.n_groups_per_token * size
                or (cfg.n_expert_groups > 1 and size < 2)):
            raise ValueError("the router cannot choose n_experts_per_token "
                             "experts from n_groups_per_token groups")
    for name in positive:
        if not getattr(cfg, name) > 0:
            raise ValueError(f"{name} must be positive")


# ---- weights --------------------------------------------------------------

def _leaf_shapes(cfg: DecoderLMConfig, kind: Optional[str] = None
                 ) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf → (shape of one layer's leaf (of one expert's; or the whole
    leaf), fan_in); the mixer's leaves as a layer of ``kind`` holds them,
    where the mixer's kinds hold different leaves."""
    d, f = cfg.d_model, cfg.d_ff
    if kind == "linear":
        hq, k = cfg.n_heads * cfg.d_head, cfg.kda_conv
        mixer = {
            "wq": ((d, hq), d), "wk": ((d, hq), d), "wv": ((d, hq), d),
            "wo": ((hq, d), hq), "conv_w": ((k, 3 * hq), k),
            "w_beta": ((d, cfg.n_heads), d), "w_a": ((d, hq), d),
            "w_og": ((d, cfg.n_heads), d),
        }
    elif kind == "latent":
        h, kvr = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        mixer = {
            "wq": ((d, h * (dn + dr)), d), "wo": ((h * dv, d), h * dv),
            "w_dkv": ((d, kvr + dr), d), "w_ukv": ((kvr, h * (dn + dv)), kvr),
            "w_og": ((d, h), d),
        }
    elif kind == "conv":
        k = cfg.conv_taps
        mixer = {"w_conv_in": ((d, 3 * d), d), "wo": ((d, d), d),
                 "conv_w": ((k, d), k)}
    elif _holds(cfg, "w_dkv"):
        h, qr, kvr = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        mixer = {
            "wo": ((h * dv, d), h * dv),
            "w_dq": ((d, qr), d), "w_uq": ((qr, h * (dn + dr)), qr),
            "w_dkv": ((d, kvr + dr), d), "w_ukv": ((kvr, h * (dn + dv)), kvr),
            "wi_q": ((qr, hi * di), qr), "wi_k": ((d, di), d),
            "wi_w": ((d, hi), d),
        }
    else:
        hq, hkv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
        mixer = {
            "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
            "wo": ((hq, d), hq), "wg": ((d, cfg.n_kv_heads), d),
        }
    if cfg.mixer == "hybrid_ssm":
        d_ssm = cfg.ssm_n_heads * cfg.ssm_d_head
        conv, k = cfg.ssm_conv_dim, cfg.ssm_d_conv
        mixer.update({
            "w_ssm_in": ((d, cfg.ssm_in_dim), d),
            "w_ssm_out": ((d_ssm, d), d_ssm),
            "conv_w": ((k, conv), k), "conv_b": ((conv,), k),
        })
    fe, fs = cfg.d_expert, cfg.d_expert * cfg.n_shared_experts
    return {
        "embed": ((cfg.vocab_size, d), 1), "head": ((cfg.vocab_size, d), d),
        **mixer,
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
        "w_router": ((d, cfg.n_experts), d),
        "ws_gate": ((d, fs), d), "ws_up": ((d, fs), d), "ws_down": ((fs, d), fs),
        "we_gate": ((d, fe), d), "we_up": ((d, fe), d), "we_down": ((fe, d), fe),
    }


def _leaf_gains(cfg: DecoderLMConfig) -> Dict[str, Any]:
    """leaf → what the drawn leaf is multiplied by beside ``1/sqrt(fan_in)``:
    a number, or ``((columns, number), ...)`` along the output axis. Only
    ``hybrid_ssm`` has any: the inverse of every multiplier the forward pass
    applies to the leaf's output (the published multipliers belong to
    TRAINED weights; on weights of the plain rule they would leave a layer's
    branches at a hundredth of the residual and the logits at 1/128, so that
    every token scored ``-log V`` whatever the state carried), and
    ``QUERY_GAIN`` on the queries (a trained query's scores select; unit
    scores over 65,536 keys average them, and the branch is 0.006 of the
    residual at exactly the lengths it is there for)."""
    if cfg.mixer != "hybrid_ssm":
        return {}
    a_in, s_in = cfg.attention_in_multiplier, cfg.ssm_in_multiplier
    return {
        "embed": 1.0 / cfg.embedding_multiplier,
        "head": 1.0 / cfg.lm_head_multiplier,
        "wq": QUERY_GAIN / a_in,
        "wk": 1.0 / (a_in * cfg.key_multiplier),
        "wv": 1.0 / a_in,
        "wo": 1.0 / cfg.attention_out_multiplier,
        "w_ssm_in": tuple((n, 1.0 / (s_in * m)) for n, m in cfg.ssm_parts),
        "w_ssm_out": 1.0 / cfg.ssm_out_multiplier,
        "w_gate": 1.0 / cfg.mlp_gate_multiplier,
        "w_down": 1.0 / cfg.mlp_down_multiplier,
    }


def by_columns(parts) -> np.ndarray:
    """``((columns, number), ...)`` → the float32 vector along those columns."""
    return np.concatenate([np.full(n, value, np.float32)
                           for n, value in parts])


def leaf_scale(fan_in: int, gain: Any = 1.0):
    """What a standard normal is multiplied by: ``gain / sqrt(fan_in)``, a
    Python float (float64 arithmetic, rounded once to float32 at the
    multiply), or a float32 vector along the output axis."""
    root = np.sqrt(max(1, fan_in))
    if isinstance(gain, tuple):
        return by_columns((n, g / root) for n, g in gain)
    return gain / root


def ssm_dt_bias(n_heads: int) -> np.ndarray:
    """Head j's step before the input moves it: ``SSM_DT_RANGE`` spaced
    log-uniformly over the heads, through the inverse of softplus."""
    lo, hi = SSM_DT_RANGE
    dt = np.exp(np.linspace(np.log(lo), np.log(hi), n_heads))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def gate_bias(n_kv_heads: int) -> np.ndarray:
    return np.log(GATE_TAU0 * 2.0 ** np.arange(n_kv_heads) - 1.0).astype(
        np.float32)


def kda_gate_constants(cfg: DecoderLMConfig) -> Tuple[np.ndarray, np.ndarray]:
    """``(A_log [H], dt_bias [H x d_head])`` of a linear layer, float32. A
    channel's log-decay a token is ``kda_lower_bound x sigmoid(exp(A_log_j)
    (a + dt_bias))``, ``a`` the input's say. ``exp(A_log_j)``, how much that
    say sways head ``j``, is spaced evenly over 0.5 .. 1.5; ``dt_bias`` is set
    so that at ``a = 0`` head ``j`` of ``H`` forgets over ``tau_j = 16 x
    128^(j / (H - 1))`` tokens (16 .. 2,048): ``exp(g) = 1 - 1 / tau_j``."""
    H = cfg.n_heads
    at = np.arange(H, dtype=np.float64) / max(1, H - 1)
    sway = 0.5 + at
    share = -np.log1p(-1.0 / (GATE_TAU0 * 128.0 ** at)) / -cfg.kda_lower_bound
    bias = np.log(share / (1.0 - share)) / sway
    return (np.log(sway).astype(np.float32),
            np.repeat(bias, cfg.d_head).astype(np.float32))


def _swiglu_limits(limits: Tuple[float, ...], layers) -> np.ndarray:
    """The given layers' entries of a config's limit list as the clamp
    takes them, float32: 0 (no clamp) is infinity."""
    mine = np.asarray([limits[i] for i in layers], np.float32)
    return np.where(mine > 0, mine, np.inf).astype(np.float32)


def _layer_constants(cfg: DecoderLMConfig, ffn: Optional[str],
                     kind: Optional[str] = None, layers=()) -> Dict[str, Tuple]:
    """leaf → (value, shape of one layer's leaf): what is not drawn. Where
    the mixer's kinds hold different leaves: the mixer's half for ``kind``
    (``ffn`` None), the feed-forward's half for ``ffn`` (``kind`` None).
    ``layers``: the numbers of the layers the stack holds (a value that
    differs by layer is a vector along them)."""
    d = cfg.d_model
    if kind == "linear":
        A_log, dt_bias = kda_gate_constants(cfg)
        return {"ln1": (1.0, (d,)), "A_log": (A_log, A_log.shape),
                "dt_bias": (dt_bias, dt_bias.shape),
                "o_norm": (1.0, (cfg.d_head,))}
    if kind == "latent":
        return {"ln1": (1.0, (d,)), "kv_norm": (1.0, (cfg.kv_lora_rank,))}
    if kind == "conv":
        return {"ln1": (1.0, (d,))}
    if kind == "full":
        # ``window_gqa``'s norms a head, its query norm's weight and reason.
        return {"ln1": (1.0, (d,)), "q_norm": (QUERY_NORM_GAIN, (cfg.d_head,)),
                "k_norm": (1.0, (cfg.d_head,))}
    out = {"ln1": (1.0, (d,)), "ln2": (1.0, (d,))}
    if isinstance(MIXER_LEAVES[cfg.mixer], dict):
        del out["ln1"]                     # the mixer's half has it
    elif _holds(cfg, "w_dkv"):
        out.update(q_norm=(1.0, (cfg.q_lora_rank,)),
                   kv_norm=(1.0, (cfg.kv_lora_rank,)))
        if _holds(cfg, "wi_k"):
            out.update(ik_norm=(1.0, (cfg.index_head_dim,)),
                       ik_bias=(0.0, (cfg.index_head_dim,)))
    elif cfg.mixer == "hybrid_ssm":
        h = cfg.ssm_n_heads
        out.update(
            ssm_norm=(1.0, (h * cfg.ssm_d_head,)),
            A_log=(np.log(np.arange(1, h + 1, dtype=np.float32)), (h,)),
            D=(1.0, (h,)), dt_bias=(ssm_dt_bias(h), (h,)))
    elif _holds(cfg, "wg"):
        out.update(bg=(gate_bias(cfg.n_kv_heads), (cfg.n_kv_heads,)),
                   q_norm=(1.0, (cfg.d_head,)), k_norm=(1.0, (cfg.d_head,)))
    else:
        # Normed queries and keys of weight 1 score with unit spread, and a
        # softmax over thousands of such keys averages them (the lesson of
        # ``_leaf_gains``): the query norm's weight is ``QUERY_NORM_GAIN``.
        out.update(q_norm=(QUERY_NORM_GAIN, (cfg.d_head,)),
                   k_norm=(1.0, (cfg.d_head,)))
    if ffn == "experts" and cfg.scoring_func == "sigmoid":
        out["router_bias"] = (0.0, (cfg.n_experts,))
    if ffn == "experts":
        for leaf, limits in (("expert_limit", cfg.expert_swiglu_limits),
                             ("shared_limit", cfg.shared_swiglu_limits)):
            if limits:
                out[leaf] = (_swiglu_limits(limits, layers), ())
    return out


def init_params(cfg: DecoderLMConfig, model_id: str, sharding=None) -> Params:
    """The family's weights, built on the device in the stored dtype. With
    ``sharding`` the leaves come out committed to it, which is how
    ``TpuRuntime.get_params`` keeps them as built."""
    dtype = cfg.compute_dtype
    root = layers.seed_from(model_id)
    gains = _leaf_gains(cfg)
    by_kind = isinstance(MIXER_LEAVES[cfg.mixer], dict)

    programs: Dict[Tuple, Callable] = {}

    def program(shape, fan_in, gain, first, n, experts):
        """``key -> leaf``: one matrix (a standard normal times
        :func:`leaf_scale`); stacked over layers ``first .. first
        + n`` when given (``first`` a tuple: over the layers of those
        numbers), and under each over the ``experts`` (ids) held,
        every entry from its own folded key. Layers are drawn at once; a
        layer's experts one after another (one small program in a loop, not
        a vmapped 16)."""
        def fn(key):
            def one(k):
                w = jax.random.normal(k, shape, dtype=jnp.float32)
                return (w * leaf_scale(fan_in, gain)).astype(dtype)

            if first is None:
                return one(key)
            if isinstance(first, tuple):
                numbers = jnp.asarray(first)
            else:
                numbers = jnp.arange(n) + first if first else jnp.arange(n)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(numbers)
            if experts is None:
                return jax.vmap(one)(keys)
            return jax.lax.map(lambda k: jax.lax.map(
                lambda e: one(jax.random.fold_in(k, e)),
                jnp.asarray(experts)), keys)

        return jax.jit(fn, out_shardings=sharding)

    def draw(name, first=None, n=0, experts=None, kind=None):
        # Leaves of one shape share ONE program (wk / wv, w_gate / w_up,
        # embedding / head): tracing and lowering a draw costs the host more
        # than the device takes to run it.
        which = (*_leaf_shapes(cfg, kind)[name], gains.get(name, 1.0), first,
                 n, experts)
        if which not in programs:
            programs[which] = program(*which)
        return programs[which](jax.random.fold_in(root, LEAVES.index(name)))

    def const(value, shape):
        return jax.jit(lambda: jnp.broadcast_to(
            jnp.asarray(value, jnp.float32), shape).astype(jnp.float32),
            out_shardings=sharding)()

    held = tuple(range(cfg.expert_first, cfg.expert_first + cfg.n_experts_held))
    params = {"embed": draw("embed"), "head": draw("head"),
              "final_norm": const(1.0, (cfg.d_model,))}
    for group, ffn, first, n in cfg.layer_groups:
        own = () if by_kind else MIXER_LEAVES[cfg.mixer]
        params[group] = {
            **{name: draw(name, first, n,
                          held if name in EXPERT_LEAVES else None)
               for name in own + FFN_LEAVES[ffn]
               if ffn == "dense" or _holds(cfg, name)},
            **{name: const(value, (n, *shape)) for name, (value, shape)
               in _layer_constants(cfg, ffn, None,
                                   range(first, first + n)).items()},
        }
        if by_kind:
            # The mixer's leaves by KIND: a stack a kind, over the group's
            # layers of that kind; a layer's key is its number all the same.
            params[group]["mixers"] = {kind: {
                **{name: draw(name, numbers, len(numbers), kind=kind)
                   for name in MIXER_LEAVES[cfg.mixer][kind]},
                **{name: const(value, (len(numbers), *shape))
                   for name, (value, shape)
                   in _layer_constants(cfg, None, kind).items()},
            } for kind, numbers in layers_of_kinds(cfg)[group].items()}
    return params


# ---- the mathematics ------------------------------------------------------

@part("norm")
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Float32 statistics, the input's dtype out."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


@part("around")
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions, half-split pairs (i, i + D/2): x [..., L, H, D],
    positions [L]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [L, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def linear(w: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """x [..., in] @ w [in, out]; dispatches on the quantized leaf shapes of
    ``models.quant`` as ``layers.dense`` does."""
    from agent_tpu.models import quant

    if quant.is_quantized(w):
        return quant.qdense(w, x, dtype)
    if quant.is_weight_only(w):
        return quant.wdense(w, x, dtype)
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def _project(w: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """:func:`linear` as a mixer's in- or out-projection: the part
    ``project``, inside a mixer whose own work is ``around``."""
    with part("project"):
        return linear(w, x, dtype)


@part("around")
def _power_retention_mixer(p: Params, h: jax.Array, positions: jax.Array,
                           state, cfg: DecoderLMConfig, kernel_opts):
    """h [B, L, d] (normed) → (what enters the residual [B, L, d], new
    state)."""
    from agent_tpu.kernels.power_retention import power_retention

    dtype = cfg.compute_dtype
    B, L, _ = h.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = _project(p["wq"], h, dtype).reshape(B, L, hq, dh)
    k = _project(p["wk"], h, dtype).reshape(B, L, hkv, dh)
    v = _project(p["wv"], h, dtype)
    q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta).reshape(B, L, hq * dh)
    k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta).reshape(B, L, hkv * dh)
    gate = jnp.dot(h.astype(dtype), p["wg"].astype(dtype),
                   preferred_element_type=jnp.float32) + p["bg"]
    log_g = jax.nn.log_sigmoid(gate)                        # [B, L, Hkv] f32
    y, state = power_retention(q, k, v, log_g, n_kv_heads=hkv,
                               initial_state=state, **kernel_opts)
    return _project(p["wo"], y, dtype), state


def plain_inv_freq(base: float, dim: int) -> np.ndarray:
    """``base^(-2i / dim)`` for the ``dim / 2`` rotary pairs, float64."""
    return 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))


def yarn_inv_freq(cfg: DecoderLMConfig,
                  dim: Optional[int] = None) -> np.ndarray:
    """Inverse frequencies of the ``dim / 2`` rotary pairs (``dim``: latent
    attention's ``qk_rope_head_dim`` unless given) under
    YaRN (the DeepSeek inference code's ``precompute_freqs_cis``): below the
    ``beta_fast`` rotations correction dimension untouched, above the
    ``beta_slow`` one divided by ``rope_factor``, a linear ramp between.
    Applied whenever ``max_len`` exceeds the original length."""
    dim, base = dim or cfg.qk_rope_head_dim, float(cfg.rope_theta)
    inv = plain_inv_freq(base, dim)
    if cfg.rope_factor == 1.0 or cfg.max_len <= cfg.rope_original_max_len:
        return inv.astype(np.float32)

    def correction_dim(rotations):
        return dim * np.log(cfg.rope_original_max_len / (
            rotations * 2 * np.pi)) / (2 * np.log(base))

    low = max(int(np.floor(correction_dim(cfg.rope_beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(cfg.rope_beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    smooth = 1.0 - ramp
    return (inv / cfg.rope_factor * (1 - smooth) + inv * smooth).astype(
        np.float32)


def yarn_mscale(cfg: DecoderLMConfig) -> float:
    """YaRN's attention factor, ``0.1 rope_mscale ln(rope_factor) + 1``,
    where the positions are scaled; else 1."""
    if cfg.rope_factor != 1.0 and cfg.max_len > cfg.rope_original_max_len:
        return 0.1 * cfg.rope_mscale * np.log(cfg.rope_factor) + 1.0
    return 1.0


def softmax_scale(cfg: DecoderLMConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``mscale`` squared where the
    positions are scaled: ``mscale = 0.1 rope_mscale ln(factor) + 1``."""
    return float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * (
        yarn_mscale(cfg) ** 2)


@part("around")
def rope_pairs(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray,
               interleaved: bool) -> jax.Array:
    """Rotary positions on the last axis of x [L, ..., D] with given inverse
    frequencies [D/2]: pairs ``(2i, 2i + 1)`` (``interleaved``: latent
    attention's) or ``(i, i + D/2)`` (the indexer's)."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(*xf.shape[:-1], d // 2, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
        return out.reshape(xf.shape).astype(x.dtype)
    a, b = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def query_position_scale(cfg: DecoderLMConfig, positions: jax.Array):
    """``a(t) = 1 + query_scale_beta x ln(1 + floor(t / rope_original_max_len))``
    for every position, float32 [S, 1]; ``None`` where the config has no such
    scale (the program is then the one without it)."""
    if not cfg.query_scale_beta:
        return None
    steps = jnp.floor_divide(positions, cfg.rope_original_max_len)
    return (1.0 + cfg.query_scale_beta * jnp.log1p(
        steps.astype(jnp.float32)))[:, None]


def _project_f32(w: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """:func:`_project` with the float32 sums kept: for what is scaled,
    gated or exponentiated before it is rounded."""
    with part("project"):
        if isinstance(w, dict):
            return linear(w, x, jnp.float32)
        return jnp.dot(x.astype(dtype), w.astype(dtype),
                       preferred_element_type=jnp.float32)


def _latent_projections(p: Params, h: jax.Array, positions: jax.Array,
                        cfg: DecoderLMConfig):
    """What every latent mixer computes of a segment's normed ``h [S, d]``:
    ``(cq, q, q_rope, latent)``: the normed query latent ``[S, q_lora_rank]``
    in the compute dtype (``None`` where the layer projects its queries
    straight from ``h``: it holds ``wq`` and no ``w_dq``); the per-head
    queries ``[S, H, nope + rope]`` as
    projected (the rotary part NOT yet rotated) and their rotated rotary
    part ``[S, H, rope]``; what the cache holds of a token, ``[S,
    kv_lora_rank + rope]`` = ``[RMSNorm(c) | RoPE(kR)]``. The softmax scale,
    and the position's own scale where the config has one, go into the query
    latent before it is rounded to the compute dtype (into the queries' float32
    sums where there is no latent): a multiply less on
    every score of the attention."""
    dtype, eps = cfg.compute_dtype, cfg.rms_norm_eps
    S = h.shape[0]
    nh, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr = cfg.kv_lora_rank
    inv = yarn_inv_freq(cfg)

    def scale():
        by_position = query_position_scale(cfg, positions)
        return softmax_scale(cfg) * (1.0 if by_position is None
                                     else by_position)

    if "w_dq" in p:
        cq = rms_norm(_project(p["w_dq"], h, dtype).astype(jnp.float32),
                      p["q_norm"], eps)
        q = _project(p["w_uq"], (cq * scale()).astype(dtype),
                     dtype).reshape(S, nh, dn + dr)
        cq = cq.astype(dtype)
    else:
        cq = None
        q = (_project_f32(p["wq"], h, dtype) * scale()).astype(dtype).reshape(
            S, nh, dn + dr)
    q_rope = rope_pairs(q[..., dn:], positions, inv, interleaved=True)
    latent = _project(p["w_dkv"], h, dtype)
    latent = jnp.concatenate([
        rms_norm(latent[:, :kvr], p["kv_norm"], eps),
        rope_pairs(latent[:, kvr:], positions, inv, interleaved=True)], -1)
    return cq, q, q_rope, latent


def _head_major_ukv(p: Params, cfg: DecoderLMConfig) -> jax.Array:
    """``w_ukv`` as the expansion takes it: ``[H, kv_lora_rank, nope + v]``."""
    return _plain_weights(p["w_ukv"], cfg.compute_dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim).transpose(1, 0, 2)


@part("around")
def _sparse_mla_mixer(p: Params, h: jax.Array, positions: jax.Array,
                      state, cfg: DecoderLMConfig, kernel_opts):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's cache with the segment written at its positions). ``state``: ``{"kv": [1, Lk,
    kv_lora_rank + rope], "ki": [1, Lk, index_head_dim]}``. One document a
    program: the kernels take no batch."""
    from agent_tpu.kernels import sparse_mla

    if h.shape[0] != 1:
        raise ValueError("sparse_mla runs one document a program")
    dtype = cfg.compute_dtype
    h = h[0]
    S = h.shape[0]
    nh, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr, hi, di = cfg.kv_lora_rank, cfg.index_n_heads, cfg.index_head_dim
    inv = yarn_inv_freq(cfg)
    pos0 = positions[0]
    cq, q, q_rope, latent = _latent_projections(p, h, positions, cfg)

    # The indexer: rotary part FIRST, half-split pairs; a LayerNorm on keys.
    qi = _project(p["wi_q"], cq, dtype).reshape(S, hi, di)
    qi = jnp.concatenate([rope_pairs(qi[..., :dr], positions, inv, False),
                          qi[..., dr:]], -1)
    ki = layers.layer_norm({"scale": p["ik_norm"], "bias": p["ik_bias"]},
                           _project(p["wi_k"], h, dtype), 1e-6)
    ki = jnp.concatenate([rope_pairs(ki[:, :dr], positions, inv, False),
                          ki[:, dr:]], -1)
    with part("project"):
        wi = jnp.dot(h.astype(dtype), p["wi_w"].astype(dtype),
                     preferred_element_type=jnp.float32) * float(
            hi ** -0.5 * di ** -0.5)

    kv = jax.lax.dynamic_update_slice(state["kv"][0], latent, (pos0, 0))
    kic = jax.lax.dynamic_update_slice(state["ki"][0], ki, (pos0, 0))
    mask = sparse_mla.index_select(qi, wi, kic, pos0, cfg.index_topk,
                                   **kernel_opts)
    w_ukv = _head_major_ukv(p, cfg)
    k_nope, v = sparse_mla.expand_latents(kv[:, :kvr], w_ukv, pos0 + S, dn,
                                          **kernel_opts)
    o = sparse_mla.masked_attention(
        q[..., :dn].transpose(1, 0, 2), q_rope.transpose(1, 0, 2), k_nope,
        kv[:, kvr:], v, mask, pos0, **kernel_opts)
    o = o.transpose(1, 0, 2).reshape(1, S, nh * cfg.v_head_dim)
    return _project(p["wo"], o, dtype), {"kv": kv[None], "ki": kic[None]}


def _dense_latent_attention(p: Params, h: jax.Array, positions: jax.Array,
                            state, cfg: DecoderLMConfig, kernel_opts):
    """Dense latent attention of a segment's normed ``h [S, d]`` → (the
    heads' outputs ``[1, S, H x v_head_dim]`` BEFORE the out-projection, the
    layer's cache ``[Lk, kv_lora_rank + rope]`` with the segment written at
    its positions). The latents the segment can see are expanded HERE, for
    this layer alone, into head-major joined keys ``[c W_UK | kR]`` and
    values (the rotary key reaches every head through an identity block of
    the expansion's weight: ``sparse_mla.join_rotary_key``), and every
    causal key is attended: plain causal attention at one query head a key
    head."""
    from agent_tpu.kernels import causal_attention, sparse_mla

    S = h.shape[0]
    nh, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    pos0 = positions[0]
    _, q, q_rope, latent = _latent_projections(p, h, positions, cfg)
    # A joined key that is no whole number of lanes (128 + 64) is padded to
    # one with zeros, on the queries and in the expansion's weight alike.
    dk = -(-(dn + dr) // 128) * 128 if dn + dr > 128 else dn + dr
    q = jnp.concatenate([q[..., :dn], q_rope] + (
        [jnp.zeros((S, nh, dk - dn - dr), q.dtype)] if dk > dn + dr else []),
        -1)                                                      # [S, H, Dk]

    kv = jax.lax.dynamic_update_slice(state["kv"][0], latent, (pos0, 0))
    k, v = sparse_mla.expand_latents(
        kv, sparse_mla.join_rotary_key(_head_major_ukv(p, cfg), dn, dr, dk),
        pos0 + S, dk, **kernel_opts)
    o = causal_attention.causal_attention(
        q.transpose(1, 0, 2)[:, None], k, v, pos0, **kernel_opts)
    return o[:, 0].transpose(1, 0, 2).reshape(1, S, nh * cfg.v_head_dim), kv


@part("around")
def _dense_mla_mixer(p: Params, h: jax.Array, positions: jax.Array,
                     state, cfg: DecoderLMConfig, kernel_opts):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's cache with the segment written at its positions). ``state``:
    ``{"kv": [1, Lk, kv_lora_rank + rope]}``: latents and nothing expanded
    (:func:`_dense_latent_attention`)."""
    if h.shape[0] != 1:
        raise ValueError("dense_mla runs one document a program")
    o, kv = _dense_latent_attention(p, h[0], positions, state, cfg,
                                    kernel_opts)
    return _project(p["wo"], o, cfg.compute_dtype), {"kv": kv[None]}


def _sparse_mla_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """The empty cache of ``batch`` documents of ``cache_len`` (padded)
    tokens: every layer's latents and index keys, in the stored dtype."""
    n, dtype = cfg.n_layers, cfg.compute_dtype
    return {"kv": jnp.zeros((n, batch, cache_len, cfg.kv_lora_rank
                             + cfg.qk_rope_head_dim), dtype),
            "ki": jnp.zeros((n, batch, cache_len, cfg.index_head_dim), dtype)}


def _dense_mla_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """The empty cache: every layer's latents, and nothing else."""
    return {"kv": jnp.zeros((cfg.n_layers, batch, cache_len, cfg.kv_lora_rank
                             + cfg.qk_rope_head_dim), cfg.compute_dtype)}


def _write_cache(stack: jax.Array, new: jax.Array, layer, pos0) -> jax.Array:
    """The layers' stack of caches ``[layers, 1, Hkv, Lk, D]`` with a
    segment's ``new [Hkv, S, D]`` written at ``layer`` and ``pos0``: in the
    layer scan's carry that is a write in place."""
    return jax.lax.dynamic_update_slice(stack, new[None, None],
                                        (layer, 0, 0, pos0, 0))


@part("around")
def _times(x: jax.Array, m: float, dtype) -> jax.Array:
    """``x * m`` in float32, rounded to ``dtype``; ``x`` itself where the
    multiplier is 1 (the other mixers' programs stay as they were)."""
    if m == 1.0:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * m).astype(dtype)


@part("around")
def _hybrid_ssm_mixer(p: Params, h: jax.Array, positions: jax.Array,
                      state, cfg: DecoderLMConfig, kernel_opts):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's state). Two branches read the one ``h`` and their outputs are
    summed, each through its own out-projection and multiplier: causal
    grouped-query attention over the key and value cache, and a Mamba-2
    scan behind a causal convolution. ``state``: ``{"k", "v": [layers, 1,
    Hkv, Lk, D]`` (the caches of ALL the layers, ``MIXER_CACHES``: written at
    ``"layer"``, an int32 scalar, and the segment's positions, attended
    there, and handed back whole), ``"ssm": [1, H, N, P]`` float32 (the
    scan's), ``"conv": [1, K - 1, channels]`` float32 (the convolution's
    last inputs)``}``. One document a program.

    Every multiplier is applied where the published pass applies it, in
    float32 on the rounded product, and rounded again; none is folded into a
    weight. The softmax scale goes into the rotated queries before they are
    rounded, as ``sparse_mla`` does."""
    from agent_tpu.kernels import causal_attention, ssd

    if h.shape[0] != 1:
        raise ValueError("hybrid_ssm runs one document a program")
    dtype, f32 = cfg.compute_dtype, jnp.float32
    h = h[0]
    S = h.shape[0]
    pos0 = positions[0]

    # Attention: G query heads a key-value head, rotary positions, no norm.
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ha = _times(h, cfg.attention_in_multiplier, dtype)
    q = rope(_project(p["wq"], ha, dtype).reshape(S, hq, dh).astype(f32),
             positions, cfg.rope_theta)
    q = (q * float(dh) ** -0.5).astype(dtype)
    k = _project(p["wk"], ha, dtype).astype(f32) * cfg.key_multiplier
    k = rope(k.reshape(S, hkv, dh), positions, cfg.rope_theta).astype(dtype)
    v = _project(p["wv"], ha, dtype).reshape(S, hkv, dh)
    layer = state["layer"]
    kc = _write_cache(state["k"], k.transpose(1, 0, 2), layer, pos0)
    vc = _write_cache(state["v"], v.transpose(1, 0, 2), layer, pos0)
    o = causal_attention.causal_attention(
        q.reshape(S, hkv, hq // hkv, dh).transpose(1, 2, 0, 3), kc, vc, pos0,
        layer, **kernel_opts)
    attended = _project(p["wo"], o.transpose(2, 0, 1, 3).reshape(S, hq * dh),
                      dtype)

    # The scan: in-projection [z | x | B | C | dt], each part times its own
    # multiplier; convolution and SiLU over [x | B | C].
    H, P, N, G = (cfg.ssm_n_heads, cfg.ssm_d_head, cfg.ssm_d_state,
                  cfg.ssm_n_groups)
    d_ssm = H * P
    proj = _project(p["w_ssm_in"], _times(h, cfg.ssm_in_multiplier, dtype),
                  dtype).astype(f32)
    proj = proj * by_columns(cfg.ssm_parts)
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * G * N], axis=1)
    xbc, tail = ssd.causal_conv(xbc, state["conv"][0], p["conv_w"],
                                p["conv_b"])
    x, B, C = jnp.split(jax.nn.silu(xbc), [d_ssm, d_ssm + G * N], axis=1)
    y, scanned = ssd.ssd_scan(
        x.astype(dtype), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), B.astype(dtype), C.astype(dtype), n_heads=H,
        n_groups=G, chunk=cfg.ssm_chunk, initial_state=state["ssm"][0],
        **kernel_opts)
    y = y.astype(f32) + jnp.repeat(p["D"], P) * x
    # Gate, THEN a group's RMS norm (``mamba_norm_before_gate`` false).
    y = rms_norm((y * jax.nn.silu(z)).reshape(S, G, d_ssm // G),
                 p["ssm_norm"].reshape(G, d_ssm // G), cfg.rms_norm_eps)
    scanned_out = _project(p["w_ssm_out"], y.reshape(S, d_ssm).astype(dtype),
                         dtype)

    mixed = (attended.astype(f32) * cfg.attention_out_multiplier
             + scanned_out.astype(f32) * cfg.ssm_out_multiplier).astype(dtype)
    return mixed[None], {"k": kc, "v": vc, "ssm": scanned[None],
                         "conv": tail[None]}


def _hybrid_ssm_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """Before a document's first token, TWO kinds of state a layer: the empty
    key and value cache of ``cache_len`` (padded) tokens in the stored dtype,
    which grows with position, and the scan's state and the convolution's
    tail, float32 zeros of fixed size."""
    n, dtype, f32 = cfg.n_layers, cfg.compute_dtype, jnp.float32
    cache = (n, batch, cfg.n_kv_heads, cache_len, cfg.d_head)
    return {"k": jnp.zeros(cache, dtype), "v": jnp.zeros(cache, dtype),
            "ssm": jnp.zeros((n, batch, cfg.ssm_n_heads, cfg.ssm_d_state,
                              cfg.ssm_d_head), f32),
            "conv": jnp.zeros((n, batch, cfg.ssm_d_conv - 1,
                               cfg.ssm_conv_dim), f32)}


def kind_rotary(cfg: DecoderLMConfig, kind: str) -> Tuple[np.ndarray, float]:
    """``(inverse frequencies [d_head / 2], factor)`` of a layer kind's
    rotary positions: YaRN's table and its attention factor (on cos AND sin:
    queries and keys both carry it, the scores its square) on a ``full``
    layer, the plain table of ``rope_theta`` and 1 on a ``window`` layer."""
    if kind == "full":
        return yarn_inv_freq(cfg, cfg.d_head), float(yarn_mscale(cfg))
    return plain_inv_freq(float(cfg.rope_theta), cfg.d_head).astype(
        np.float32), 1.0


@part("around")
def _window_gqa_mixer(p: Params, h: jax.Array, positions: jax.Array,
                      state, cfg: DecoderLMConfig, kernel_opts, kind: str):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's state). Grouped-query softmax attention, queries and keys
    RMS-normed a head and rotated by halves under the KIND's table and factor
    (:func:`kind_rotary`); the softmax scale goes into the rotated queries
    before they are rounded. A ``full`` layer's ``state`` is ``{"k", "v":
    [full layers, 1, Hkv, Lk, D], "layer"}``: the caches of ALL the full
    layers (``MIXER_CACHES``), written at ``layer`` and the segment's
    positions, attended there up to each query, and handed back whole (heads
    of half a lane tile lie two a row: ``causal_attention.cache_rows``). A
    ``window`` layer's is ``{"k", "v": [1, Hkv, sliding_window, D]}``, the
    LAST ``sliding_window`` keys and values before
    the segment: the segment attends ``[that tail | its own]``, each query
    the ``sliding_window`` keys up to itself, and hands on the last
    ``sliding_window`` of them. One document a program."""
    from agent_tpu.kernels import causal_attention

    if h.shape[0] != 1:
        raise ValueError("window_gqa runs one document a program")
    dtype, f32, eps = cfg.compute_dtype, jnp.float32, cfg.rms_norm_eps
    h = h[0]
    S = h.shape[0]
    pos0 = positions[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    inv, factor = kind_rotary(cfg, kind)

    def turned(x, norm, scale):
        x = rms_norm(x.astype(f32), norm, eps)
        return (rope_pairs(x, positions, inv, False) * scale).astype(dtype)

    q = turned(_project(p["wq"], h, dtype).reshape(S, hq, dh), p["q_norm"],
               factor * float(dh) ** -0.5)
    k = turned(_project(p["wk"], h, dtype).reshape(S, hkv, dh), p["k_norm"],
               factor).transpose(1, 0, 2)
    v = _project(p["wv"], h, dtype).reshape(S, hkv, dh).transpose(1, 0, 2)
    q = q.reshape(S, hkv, hq // hkv, dh).transpose(1, 2, 0, 3)
    if kind == "full":
        layer = state["layer"]
        kc = _write_cache(state["k"], causal_attention.cache_rows(k), layer,
                          pos0)
        vc = _write_cache(state["v"], causal_attention.cache_rows(v), layer,
                          pos0)
        o = causal_attention.causal_attention(q, kc, vc, pos0, layer,
                                              **kernel_opts)
    else:
        window = cfg.sliding_window
        kc = jnp.concatenate([state["k"][0], k], axis=1)
        vc = jnp.concatenate([state["v"][0], v], axis=1)
        o = causal_attention.window_attention(q, kc, vc, pos0, window=window,
                                              **kernel_opts)
        kc, vc = kc[None, :, -window:], vc[None, :, -window:]
    o = o.transpose(2, 0, 1, 3).reshape(1, S, hq * dh)
    return _project(p["wo"], o, dtype), {"k": kc, "v": vc}


def _window_gqa_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """Before a document's first token, by KIND: a full layer's empty key and
    value cache of ``cache_len`` (padded) tokens, a window layer's
    ``sliding_window`` keys and values that lie before the document (never
    attended: the window kernel masks by position), in the stored dtype."""
    from agent_tpu.kernels.causal_attention import cache_shape

    kinds = layer_kinds(cfg)
    periods = cfg.n_layers // len(kinds)

    def empty(kind, shape):
        shape = (periods * kinds.count(kind), batch, *shape)
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype)}

    return {"window": empty("window", (cfg.n_kv_heads, cfg.sliding_window,
                                       cfg.d_head)),
            "full": empty("full", cache_shape(cfg.n_kv_heads, cache_len,
                                              cfg.d_head))}


def _gated_by_head(o: jax.Array, gate: jax.Array, dtype) -> jax.Array:
    """``o [S, H, D]`` times ``sigmoid(gate [S, H])``, one gate a head
    (float32, rounded once) → ``[S, H x D]``."""
    o = o.astype(jnp.float32) * jax.nn.sigmoid(gate)[..., None]
    return o.astype(dtype).reshape(o.shape[0], -1)


@part("around")
def _hybrid_kda_mixer(p: Params, h: jax.Array, positions: jax.Array,
                      state, cfg: DecoderLMConfig, kernel_opts, kind: str):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's state), by the layer's KIND; both kinds gate their heads'
    outputs, one ``sigmoid(h W_og)`` a head, before the out-projection.

    ``latent``: dense latent attention with no query rank
    (:func:`_dense_latent_attention`); ``state``: ``{"kv": [1, Lk,
    kv_lora_rank + rope]}``.

    ``linear``: ``q, k, v = SiLU(conv(h W))`` (a causal depthwise
    convolution of ``kda_conv``, no bias), q and k L2-normalised a head and q
    scaled by ``d_head^-1/2``; ``beta = sigmoid(h W_beta)`` a head; the
    log-decay a channel ``kda_lower_bound x sigmoid(exp(A_log) (h W_a +
    dt_bias))``; the delta rule over the segment (``kernels/kda.py``); an
    RMS norm a head on what it returns. ``state``: ``{"S": [1, H, d, d]``
    float32, ``"conv": [1, K - 1, 3 H d]`` float32 (the convolution's last
    inputs)``}``. One document a program."""
    if h.shape[0] != 1:
        raise ValueError("hybrid_kda runs one document a program")
    dtype, f32 = cfg.compute_dtype, jnp.float32
    h = h[0]
    S = h.shape[0]
    gate = _project_f32(p["w_og"], h, dtype)                      # [S, H]
    if kind == "latent":
        o, kv = _dense_latent_attention(p, h, positions, state, cfg,
                                        kernel_opts)
        o = _gated_by_head(o.reshape(S, cfg.n_heads, cfg.v_head_dim), gate,
                           dtype)
        return _project(p["wo"], o[None], dtype), {"kv": kv[None]}
    from agent_tpu.kernels import kda, ssd

    H, dh = cfg.n_heads, cfg.d_head
    hq = H * dh
    tails, heads = [], []
    for i, name in enumerate(("wq", "wk", "wv")):
        u, tail = ssd.causal_conv(
            _project(p[name], h, dtype), state["conv"][0, :, i * hq:(i + 1) * hq],
            p["conv_w"][:, i * hq:(i + 1) * hq], jnp.zeros((hq,), f32))
        tails.append(tail)
        heads.append(jax.nn.silu(u).reshape(S, H, dh))
    q, k, v = heads

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = (unit(q) * float(dh) ** -0.5).astype(dtype).reshape(S, hq)
    k = unit(k).astype(dtype).reshape(S, hq)
    beta = jax.nn.sigmoid(_project_f32(p["w_beta"], h, dtype))     # [S, H]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(p["A_log"]), dh)
        * (_project_f32(p["w_a"], h, dtype) + p["dt_bias"]))       # [S, H d]
    o, new = kda.kda_chunks(
        q, k, v.astype(dtype).reshape(S, hq), g, beta, n_heads=H,
        lower_bound=cfg.kda_lower_bound, initial_state=state["S"][0],
        **kernel_opts)
    o = rms_norm(o.reshape(S, H, dh), p["o_norm"], cfg.rms_norm_eps)
    return _project(p["wo"], _gated_by_head(o, gate, dtype)[None], dtype), {
        "S": new[None], "conv": jnp.concatenate(tails, axis=1)[None]}


def _hybrid_kda_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """Before a document's first token, by KIND: a linear layer's float32
    state and convolution tail, zeros of fixed size; a latent layer's empty
    cache of ``cache_len`` (padded) tokens in the stored dtype."""
    f32 = jnp.float32
    count = {kind: sum(len(of[kind]) for of in layers_of_kinds(cfg).values()
                       if kind in of) for kind in ("linear", "latent")}
    return {
        "linear": {
            "S": jnp.zeros((count["linear"], batch, cfg.n_heads, cfg.d_head,
                            cfg.d_head), f32),
            "conv": jnp.zeros((count["linear"], batch, cfg.kda_conv - 1,
                               3 * cfg.n_heads * cfg.d_head), f32)},
        "latent": {"kv": jnp.zeros(
            (count["latent"], batch, cache_len,
             cfg.kv_lora_rank + cfg.qk_rope_head_dim), cfg.compute_dtype)}}


@part("around")
def _conv_gqa_mixer(p: Params, h: jax.Array, positions: jax.Array,
                    state, cfg: DecoderLMConfig, kernel_opts, kind: str):
    """h [1, S, d] (normed) → (what enters the residual [1, S, d], the
    layer's state), by the layer's KIND.

    ``full``: :func:`_window_gqa_mixer`'s full kind, the one function (a
    per-head RMS norm on queries and keys, the rotation by halves under the
    plain table where the config names no scaling, every causal key).

    ``conv``: ``[B | C | z] = h W_in``; ``g = B x z``; ``c_t = sum_i w_i
    g_{t - (K-1) + i}`` a channel (causal, depthwise, ``conv_taps`` taps, no
    bias, zeros before the document); ``(C x c) W_out``. No activation.
    ``state``: ``{"tail": [1, K - 1, d]`` float32, the last rows of ``g``
    before the segment``}``. The gates and the taps are stated on the
    projection's rounded columns in float32 and rounded once, under one
    scope (``conv_gate``) and with nothing between them, so that XLA may
    fuse them as it finds best (on the chip it keeps the projection in the
    core's memory and rides the taps and ``C x`` inside the out-projection's
    fusion: PERF.md section 5). One document a program."""
    if kind == "full":
        return _window_gqa_mixer(p, h, positions, state, cfg, kernel_opts,
                                 kind)
    from agent_tpu.kernels import ssd

    if h.shape[0] != 1:
        raise ValueError("conv_gqa runs one document a program")
    dtype, f32, d = cfg.compute_dtype, jnp.float32, cfg.d_model
    proj = _project(p["w_conv_in"], h[0], dtype)                 # [S, 3 d]
    with part("mixer"), jax.named_scope("conv_gate"):
        B, C, z = (proj[:, i * d:(i + 1) * d].astype(f32) for i in range(3))
        c, tail = ssd.causal_conv(B * z, state["tail"][0], p["conv_w"])
        y = (C * c).astype(dtype)
    return _project(p["wo"], y[None], dtype), {"tail": tail[None]}


def _conv_gqa_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """Before a document's first token, by KIND: a ``conv`` layer's tail,
    float32 zeros of ``conv_taps - 1`` rows (what lies before a document is
    0); an attention layer's empty key and value cache of ``cache_len``
    (padded) tokens in the stored dtype, laid out as the attention kernel
    reads it (``causal_attention.cache_shape``)."""
    from agent_tpu.kernels.causal_attention import cache_shape

    by_layer = kinds_by_layer(cfg)
    cache = (by_layer.count("full"), batch,
             *cache_shape(cfg.n_kv_heads, cache_len, cfg.d_head))
    return {"conv": {"tail": jnp.zeros(
                (by_layer.count("conv"), batch, cfg.conv_taps - 1,
                 cfg.d_model), jnp.float32)},
            "full": {"k": jnp.zeros(cache, cfg.compute_dtype),
                     "v": jnp.zeros(cache, cfg.compute_dtype)}}


# mixer name → fn(layer params, normed h, positions, state, cfg, opts)
# → (what enters the residual [B, L, d], new state); a mixer whose layers
# come in kinds (``layer_kinds``) takes the layer's ``kind`` besides. One
# entry a sequence mixer; the out-projection is the mixer's (one has two).
MIXERS: Dict[str, Callable] = {"power_retention": _power_retention_mixer,
                               "sparse_mla": _sparse_mla_mixer,
                               "hybrid_ssm": _hybrid_ssm_mixer,
                               "dense_mla": _dense_mla_mixer,
                               "window_gqa": _window_gqa_mixer,
                               "hybrid_kda": _hybrid_kda_mixer,
                               "conv_gqa": _conv_gqa_mixer}
# mixer name → fn(cfg, batch, cache_len) → the state before a document's
# first segment, for the mixers whose state is allocated (a cache); the
# others start from ``None``.
MIXER_STATES: Dict[str, Callable] = {"sparse_mla": _sparse_mla_state,
                                     "hybrid_ssm": _hybrid_ssm_state,
                                     "dense_mla": _dense_mla_state,
                                     "window_gqa": _window_gqa_state,
                                     "hybrid_kda": _hybrid_kda_state,
                                     "conv_gqa": _conv_gqa_state}
# mixer name → which of its state is a cache that grows with the document and
# that the mixer writes and reads IN PLACE: booleans in a tree that is a
# prefix of the state's own. Those leaves are the layer scan's carry
# (:func:`_caches_apart`), the whole ``[layers, ...]`` stack handed to the
# mixer with ``"layer"`` beside it; everything else is stepped over a layer's
# slice at a time (small states of fixed size, and the latent caches, whose
# kernels take standalone operands).
MIXER_CACHES: Dict[str, Any] = {
    "hybrid_ssm": {"k": True, "v": True, "ssm": False, "conv": False},
    "window_gqa": {"full": True, "window": False},
    "conv_gqa": {"full": True, "conv": False}}


def starts_from_nothing(cfg: DecoderLMConfig) -> bool:
    """Whether :func:`init_state` is ``None``, from the config alone."""
    return cfg.mixer not in MIXER_STATES and not cfg.n_experts


@part("around")
def init_state(cfg: DecoderLMConfig, batch: int, cache_len: int):
    """What :func:`forward_segment` takes as ``state`` for a document's
    FIRST segment: ``None`` where the mixer starts from nothing and no layer
    routes; else ``{"mixer": the mixer's empty state or None, "pairs": 0}``,
    with ``"tiles": {"visited": 0, "rows": 0}`` beside them where the layers
    come in kinds (see :func:`forward_segment`)."""
    make = MIXER_STATES.get(cfg.mixer)
    mixer = make(cfg, batch, cache_len) if make else None
    if cfg.n_experts:
        zero = jnp.zeros((), jnp.float32)
        return {"mixer": mixer, "pairs": zero,
                **({"tiles": {"visited": zero, "rows": zero}}
                   if layer_kinds(cfg) else {})}
    return mixer


@part("ffn")
def _swiglu(p: Params, n: jax.Array, names, dtype, gate_multiplier=1.0,
            down_multiplier=1.0, limit=None) -> jax.Array:
    """``limit`` (a scalar, infinity for none; ``None``: the model has no
    such list): ``silu(min(gate, limit)) x clip(up, -limit, limit)``."""
    gate, up, down = names
    g = _times(linear(p[gate], n, dtype), gate_multiplier, dtype)
    if limit is None:
        ff = jax.nn.silu(g) * linear(p[up], n, dtype)
    else:
        limit = limit.astype(dtype)
        ff = jax.nn.silu(jnp.minimum(g, limit)) * jnp.clip(
            linear(p[up], n, dtype), -limit, limit)
    return _times(linear(p[down], ff, dtype), down_multiplier, dtype)


def _plain_weights(leaf: Any, dtype) -> jax.Array:
    """A leaf [..., in, out] in the compute dtype for a kernel that takes
    plain weights (the grouped expert matmul, the latents' expansion): as
    stored, or an int8 table (``models.quant``) times its scales."""
    if isinstance(leaf, dict):
        table = leaf["w_q"] if "w_q" in leaf else leaf["w8"]
        return (table.astype(jnp.float32)
                * leaf["w_scale"][..., None, :]).astype(dtype)
    return leaf.astype(dtype)


@part("experts")
def _experts_ffn(p: Params, n: jax.Array, cfg: DecoderLMConfig, kernel_opts):
    """n [B, S, d] (normed) → (shared expert, where the model has one, + the
    routed experts held here, [B, S, d]; the (token, expert) pairs routed
    here: a float32 scalar, or ``{"pairs", "tiles"}`` where the model's
    layers come in kinds: ``moe.held_work`` beside them, the ``ROW_TILE``
    tiles those pairs fill and the rows the grouped matmul computes for
    them). ``p``: one layer's
    leaves; where it has ``expert_layer`` (:func:`_read_in_place`), its
    ``EXPERT_LEAVES`` are the whole group's stacks, already plain and in the
    compute dtype, and that is the layer."""
    from agent_tpu.models import moe

    dtype = cfg.compute_dtype
    B, S, d = n.shape
    flat = n.reshape(B * S, d)
    # Scores that decide a discrete choice stay in float32.
    if isinstance(p["w_router"], dict):
        logits = linear(p["w_router"], flat, jnp.float32)
    else:
        logits = jnp.dot(flat.astype(dtype), p["w_router"].astype(dtype),
                         preferred_element_type=jnp.float32)
    if cfg.scoring_func == "softmax":
        experts, gates = moe.route_softmax(
            logits, top_k=cfg.n_experts_per_token, scale=cfg.routed_scale)
    else:
        experts, gates = moe.route_sigmoid_grouped(
            logits, p["router_bias"], n_groups=cfg.n_expert_groups,
            groups_kept=cfg.n_groups_per_token, top_k=cfg.n_experts_per_token,
            scale=cfg.routed_scale)
    routed, pairs = moe.held_experts_ffn(
        flat.astype(dtype), experts, gates,
        *(_plain_weights(p[name], dtype) for name in EXPERT_LEAVES),
        cfg.expert_first, layer=p.get("expert_layer"),
        limit=p.get("expert_limit"), **kernel_opts)
    if _holds(cfg, "ws_gate"):
        shared = _swiglu(p, flat, ("ws_gate", "ws_up", "ws_down"), dtype,
                         limit=p.get("shared_limit"))
        routed = shared.astype(jnp.float32) + routed
    y = routed.astype(dtype).reshape(B, S, d)
    counted = pairs.astype(jnp.float32)
    if layer_kinds(cfg):
        work = moe.held_work(experts, cfg.expert_first, cfg.n_experts_held)
        counted = {"pairs": counted, "tiles": {
            name: n.astype(jnp.float32) for name, n in work.items()}}
    return y, counted


def _read_in_place(leaves: Params, dtype) -> Tuple[Params, Params]:
    """A group's stacked leaves → (those the layer scan takes a layer's slice
    of, those its step reads whole). The grouped expert matmul is a custom
    call and wants a standalone operand: a layer's slice of the held experts'
    leaves handed to it is a COPY of all of them, made once a layer a segment
    to be read once, where the stack itself is the loop's invariant and the
    kernel indexes it. So ``EXPERT_LEAVES`` stored as plain arrays in the
    compute dtype stay out of the scan, and ``expert_layer`` (the layer's
    number in the stack) is scanned in their place. Leaves that have to be
    cast or dequantized (an int8 table is a ``dict``) are sliced first, as
    every other leaf is: the product is a fresh array either way, and
    casting the whole stack inside the loop would be the copy again."""
    if not all(name in leaves and not isinstance(leaves[name], dict)
               and leaves[name].dtype == dtype for name in EXPERT_LEAVES):
        return leaves, {}
    whole = {name: leaves[name] for name in EXPERT_LEAVES}
    scanned = {k: v for k, v in leaves.items() if k not in whole}
    scanned["expert_layer"] = jnp.arange(whole["we_gate"].shape[0],
                                         dtype=jnp.int32)
    return scanned, whole


def _layer(p: Params, x: jax.Array, positions, state, cfg, kernel_opts,
           ffn: str = "dense", kind: Optional[str] = None):
    dtype = cfg.compute_dtype
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    mixed, state = MIXERS[cfg.mixer](p, h, positions, state, cfg, kernel_opts,
                                     **({"kind": kind} if kind else {}))
    with part("around"):
        x = x + mixed
    n = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    if ffn == "experts":
        y, pairs = _experts_ffn(p, n, cfg, kernel_opts)
        with part("around"):
            return x + y, state, pairs
    y = _swiglu(p, n, ("w_gate", "w_up", "w_down"), dtype,
                cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier)
    with part("around"):
        return x + y, state, jnp.zeros((), jnp.float32)


def _caches_apart(state, caches):
    """A mixer's state, or a part of it → (what the layer scan steps over a
    layer's slice at a time, what it CARRIES whole), by ``caches`` (a
    mixer's ``MIXER_CACHES`` or the same part of it; ``None``: nothing is
    carried). Each tree has ``None`` where the other has the leaves. A cache
    stepped over (the scan's ``xs`` and ``ys``) is sliced out of the stack,
    copied for the attention's custom call, written back, and the stack
    copied whole once a segment, donated or not."""
    if state is None or caches is None:
        return state, None
    def those(carried: bool):
        return jax.tree_util.tree_map(
            lambda cache, leaves: leaves if cache == carried else None,
            caches, state)

    return those(False), those(True)


def _caches_joined(stepped, carried, caches, layer=None):
    """:func:`_caches_apart` undone; with ``layer`` (the layer's number in the
    carried stacks) beside the leaves where a mixer is to be handed them."""
    if carried is None:
        return stepped
    state = jax.tree_util.tree_map(
        lambda cache, one, whole: whole if cache else one, caches, stepped,
        carried)
    return state if layer is None else {**state, "layer": layer}


def _scan_layers(params: Params, x: jax.Array, positions, mixer_state, pairs,
                 cfg: DecoderLMConfig, kernel_opts):
    """The layer scan of a model whose layers are all alike: group by group
    (``layer_groups``), one step a layer, its slice of the state scanned
    beside its leaves and the mixer's caches carried beside ``x``
    (:func:`_caches_apart`). Returns ``(x, the new mixer state, pairs)``."""
    caches = MIXER_CACHES.get(cfg.mixer)
    stepped, carried = _caches_apart(mixer_state, caches)
    new_states = []
    for group, ffn, first, n in cfg.layer_groups:
        with part("around"):
            mine = jax.tree_util.tree_map(lambda a: a[first:first + n],
                                          stepped)
            layers = None if carried is None else first + jnp.arange(
                n, dtype=jnp.int32)
        record_caches_in_place(
            cfg.mixer, n * len(jax.tree_util.tree_leaves(carried)))

        scanned, whole = _read_in_place(params[group], cfg.compute_dtype)

        def step(carry, xs, ffn=ffn, whole=whole):
            x, pairs, carried = carry
            p, st, layer = xs
            x, st, more = _layer(
                {**p, **whole}, x, positions,
                _caches_joined(st, carried, caches, layer), cfg, kernel_opts,
                ffn)
            st, carried = _caches_apart(st, caches)
            return (x, pairs + more, carried), st

        # The loop's own work (a layer's leaves and state sliced out of the
        # stack, the new state written into it) is ``around``: copies the
        # model's stacking asks for; every part inside the body is its own.
        with part("around"):
            (x, pairs, carried), st = jax.lax.scan(
                step, (x, pairs, carried), (scanned, mine, layers))
        new_states.append(st)
    with part("around"):
        stepped = new_states[0] if len(new_states) == 1 else \
            jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, 0),
                                   *new_states)
    return x, _caches_joined(stepped, carried, caches), pairs


def _scan_periods(leaves: Params, x: jax.Array, positions, mixer_state,
                  counted, cfg: DecoderLMConfig, kernel_opts, ffn: str,
                  kinds: Tuple[str, ...]):
    """The layer scan of a model whose layers come in kinds: one step a
    PERIOD (``kinds``: :func:`group_kinds`), its body the period's layers one
    after another, each with its kind's share of the state. ``leaves``: the
    group's stacked leaves ``[layers, ...]``, seen as ``[periods, a period's
    layers, ...]`` (a bitcast), and under ``"mixers"``, where the mixer's
    kinds hold DIFFERENT leaves, ``{kind: that kind's leaves [the kind's
    layers, ...]}``, seen as ``[periods, the kind's layers a period, ...]``:
    a layer takes the feed-forward's leaves at its place in the period and
    the mixer's at its place among its kind. The expert stacks stay whole
    and are read in
    place by the layer's number (:func:`_read_in_place`: ``period x a
    period's layers + place``). ``mixer_state``: ``{kind: leaves [that
    kind's layers, ...]}``, stepped over a period's layers of the kind, but
    for the mixer's caches: those are carried whole (:func:`_caches_apart`)
    and the layer's number among its kind's is ``period x the kind's layers
    a period + its place among them``.
    ``counted``: what the expert layers count, added up along the way.
    Returns ``(x, the new mixer state, counted)``."""
    every = len(kinds)
    of_kinds = sorted(set(kinds))          # one order, one text
    caches = MIXER_CACHES.get(cfg.mixer) or dict.fromkeys(of_kinds, False)

    def by_period(tree, n):
        return jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] // n, n, *a.shape[1:]), tree)

    with part("around"):
        leaves = dict(leaves)
        mixers = leaves.pop("mixers", {})
        scanned, whole = _read_in_place(leaves, cfg.compute_dtype)
        periods = jax.tree_util.tree_leaves(scanned)[0].shape[0] // every
        stepped, carried = _caches_apart(
            {kind: mixer_state[kind] for kind in of_kinds},
            {kind: caches[kind] for kind in of_kinds})
        xs = (by_period(scanned, every),
              {kind: by_period(stepped[kind], kinds.count(kind))
               for kind in of_kinds},
              jnp.arange(periods, dtype=jnp.int32),
              *([{kind: by_period(mixers[kind], kinds.count(kind))
                  for kind in of_kinds}] if mixers else []))
    record_caches_in_place(cfg.mixer, sum(
        periods * kinds.count(kind) * len(jax.tree_util.tree_leaves(
            carried[kind])) for kind in of_kinds))

    def at(tree, place):
        return jax.tree_util.tree_map(lambda a: a[place], tree)

    def step(carry, xs):
        x, counted, carried = carry
        period, states, number, *by_kind = xs
        seen = {kind: 0 for kind in states}
        new = {kind: [] for kind in states}
        for place, kind in enumerate(kinds):
            with part("around"):
                p, st = at(period, place), at(states[kind], seen[kind])
                if by_kind:
                    p = {**p, **at(by_kind[0][kind], seen[kind])}
                st = _caches_joined(
                    st, carried[kind], caches[kind],
                    number * kinds.count(kind) + seen[kind])
            x, st, more = _layer({**p, **whole}, x, positions, st, cfg,
                                 kernel_opts, ffn, kind)
            if ffn == "experts":           # a dense layer counts nothing
                counted = jax.tree_util.tree_map(jnp.add, counted, more)
            seen[kind] += 1
            st, kept = _caches_apart(st, caches[kind])
            carried = {**carried, kind: kept}
            new[kind].append(st)
        with part("around"):
            return (x, counted, carried), {kind: jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *sts) for kind, sts in new.items()}

    with part("around"):
        (x, counted, carried), new = jax.lax.scan(
            step, (x, counted, carried), xs)
        new = jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), new)
    return x, _caches_joined(new, carried, {
        kind: caches[kind] for kind in of_kinds}), counted


def _scan_groups_of_kinds(params: Params, x: jax.Array, positions,
                          mixer_state, counted, cfg: DecoderLMConfig,
                          kernel_opts):
    """:func:`_scan_periods` group by group (``layer_groups``): each group
    takes its layers' share of every kind's state (a kind's layers are
    stacked in the groups' order) and the shares are joined again."""
    groups = cfg.layer_groups
    if len(groups) == 1:
        (group, ffn, _, _), = groups
        return _scan_periods(params[group], x, positions, mixer_state,
                             counted, cfg, kernel_opts, ffn,
                             group_kinds(cfg, ffn))
    of = layers_of_kinds(cfg)
    at = {kind: 0 for kind in mixer_state}
    new = {kind: [] for kind in mixer_state}
    for group, ffn, _, _ in groups:
        with part("around"):
            mine = {kind: jax.tree_util.tree_map(
                lambda a, lo=at[kind], n=len(numbers): a[lo:lo + n],
                mixer_state[kind]) for kind, numbers in of[group].items()}
        x, mine, counted = _scan_periods(
            params[group], x, positions, mine, counted, cfg, kernel_opts,
            ffn, group_kinds(cfg, ffn))
        for kind, st in mine.items():
            new[kind].append(st)
            at[kind] += len(of[group][kind])
    with part("around"):
        return x, {kind: sts[0] if len(sts) == 1 else jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a, 0), *sts)
            for kind, sts in new.items()}, counted


def forward_segment(params: Params, ids: jax.Array, pos0: jax.Array,
                    state, cfg: DecoderLMConfig, **kernel_opts):
    """One fixed-shape segment of a document: ids [B, S] int32, ``pos0`` the
    position of its first token, ``state`` what the previous segment
    returned, or :func:`init_state` for the document's first. The mixer's
    state is a pytree with a leading layer axis (``None``: a mixer that
    starts from nothing); where the model has expert layers it comes inside
    ``{"mixer": ..., "pairs": ...}``, ``pairs`` the running count of (token,
    expert) pairs routed to the experts held here (a model whose layers come
    in kinds has ``tiles`` beside it: ``{"visited", "rows"}``, the tiles the
    grouped matmul visited for them and the rows it computed, and its
    ``mixer`` is ``{kind: leaves}``, :func:`_scan_periods`).
    Returns the final-normed
    hidden states [B, S, d] and the state after the segment."""
    with part("embed"):
        x = _times(params["embed"][ids], cfg.embedding_multiplier,
                   cfg.compute_dtype)
    positions = pos0.astype(jnp.int32) + jnp.arange(ids.shape[1])
    routed = bool(cfg.n_experts)
    mixer_state = state["mixer"] if routed and state is not None else state
    pairs = state["pairs"] if routed and state is not None else jnp.zeros(
        (), jnp.float32)
    if layer_kinds(cfg):
        if state is None:
            raise ValueError("layers in kinds start from init_state")
        counted = ({k: v for k, v in state.items() if k != "mixer"}
                   if routed else pairs)
        x, mixer_state, counted = _scan_groups_of_kinds(
            params, x, positions, mixer_state, counted, cfg, kernel_opts)
    else:
        x, mixer_state, pairs = _scan_layers(params, x, positions,
                                             mixer_state, pairs, cfg,
                                             kernel_opts)
        counted = {"pairs": pairs}
    # The head's multiplier goes into the hidden states: for a power of two
    # (the published 2^-7) the logits are the same numbers, bit for bit.
    hidden = _times(rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
                    cfg.lm_head_multiplier, cfg.compute_dtype)
    return hidden, ({"mixer": mixer_state, **counted} if routed
                    else mixer_state)


def _retention_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    from agent_tpu.kernels.power_retention import retention_chunk

    d, dh = float(cfg.d_model), cfg.d_head
    chunk = retention_chunk(int(t))
    hq, hkv = float(cfg.n_heads * dh), float(cfg.n_kv_heads * dh)
    state = 2.0 * (dh // 2 + 1) * dh * dh
    return (2.0 * d * (2.0 * hq + 2.0 * hkv + cfg.n_kv_heads),
            cfg.n_heads * (4.0 * chunk * dh + state) + cfg.n_kv_heads * state)


def _latent_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    d = float(cfg.d_model)
    h, qr, kvr = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    proj = 2.0 * (d * qr + qr * h * (dn + dr) + d * (kvr + dr) + h * dv * d)
    # Every causal pair's score and value product on expanded keys, and the
    # expansion of the keys seen so far; under a selection, the index
    # scores of every causal pair and the indexer's projections besides.
    seen = pos0 + t / 2.0
    mixer = 2.0 * h * (dn + dr + dv) * seen + (
        2.0 * kvr * h * (dn + dv) * (pos0 + t) / t)
    if _holds(cfg, "wi_k"):
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        proj += 2.0 * (qr * hi * di + d * di + d * hi)
        mixer += 2.0 * hi * di * seen
    return proj, mixer


def _hybrid_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    d = float(cfg.d_model)
    dh, hq, hkv = cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    H, P, N = cfg.ssm_n_heads, cfg.ssm_d_head, cfg.ssm_d_state
    c, d_ssm = cfg.ssm_chunk, cfg.ssm_n_heads * cfg.ssm_d_head
    proj = 2.0 * d * (2 * hq * dh + 2 * hkv * dh + cfg.ssm_in_dim) + (
        2.0 * d_ssm * d)
    # Every causal pair's score and value product; a chunk's block, the
    # state's read and update a head, C B^T once a group.
    return proj, 4.0 * hq * dh * (pos0 + t / 2.0) + H * (
        2.0 * c * P + 4.0 * N * P) + 2.0 * cfg.ssm_n_groups * c * N


def _window_gqa_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    d = float(cfg.d_model)
    dh, hq, hkv = cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    # A layer's score and value products by its KIND: every causal pair on a
    # full layer, the window's on the others (``min(position + 1,
    # sliding_window)`` keys a token); the mean over a period's layers.
    kinds, w = layer_kinds(cfg), cfg.sliding_window
    ramp = max(0, min(pos0 + int(t), w) - pos0)     # tokens still under w keys
    in_window = (ramp * (2 * pos0 + ramp + 1) / 2.0 + (t - ramp) * w) / t
    keys = {"full": pos0 + t / 2.0, "window": in_window}
    return (2.0 * d * (2 * hq * dh + 2 * hkv * dh),
            4.0 * hq * dh * sum(keys[kind] for kind in kinds) / len(kinds))


def _hybrid_kda_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    from agent_tpu.kernels.kda import kernel_flops_per_token

    d = float(cfg.d_model)
    h, dh, kvr = cfg.n_heads, cfg.d_head, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    # A layer's projections and its mixer's own work by its KIND: the delta
    # rule's chunked form on a linear layer; on the latent one every causal
    # pair on expanded keys and the expansion of the keys seen so far; the
    # mean over the model's layers.
    kinds = [kind for of in layers_of_kinds(cfg).values()
             for kind, numbers in of.items() for _ in numbers]
    proj = {"linear": 2.0 * d * (5 * h * dh + 2 * h),
            "latent": 2.0 * (d * h * (dn + dr) + d * (kvr + dr) + h * dv * d
                             + d * h)}
    mixer = {"linear": float(h * kernel_flops_per_token(dh)),
             "latent": 2.0 * h * (dn + dr + dv) * (pos0 + t / 2.0) + (
                 2.0 * kvr * h * (dn + dv) * (pos0 + t) / t)}
    return (sum(proj[kind] for kind in kinds) / len(kinds),
            sum(mixer[kind] for kind in kinds) / len(kinds))


def _conv_gqa_flops(cfg: DecoderLMConfig, t: float, pos0: int):
    d = float(cfg.d_model)
    dh, hq, hkv = cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    # A layer's projections and its mixer's own work by its KIND: the two
    # gates and the taps on a conv layer, every causal pair's score and value
    # product on an attention layer; the mean over the model's layers.
    kinds = kinds_by_layer(cfg)
    proj = {"conv": 2.0 * d * 4 * d,
            "full": 2.0 * d * (2 * hq * dh + 2 * hkv * dh)}
    mixer = {"conv": (2.0 * cfg.conv_taps + 2.0) * d,
             "full": 4.0 * hq * dh * (pos0 + t / 2.0)}
    return (sum(proj[kind] for kind in kinds) / len(kinds),
            sum(mixer[kind] for kind in kinds) / len(kinds))


# mixer name → fn(cfg, tokens, pos0) → (projections', mixer's own) FLOPs a
# token a layer of a segment of ``tokens`` that starts at ``pos0``.
MIXER_FLOPS: Dict[str, Callable] = {"power_retention": _retention_flops,
                                    "sparse_mla": _latent_flops,
                                    "hybrid_ssm": _hybrid_flops,
                                    "dense_mla": _latent_flops,
                                    "window_gqa": _window_gqa_flops,
                                    "hybrid_kda": _hybrid_kda_flops,
                                    "conv_gqa": _conv_gqa_flops}


def segment_flops(cfg: DecoderLMConfig, n_tokens: int, pos0: int) -> float:
    """Forward FLOPs the program does for one dispatched segment of
    ``n_tokens`` tokens that starts at ``pos0`` (matmul terms, the ``device_
    mfu{op}`` numerator): projections and feed-forwards per layer, the
    mixer's own count, the untied head."""
    d, t = float(cfg.d_model), float(n_tokens)
    proj, mixer = MIXER_FLOPS[cfg.mixer](cfg, t, pos0)
    dense = 6.0 * d * cfg.d_ff
    experts = 2.0 * d * cfg.n_experts + 6.0 * d * cfg.d_expert * (
        cfg.n_shared_experts + cfg.n_experts_per_token
        * cfg.n_experts_held / max(1, cfg.n_experts))
    ffn = sum(n * (experts if kind == "experts" else dense)
              for _, kind, _, n in cfg.layer_groups)
    return t * (cfg.n_layers * (proj + mixer) + ffn
                + 2.0 * d * cfg.vocab_size)


@part("head")
def blocked_logprobs(hidden: jax.Array, head: jax.Array, targets: jax.Array,
                     vocab_block: Optional[int] = None) -> jax.Array:
    """log p(target) per position, float32, never holding more than a
    [N, vocab_block] block of logits: a running log-sum-exp and the target's
    logit, vocabulary block by block. hidden [N, d], head [V, d] (rows are
    vocabulary entries), targets [N] int32."""
    V = head.shape[0]
    vb = min(int(vocab_block or VOCAB_BLOCK), V)
    n_full, tail = divmod(V, vb)
    N = hidden.shape[0]
    f32 = jnp.float32

    def fold(carry, w, offset):
        m, l, hit = carry
        logits = jax.lax.dot_general(hidden, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)  # [N, rows]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[:, None]).sum(axis=-1)
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mine = cols == (targets - offset)[:, None]
        return m_new, l, hit + jnp.where(mine, logits, 0.0).sum(axis=-1)

    carry = (jnp.full((N,), -jnp.inf, f32), jnp.zeros((N,), f32),
             jnp.zeros((N,), f32))
    carry = jax.lax.fori_loop(
        0, n_full, lambda i, c: fold(
            c, jax.lax.dynamic_slice_in_dim(head, i * vb, vb, 0), i * vb),
        carry)
    if tail:
        carry = fold(carry, head[n_full * vb:], n_full * vb)
    m, l, hit = carry
    return hit - (m + jnp.log(l))


@part("head")
def segment_block_sums(hidden: jax.Array, head: jax.Array,
                       targets: jax.Array, n_valid: jax.Array,
                       block: int = LOSS_BLOCK) -> jax.Array:
    """hidden [1, S, d], targets [1, S] (the NEXT token of every position),
    ``n_valid`` positions that have one → the log-probabilities summed a
    ``block`` of positions, [S / block] float32."""
    S = hidden.shape[1]
    lp = blocked_logprobs(hidden[0], head, targets[0])
    lp = jnp.where(jnp.arange(S) < n_valid, lp, 0.0)
    return lp.reshape(S // block, block).sum(axis=-1)
