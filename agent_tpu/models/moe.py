"""Mixture-of-Experts FFN with expert parallelism over an ``ep`` mesh axis.

The reference had no MoE (SURVEY.md §2.8: "No (no MoE models)"; the mesh
design brief was "must not preclude it"). This module goes one step further
and implements it, Mesh-TensorFlow/Switch style, in the einsum-dispatch
formulation that XLA shards well:

- Router: top-1 gating over ``n_experts`` with a capacity limit per expert
  (tokens over capacity are dropped — their residual path carries them, the
  standard Switch behavior).
- Dispatch/combine are one-hot einsums, so expert inputs materialize as an
  ``[E, C, d]`` tensor whose expert dim shards over ``ep`` — XLA inserts the
  all-to-all at the dispatch/combine boundaries when the mesh has an ``ep``
  axis (``moe_param_specs``/``expert_batch_spec``); on a 1-axis mesh the
  same program runs unsharded.
- Static shapes throughout: capacity is computed from a factor at init time,
  never from data.

``build_mesh`` already accepts arbitrary extra axes (``MESH_SHAPE=
"dp=2,ep=4"``), so this slots into the existing runtime unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from agent_tpu.models import layers
from agent_tpu.models.layers import Params
from agent_tpu.obs.trace import part


@dataclass(frozen=True)
class MoeConfig:
    d_model: int = 128
    d_ff: int = 512
    n_experts: int = 4
    capacity_factor: float = 1.25
    dtype: str = "float32"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def capacity(self, n_tokens: int) -> int:
        """Static per-expert token capacity for a given (padded) token count."""
        return max(1, int(np.ceil(n_tokens / self.n_experts * self.capacity_factor)))


def init_moe_ffn(key: jax.Array, cfg: MoeConfig) -> Params:
    """Router + expert-stacked FFN weights (expert dim first → ep-shardable)."""
    kr, k1, k2 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(cfg.d_model)
    scale_out = 1.0 / np.sqrt(cfg.d_ff)
    return {
        "router": {
            "w": jax.random.normal(kr, (cfg.d_model, cfg.n_experts), jnp.float32)
            * scale_in,
        },
        "wi": jax.random.normal(
            k1, (cfg.n_experts, cfg.d_model, cfg.d_ff), jnp.float32
        ) * scale_in,
        "wo": jax.random.normal(
            k2, (cfg.n_experts, cfg.d_ff, cfg.d_model), jnp.float32
        ) * scale_out,
    }


def moe_param_specs(cfg: MoeConfig = None) -> Params:
    """PartitionSpecs: experts over ``ep``, router replicated. The layout
    is structural (no config dependence); ``cfg`` stays for call-site
    symmetry with the other spec builders."""
    return {
        "router": {"w": P()},
        "wi": P("ep", None, None),
        "wo": P("ep", None, None),
    }


def expert_batch_spec() -> P:
    """[G, E, C, d] expert-batch tensors: expert dim over ``ep``."""
    return P(None, "ep", None, None)


# Routing group size (tokens). Capacity — and therefore the [t, E, C]
# dispatch/combine tensors and their einsums — scales with the token count
# being routed TOGETHER, so routing a whole serving batch as one group makes
# the dispatch einsums dominate (at BERT-base-8E serving shapes, B 1024 ×
# L 512, one group is 524k tokens). Bounded groups are the standard
# GShard/Switch answer — dispatch/FFN flops ≈ G·cf / (4·d_ff). Default 512 =
# one seq-512 row per group (capacity 80 at E=8/cf 1.25 — small-group drop
# variance still bounded). Rows/s by group size: not measured on the present
# tree (PERF.md §7: no cell runs `moe_experts > 0`). Tokens route
# independently per group; drops depend only on in-group competition.
MOE_GROUP_TOKENS = 512


def moe_ffn(params: Params, x: jax.Array, cfg: MoeConfig,
            mesh=None, group_size: int = 0) -> tuple:
    """Switch FFN. ``x``: [T, d_model] tokens → ([T, d_model], aux_loss).

    Returns the combined expert outputs (zero rows for capacity-dropped
    tokens — callers add the residual) and the load-balancing auxiliary loss
    (mean fraction·probability product, Switch §2.2 shape).

    Tokens are routed in fixed groups of ``group_size`` (default
    ``MOE_GROUP_TOKENS``; a T below that is one group, so small inputs keep
    the exact ungrouped semantics) with per-group expert capacity
    ``cfg.capacity(group)`` — see the ``MOE_GROUP_TOKENS`` note for why
    unbounded groups are quadratically wrong. ``T`` is zero-padded up to a
    group multiple; pad tokens route like real ones (they can occupy
    capacity in the final, partial group only) and their outputs are
    discarded.

    With ``mesh`` given, the [G, E, C, d] expert batches carry an explicit
    ``expert_batch_spec`` sharding constraint so the expert dim provably
    lands on ``ep`` (not left to XLA propagation from the param specs).
    """
    dtype = cfg.compute_dtype
    T, d = x.shape
    E = cfg.n_experts
    if T == 0:  # empty token set: nothing to route, aux is defined as 0
        return x, jnp.float32(0.0)
    group = min(T, group_size or MOE_GROUP_TOKENS)
    pad = (-T) % group
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)], axis=0)
    n_g = (T + pad) // group
    C = cfg.capacity(group)
    xg = x.reshape(n_g, group, d)

    logits = jnp.einsum(
        "gtd,de->gte", xg.astype(jnp.float32), params["router"]["w"]
    )                                                                # [g, t, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                          # [g, t]
    gate = jnp.take_along_axis(probs, expert_idx[..., None], axis=2)[..., 0]

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)        # [g, t, E]
    # Position of each token within its expert's in-group queue (0-based);
    # zero at non-routed experts, so summing over E extracts the position.
    pos = jnp.cumsum(onehot, axis=1) * onehot - onehot               # [g, t, E]
    # one_hot emits an all-zero row for pos >= C — that IS the capacity drop.
    pos_oh = jax.nn.one_hot(
        pos.sum(axis=-1).astype(jnp.int32), C, dtype=jnp.float32
    )                                                                # [g, t, C]
    dispatch = onehot[..., None] * pos_oh[:, :, None, :]             # [g, t, E, C]
    combine = dispatch * gate[..., None, None]

    def constrain(t):
        if mesh is None:
            return t
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, expert_batch_spec())
        )

    expert_in = constrain(jnp.einsum(
        "gtec,gtd->gecd", dispatch.astype(dtype), xg.astype(dtype)
    ))                                                               # [g, E, C, d]
    from agent_tpu.models import quant

    if quant.is_quantized(params["wi"]):
        # int8 expert FFN (quant.qmoe_expert): same W8A8 recipe as the dense
        # families, per-expert weight scales; router/dispatch/combine stay
        # high-precision.
        h = jax.nn.gelu(quant.qmoe_expert(params["wi"], expert_in, dtype))
        expert_out = constrain(quant.qmoe_expert(params["wo"], h, dtype))
    elif quant.is_weight_only(params["wi"]):
        # W8A16 expert FFN (quant.wmoe_expert): int8-resident expert tables,
        # activations stay in the compute dtype — the decode-mode recipe,
        # same per-expert scales and routing as the W8A8 path.
        h = jax.nn.gelu(quant.wmoe_expert(params["wi"], expert_in, dtype))
        expert_out = constrain(quant.wmoe_expert(params["wo"], h, dtype))
    else:
        h = jax.nn.gelu(jnp.einsum(
            "gecd,edf->gecf", expert_in, params["wi"].astype(dtype)
        ))
        expert_out = constrain(jnp.einsum(
            "gecf,efd->gecd", h, params["wo"].astype(dtype)
        ))
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(dtype), expert_out)
    y = y.reshape(n_g * group, d)[:T]

    # Switch load-balance aux loss: E · Σ_e fraction_e · mean_prob_e, per
    # routing group, averaged over groups (equal group sizes ⇒ identical to
    # the global formula when n_g == 1). Pad tokens are EXCLUDED from the
    # statistics: they route like real tokens (tail capacity slots only)
    # but a zero row's uniform-softmax argmax is expert 0, and counting
    # them would bias the router gradient against it every step T is not
    # a group multiple.
    valid = (
        jnp.arange(n_g * group).reshape(n_g, group) < T
    )[..., None].astype(jnp.float32)                                 # [g, t, 1]
    vcount = jnp.maximum(valid.sum(axis=1), 1.0)                     # [g, 1]
    fraction = (onehot * valid).sum(axis=1) / vcount                 # [g, E]
    mean_prob = (probs * valid).sum(axis=1) / vcount
    aux = ((fraction * mean_prob).sum(axis=-1) * E).mean()
    return y.astype(x.dtype), aux


def moe_block(params: Params, x: jax.Array, cfg: MoeConfig) -> tuple:
    """Pre-LN residual MoE block over [B, L, d] activations → (y, aux)."""
    B, L, d = x.shape
    h = layers.layer_norm(params["ln"], x).reshape(B * L, d)
    y, aux = moe_ffn(params["moe"], h, cfg)
    return x + y.reshape(B, L, d), aux


def init_moe_block(key: jax.Array, cfg: MoeConfig) -> Params:
    return {
        "ln": layers.init_layer_norm(cfg.d_model),
        "moe": init_moe_ffn(key, cfg),
    }


# ---- a held share of a routed expert layer --------------------------------
#
# The decoder language-model family's expert layer (``models/decoder_lm.py``):
# the router (sigmoid scores and group-limited, or a plain softmax: the
# config's ``scoring_func`` says which) scores ALL ``n_experts`` and picks
# ``top_k`` of them a token; the
# chip holds ``w_gate.shape[0]`` of them, ids ``first ..``, and computes the
# part of the result its own experts give. No capacity: no token is dropped.
# What the experts held elsewhere would add is left out here, as on one chip
# of an expert-parallel deployment before the combine.

def route_sigmoid_grouped(logits: jax.Array, bias: jax.Array, *,
                          n_groups: int, groups_kept: int, top_k: int,
                          scale: float):
    """logits [S, E] float32 → ``(experts [S, top_k] int32, gates [S, top_k]
    float32)``. Scores are ``sigmoid(logits)``; the CHOICE is by score +
    ``bias``: the experts in ``n_groups`` consecutive groups, a group's score
    the sum of its two largest, the ``groups_kept`` best groups kept, the
    ``top_k`` best experts among them (ties to the lower index). Gates are
    the chosen experts' scores (no bias) over their sum, times ``scale``."""
    S, E = logits.shape
    score = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = score + bias.astype(jnp.float32)
    if n_groups > 1:
        grouped = choice.reshape(S, n_groups, E // n_groups)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        kept = jax.lax.top_k(group_score, groups_kept)[1]        # [S, kept]
        keep = (kept[:, :, None] == jnp.arange(n_groups)).any(axis=1)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(S, E)
    experts = jax.lax.top_k(choice, top_k)[1].astype(jnp.int32)
    picked = jnp.take_along_axis(score, experts, axis=1)
    return experts, scale * picked / picked.sum(axis=-1, keepdims=True)


def route_softmax(logits: jax.Array, *, top_k: int, scale: float):
    """logits [S, E] float32 → ``(experts [S, top_k] int32, gates [S, top_k]
    float32)``: scores are ``softmax(logits)`` over ALL experts (no groups,
    no bias), the ``top_k`` largest are chosen (ties to the lower index:
    ``lax.top_k`` keeps the order of equal entries), and the gates are the
    chosen scores over their sum, times ``scale``."""
    score = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    picked, experts = jax.lax.top_k(score, top_k)
    return experts.astype(jnp.int32), scale * picked / picked.sum(
        axis=-1, keepdims=True)


def _held_dense(x, local, gates, w_gate, w_up, w_down, limit=None):
    """Every held expert on every token, weighted by the token's gate for
    it (0 where it did not choose it): the tests' sizes and off the chip."""
    f32 = jnp.float32

    def one(e, acc):
        g = jnp.where(local == e, gates, 0.0).sum(axis=-1)            # [S]
        if limit is None:
            h = jax.nn.silu(jnp.dot(x, w_gate[e])) * jnp.dot(x, w_up[e])
        else:
            lim = limit.astype(x.dtype)
            h = jax.nn.silu(jnp.minimum(jnp.dot(x, w_gate[e]), lim)) * jnp.clip(
                jnp.dot(x, w_up[e]), -lim, lim)
        return acc + g[:, None] * jnp.dot(h, w_down[e]).astype(f32)

    return jax.lax.fori_loop(0, w_gate.shape[0], one,
                             jnp.zeros(x.shape, f32))


def held_work(experts: jax.Array, first: int, n_held: int) -> dict:
    """What :func:`held_experts_ffn`'s grouped matmul does for the pairs of
    ``experts [S, k]`` routed to the experts ``first .. first + n_held - 1``,
    from one count an expert (int32 scalars): ``visited``, the ``ROW_TILE``
    tiles it visits, each expert's rows padded to whole tiles (pairs over
    tiles x ``ROW_TILE`` is how full they are); ``rows``, the rows its
    matmuls take, a tile's real rows rounded up to whole ``SUB_ROWS``
    sub-blocks (pairs over rows is the share of them that is a real row)."""
    from agent_tpu.kernels.grouped_ffn import ROW_TILE, SUB_ROWS

    local = (experts - first).reshape(-1)
    counts = (local[None, :] == jnp.arange(n_held)[:, None]).sum(axis=1)
    sub = min(SUB_ROWS, ROW_TILE)          # whole sub-blocks a tile
    return {"visited": ((counts + ROW_TILE - 1) // ROW_TILE).sum(),
            "rows": ((counts + sub - 1) // sub).sum() * sub}


@part("experts")
def held_experts_ffn(x: jax.Array, experts: jax.Array, gates: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     first: int, *, layer=None, limit=None, pallas=None,
                     interpret=None):
    """x [S, d]; experts, gates [S, k] of a router above;
    w_gate, w_up [Eh, d, f], w_down [Eh, f, d]: experts ``first .. first +
    Eh - 1``; or, with ``layer`` (an int32 scalar), the layers' stacks
    [L, Eh, d, f], [L, Eh, f, d] as the model holds them, of which that
    layer's experts are used; ``limit`` (a float32 scalar, infinity for
    none; ``None``: the model has no such list): the clamp inside an
    expert's SwiGLU, ``silu(min(gate, limit)) x clip(up, -limit, limit)``.
    Returns ``(sum over a token's chosen experts
    HELD HERE of gate x SwiGLU_e(x), float32 [S, d]; how many (token,
    expert) pairs that were)``. On the chip XLA moves INDICES only: the
    pairs sorted by expert, the counts, each expert's rows padded to whole
    tiles, and for every sorted row the token it reads and the slot it
    writes. The rows themselves are the kernels' (``kernels/grouped_ffn.py``):
    one grouped matmul over the tiles that hold rows fetches its real rows
    from ``x`` and writes them to slot ``j * S + token`` (a copy a row, a
    tile's copies waited for by size; a padded row of the tables is never
    copied either way, and of a tile's 256 rows the matmuls take the 128-row
    sub-blocks that hold a real one: :func:`held_work` counts them), a
    second pass combines the slots under the gates.
    The matmul reads a stack in place:
    inside a layer scan, hand it the stack and the layer's number, not the
    layer's slice (a copy of every held expert). Off the kernel the layer's
    slice is taken here."""
    from agent_tpu.kernels import grouped_ffn

    S, k = experts.shape
    n_held, d, fe = w_gate.shape[-3:]
    local = experts - first
    held = (local >= 0) & (local < n_held)
    pairs = held.sum()
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    if not (pallas and grouped_ffn.pallas_supported(d, fe, x.dtype)):
        if layer is not None:
            w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
        return _held_dense(x, jnp.where(held, local, -1), gates, w_gate,
                           w_up, w_down, limit), pairs
    from agent_tpu.kernels.flash_attention import resolve_interpret

    tm = grouped_ffn.ROW_TILE
    n_pairs = S * k
    n_tiles_max = -(-n_pairs // tm) + n_held
    of_pair = jnp.where(held, local, n_held).reshape(n_pairs)
    order = jnp.argsort(of_pair, stable=True)
    counts = (of_pair[None, :] == jnp.arange(n_held)[:, None]).sum(axis=1)
    first_pair = jnp.cumsum(counts) - counts       # in the sorted pairs
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tm
    tile = jnp.arange(n_tiles_max)
    tile_expert = jnp.minimum(jnp.searchsorted(tile_end, tile, side="right"),
                              n_held - 1)
    # How many of a tile's rows are real (a tile past the last holds none).
    tile_rows = jnp.clip(
        (first_row + counts)[tile_expert] - tile * tm, 0, tm)
    # Sorted row r of a tile is sorted pair r + (the tile's expert's first
    # pair - its first row): one number a tile, spread over the tile's rows.
    shift = jnp.repeat((first_pair - first_row)[tile_expert], tm)
    token, j = jnp.divmod(order[jnp.clip(
        jnp.arange(n_tiles_max * tm) + shift, 0, n_pairs - 1)], k)
    # Pair (token, j) writes row j * S + token: the pairs k-major, so the
    # combine reads the kernel's rows where they lie, one j after another.
    interpret = resolve_interpret(interpret)
    y_pairs = grouped_ffn.grouped_swiglu(
        x, token, j * S + token, tile_expert, tile_rows, w_gate, w_up,
        w_down, 0 if layer is None else layer, limit, n_slots=n_pairs,
        interpret=interpret)
    return grouped_ffn.combine_pairs(y_pairs, held, gates,
                                     interpret=interpret), pairs
