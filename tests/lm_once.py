"""What the decoder family's test files make ONCE a process, whatever the
number of cases that ask: a config's weights, its empty state, the shapes of
both, and the programs model code runs in (one segment, an expert layer, the
loss head).

``decoder_lm.init_params`` builds a fresh ``jax.jit`` a leaf shape and a
constant on every call, so every draw is three dozen compiles on the CPU
(7 s), each too small for the persistent cache to keep; model code called
eagerly is a compile a primitive a shape. A test file of this family takes
its weights from :func:`params` and runs model code through the programs
below or a ``jax.jit`` of its own, built once at module or fixture scope
(``tests/README.md``). ``--dist loadfile`` gives a file to one worker, so
"once a process" is at most once a file.

Every cache is keyed by the config (a frozen dataclass: equal by its items)
and the other arguments; ``tests/test_decoder_lm.py`` holds the cached
weights to a fresh draw's, byte for byte, for a config of every mixer."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from agent_tpu.models import decoder_lm


def _own_containers(tree):
    """The same leaves in dicts of the caller's own: ``quantize_for_family``
    takes leaves OUT of the tree it is given, and a case may swap one."""
    return jax.tree_util.tree_map(lambda leaf: leaf, tree)


@functools.lru_cache(maxsize=None)
def _params(cfg, model_id):
    # ONE program a draw: under an outer ``jit`` the three dozen programs
    # ``init_params`` builds a call (one a leaf shape, one a constant) are
    # inlined into it: 3 s on this CPU where the call by itself takes 7, for
    # the same bytes (integer bits, then elementwise arithmetic).
    return jax.jit(lambda: decoder_lm.init_params(cfg, model_id))()


def params(cfg: decoder_lm.DecoderLMConfig, model_id: str):
    """``decoder_lm.init_params(cfg, model_id)``, drawn once."""
    return _own_containers(_params(cfg, model_id))


@functools.lru_cache(maxsize=None)
def _state(cfg, batch, cache_len):
    return decoder_lm.init_state(cfg, batch, cache_len)


def state(cfg: decoder_lm.DecoderLMConfig, batch: int, cache_len: int):
    """``decoder_lm.init_state(cfg, batch, cache_len)``, made once: for a
    program that does not donate it (none on the CPU does)."""
    return _own_containers(_state(cfg, batch, cache_len))


@functools.lru_cache(maxsize=None)
def param_shapes(cfg: decoder_lm.DecoderLMConfig):
    """The weights' shapes and dtypes, nothing drawn (they follow from the
    config alone): what a case lowers or compiles a program against."""
    return jax.eval_shape(lambda: decoder_lm.init_params(cfg, "shapes"))


@functools.lru_cache(maxsize=None)
def state_shapes(cfg: decoder_lm.DecoderLMConfig, batch: int, cache_len: int):
    return jax.eval_shape(lambda: decoder_lm.init_state(cfg, batch, cache_len))


@functools.lru_cache(maxsize=None)
def _segment_program(cfg, opts):
    return jax.jit(lambda p, ids, pos0, st: decoder_lm.forward_segment(
        p, ids, pos0, st, cfg, **dict(opts)))


def segment_program(cfg: decoder_lm.DecoderLMConfig, **kernel_opts):
    """``jit(forward_segment)`` of a config under the given kernel options,
    built once: ``(params, ids, pos0, state) -> (hidden, state)``. Traced
    again only for new shapes or another tree of weights (an int8 one). NOT
    for a case that patches what the program traces through: a patch that
    arrives after the first trace is never seen, so such a case builds its
    own ``jax.jit`` under its patch."""
    return _segment_program(cfg, tuple(sorted(kernel_opts.items())))


# ---- model code as programs, one a config -----------------------------------

blocked_logprobs = jax.jit(decoder_lm.blocked_logprobs)
segment_block_sums = jax.jit(decoder_lm.segment_block_sums)
# A group's stacked leaves -> its first layer's.
first_layer = jax.jit(lambda group: jax.tree_util.tree_map(
    lambda leaf: leaf[0], group))
shared_expert = jax.jit(lambda p, n: decoder_lm._swiglu(
    p, n, ("ws_gate", "ws_up", "ws_down"), jnp.float32))


@functools.lru_cache(maxsize=None)
def experts_program(cfg: decoder_lm.DecoderLMConfig):
    """``jit(_experts_ffn)`` of a config: ``(one layer's leaves, n [B, S, d])
    -> (y, what it counted)``. The held share is a static field of the
    config, so the four shares of a layer and the uncut layer are five
    programs, and no more."""
    return jax.jit(lambda p, n: decoder_lm._experts_ffn(p, n, cfg, {}))


@functools.lru_cache(maxsize=None)
def expert_layer_program(cfg: decoder_lm.DecoderLMConfig):
    """The FFN half of one expert layer as the model runs it, residual and
    norm included: ``(one layer's leaves, u [S, d]) -> u + experts(norm(u))``
    (what the references' ``expert_layer_ffn`` states)."""
    return jax.jit(lambda p, u: u + decoder_lm._experts_ffn(
        p, decoder_lm.rms_norm(u, p["ln2"], cfg.rms_norm_eps)[None], cfg,
        {})[0][0])

