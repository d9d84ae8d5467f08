"""The ``hybrid_kda`` mixer (of every ``layer_group_size`` layers the last
latent attention, the others linear attention: a gated delta rule with a decay
a channel; the kinds hold DIFFERENT leaves, stacked by kind) over
sigmoid-routed expert layers with a clamp inside the SwiGLU, on the CPU at
tiny widths: the delta rule's three forms (chunked ``jax.numpy``, the Pallas
kernel in interpret mode, one token) against the token-by-token recurrence of
the benchmark's reference, with log-decays AT the lower bound for a whole
chunk; the family through ``map_score_lm`` in several segment programs
against the reference's one forward pass; a document in one segment against
the same in four; a reference without a mechanism; the shares of an expert
layer against the uncut layer; what the carried state holds and what the op
counts; the grouped matmul's clamp in interpret mode.

Tolerances, each with its reason:

- ``TOKEN_TOL`` 2e-5 nats a token (0.02 on a block sum of 1,024 tokens):
  ``dtype: float32`` here, so the op computes what the reference computes in
  another order (chunks and a triangular system where the reference steps a
  token at a time, segments, a cache), and float32 reordering is all that may
  differ;
- the chunked ``jax.numpy`` form in float32: 2e-5 absolute on outputs of
  order 0.1 and states of order 1 (reordering; a chunk's triangular system
  solved in blocks);
- the kernel: bf16 operands on the matmuls that meet the state and the
  values, so 3e-3 on outputs of order 0.1 and 1e-2 on a state of order 1
  (bf16's own rounding, 2^-9 relative, through a sum of 64 terms)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import grouped_ffn, kda
from agent_tpu.models import decoder_lm, moe
from agent_tpu.obs.metrics import get_registry
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("hybrid_kda_lm")

# One leading dense (linear) layer, then two periods of (linear, linear,
# latent); 16 experts in 4 groups of which 2, 8 held, 4 a token, one shared;
# limits small enough to bind on unit-variance activations.
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 4, "d_head": 16,
        "d_ff": 96, "n_layers": 7, "max_len": 16384, "mixer": "hybrid_kda",
        "dtype": "float32", "rope_theta": 6e6, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "layer_group_size": 3, "kda_conv": 4, "kda_lower_bound": -5.0,
        "n_dense_layers": 1, "n_experts": 16, "n_experts_held": 8,
        "expert_first": 4, "n_experts_per_token": 4, "n_expert_groups": 4,
        "n_groups_per_token": 2, "d_expert": 32, "n_shared_experts": 1,
        "routed_scale": 2.5, "scoring_func": "sigmoid",
        "expert_swiglu_limits": [0, 0.5, 0.5, 0.5, 0.5, 0, 0.5],
        "shared_swiglu_limits": [0, 0.7, 0, 0.7, 0.7, 0.7, 0.7]}
REF_CFG = {**TINY, "rms_norm_eps": 1e-6}
TOKEN_TOL = 2e-5
BF16 = jnp.bfloat16
LONG = 4200             # 2,048 + 2,048 + 1,024 program tokens under BUCKETS
BUCKETS = (1024, 2048)  # the op's segment sizes, halved for the CPU


# ---- (a) the delta rule's forms against the recurrence --------------------

def _inputs(S, H, d, decays):
    """Normalised queries and keys, decays between the bound and 0; with
    ``"a chunk at the bound"`` the second chunk's every decay 1e-4 off the
    bound: a sub-block's column factor is ``exp(80)`` there. With ``"keys
    repeated"`` a head has FIVE keys, taken in turn (each three times a
    sub-block and in every sub-block), ``beta`` within 0.03 of 1 and decays
    within 1e-3 of 0: the rows of the triangular system are nearly equal,
    which is what the diagonal blocks' finite product is there for. A query
    there asks for its own token's key (the output is the value just
    written, scaled), and values and state are 0.3 of the other cases': what
    a token writes is then the DIFFERENCE of two values, a chunk's thirteen
    writes under one key cancel in the state, and bf16's rounding of each
    is measured against the values, not against what is left of them."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (S, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (S, H, d)))
    v = jax.random.normal(ks[2], (S, H, d))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (S, H, d)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (S, H)))
    state = 0.3 * jax.random.normal(ks[5], (H, d, d))
    if decays == "a chunk at the bound":
        g = g.at[kda.CHUNK:2 * kda.CHUNK].set(-5.0 + 1e-4)
    elif decays == "keys repeated":
        k = k[jnp.arange(S) % 5]
        q, v, state = k * d ** -0.5, 0.3 * v, 0.3 * state
        g, beta = g * 2e-4, 1.0 - 0.03 * beta
    return q, k, v, g, beta, state


@jax.jit
def _recurrence(q, k, v, g, beta, state):
    """``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T`` as
    written, a matrix product a token; ``o_t = S_t^T q_t``."""
    def token(S, x):
        q, k, v, g, beta = x
        eye = jnp.eye(k.shape[-1])
        forget = eye - beta[:, None, None] * k[:, :, None] * k[:, None, :]
        S = jnp.einsum("hij,hjv->hiv", forget, jnp.exp(g)[:, :, None] * S) + (
            beta[:, None, None] * k[:, :, None] * v[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q, S)

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


@jax.jit
def _by_steps(q, k, v, g, beta, state):
    def token(S, x):
        o, S = kda.kda_step(*x, S)
        return S, o

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


# The kernel's steps by head count (``gcd(heads, HEADS_A_STEP)`` heads a step):
# 2 heads are one PAIR, 3 are three steps of ONE head (the unpaired step: the
# blocks of one head on 64 lanes), 8 one step of four pairs, 32 two grid steps
# of eight pairs (so a pair's lanes and a head block's state are both
# indexed).
_FORMS = [(form, decays, 2) for form in ("jax.numpy", "kernel", "one token")
          for decays in ("decays anywhere", "a chunk at the bound")] + [
    ("kernel", decays, heads) for heads in (3, 8, 32)
    for decays in ("decays anywhere", "a chunk at the bound")] + [
    ("jax.numpy", "keys repeated", 2)] + [
    ("kernel", "keys repeated", heads) for heads in (2, 3, 8, 32)]


@pytest.mark.parametrize(
    "form, decays, heads", _FORMS,
    ids=[f"{form}-{decays}" + (f"-{heads} heads" if heads != 2 else "")
         for form, decays, heads in _FORMS])
def test_the_delta_rule_matches_the_recurrence(form, decays, heads):
    S, H, d = 3 * kda.CHUNK - 20, heads, 128   # a last chunk padded
    q, k, v, g, beta, state = _inputs(S, H, d, decays)
    flat = lambda a: a.reshape(S, H * d)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        if form == "kernel":
            q, k, v = (a.astype(BF16).astype(jnp.float32) for a in (q, k, v))
        want_o, want_s = _recurrence(q, k, v, g, beta, state)
        if form == "one token":
            o, new = _by_steps(q, k, v, g, beta, state)
            o = flat(o)
        else:
            dtype = BF16 if form == "kernel" else jnp.float32
            o, new = kda.kda_chunks(
                flat(q).astype(dtype), flat(k).astype(dtype),
                flat(v).astype(dtype), flat(g), beta, n_heads=H,
                lower_bound=-5.0, initial_state=state,
                pallas=form == "kernel", interpret=True)
    assert float(jnp.abs(want_o).max()) > 0.05
    o_tol, s_tol = (3e-3, 1e-2) if form == "kernel" else (2e-5, 2e-5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(flat(want_o)), atol=o_tol)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want_s),
                               atol=s_tol)


@pytest.mark.parametrize("decays", ["decays anywhere", "keys repeated"])
def test_a_pair_of_heads_computes_what_each_head_computes_alone(decays):
    """Eight heads in one call run as four PAIRS (the diagonal blocks'
    inverse of two heads side by side on the lanes); the same heads one a
    call run the unpaired step. A head's numbers must not know its
    neighbour: equal to float32 rounding (a product's zeros are exact, so
    only the order of a sum may differ), outputs rounded to bf16 once."""
    S, H, d = 2 * kda.CHUNK, 8, 128
    q, k, v, g, beta, state = (
        a if i > 2 else a.astype(BF16)
        for i, a in enumerate(_inputs(S, H, d, decays)))
    flat = lambda a: a.reshape(S, -1)  # noqa: E731
    run = functools.partial(kda.kda_chunks, lower_bound=-5.0, pallas=True,
                            interpret=True)
    with jax.default_matmul_precision("highest"):
        o, new = run(flat(q), flat(k), flat(v), flat(g), beta, n_heads=H,
                     initial_state=state)
        for h in range(H):
            o_h, new_h = run(q[:, h], k[:, h], v[:, h], g[:, h],
                             beta[:, h:h + 1], n_heads=1,
                             initial_state=state[h:h + 1])
            np.testing.assert_allclose(
                np.asarray(o[:, h * d:(h + 1) * d], np.float32),
                np.asarray(o_h, np.float32), atol=2e-3 * 2 ** -8, rtol=2 ** -7)
            np.testing.assert_allclose(np.asarray(new[h]),
                                       np.asarray(new_h[0]), atol=2e-6)


def test_the_references_recurrence_is_the_same_rule():
    """The benchmark's ``delta_rule`` (an update of rank one a token) against
    the matrix form above: two statements of one equation."""
    q, k, v, g, beta, state = _inputs(100, 2, 16, "decays anywhere")
    with jax.default_matmul_precision("highest"):
        want, _ = _recurrence(q, k, v, g, beta, jnp.zeros_like(state))
        got = jax.jit(ref.delta_rule)(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_which_shapes_the_kernel_takes():
    assert kda.pallas_supported(128, 4096, -5.0, BF16)
    assert not kda.pallas_supported(64, 4096, -5.0, BF16)       # lanes
    assert not kda.pallas_supported(128, 4096 + 16, -5.0, BF16)  # whole chunks
    assert not kda.pallas_supported(128, 4096, -5.0, jnp.float32)
    # exp(6 x 16) is past what a sub-block's float32 holds safely.
    assert not kda.pallas_supported(128, 4096, -6.0, BF16)
    assert kda.CHUNK % kda.SUB == 0 and kda.chunks_of(4096 + 1) == 65


# ---- (b) the family in segments against the reference's one pass ----------

def _short_segments() -> pytest.MonkeyPatch:
    from agent_tpu.ops import map_score_lm

    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    return mp


@pytest.fixture(scope="module")
def served():
    """A document of three segments and a short one through
    ``map_score_lm``: ``(documents, result, counters gained)``."""
    reset_runtime()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in (LONG, 37)]
    before = get_registry().snapshot()
    mp = _short_segments()
    try:
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs], "model_config": TINY,
            "model_path": "kda-a", "allow_fallback": False})
    finally:
        mp.undo()
    after = get_registry().snapshot()
    reset_runtime()
    assert out["ok"] is True, out
    return docs, out, (before, after)


@pytest.fixture(scope="module")
def reference_logprobs(served):
    return ref.token_logprobs(REF_CFG, "kda-a", served[0])


def _gaps(result, logprobs):
    return [np.abs(np.asarray(blocks) - ref.block_sums(lp))
            for blocks, lp in zip(result["block_logprob_sums"], logprobs)]


def test_segments_with_both_kinds_of_state_match_one_forward_pass(
        served, reference_logprobs):
    docs, out, _ = served
    assert out["n_tokens"] == [LONG, 37]
    assert [len(b) for b in out["block_logprob_sums"]] == [5, 1]
    long_gap, short_gap = _gaps(out, reference_logprobs)
    assert short_gap.max() < TOKEN_TOL * 37
    assert long_gap.max() < TOKEN_TOL * 1024, long_gap


def _counter(snapshots, name, **labels):
    def value(snap):
        family = snap.get(name) or {"series": []}
        return sum(s["value"] for s in family["series"]
                   if all(s["labels"].get(k) == v for k, v in labels.items()))
    return value(snapshots[1]) - value(snapshots[0])


def test_the_op_counts_the_new_state_and_the_families_experts(served):
    _, _, snaps = served
    n = LONG + 37
    assert _counter(snaps, "kda_tokens_total", path="first_chunk") == 64 + 37
    assert _counter(snaps, "kda_tokens_total", path="state") == LONG - 64
    assert _counter(snaps, "kda_chunks_total") == (2048 + 2048 + 1024 + 1024) // 64
    assert _counter(snaps, "causal_attention_pairs_total", kind="causal") == (
        LONG * (LONG + 1) // 2 + 37 * 38 // 2)
    assert _counter(snaps, "latent_keys_expanded_total", kind="cached") == n
    assert _counter(snaps, "latent_keys_expanded_total", kind="expanded") == (
        2048 + 4096 + 5120 + 1024)
    # Six expert layers over the padded tokens; half the experts are held.
    tokens = _counter(snaps, "moe_tokens_total")
    assert tokens == 6 * (5120 + 1024)
    pairs = _counter(snaps, "moe_expert_pairs_total")
    assert 0.2 * 4 * tokens < pairs < 0.8 * 4 * tokens
    tiles = _counter(snaps, "moe_tiles_total")
    assert tiles >= pairs / grouped_ffn.ROW_TILE
    rows = _counter(snaps, "moe_rows_computed_total")
    assert rows % grouped_ffn.SUB_ROWS == 0
    assert pairs <= rows <= tiles * grouped_ffn.ROW_TILE


# ---- (c) one segment against four ------------------------------------------

def test_a_document_in_one_segment_equals_the_same_in_four():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "kda-b")
    program = lm_once.segment_program(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, TINY["vocab_size"], (1, 512)), jnp.int32)
    whole, state1 = program(params, ids, jnp.int32(0),
                            lm_once.state(cfg, 1, 512))
    state, parts = lm_once.state(cfg, 1, 512), []
    for at in range(0, 512, 128):
        hidden, state = program(params, ids[:, at:at + 128], jnp.int32(at),
                                state)
        parts.append(hidden)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, 1)),
                               np.asarray(whole), atol=2e-4)
    # Both kinds of state were carried: the linear layers' and the latents'.
    for a, b in zip(jax.tree_util.tree_leaves(state["mixer"]),
                    jax.tree_util.tree_leaves(state1["mixer"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    assert float(jnp.abs(state["mixer"]["linear"]["S"]).max()) > 0.01
    assert float(state["pairs"]) == float(state1["pairs"]) > 0


def test_the_carried_state_holds_two_unlike_kinds():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    state = lm_once.state_shapes(cfg, 1, 5120)
    assert set(state) == {"mixer", "pairs", "tiles"}
    mixer = state["mixer"]
    assert set(mixer) == {"linear", "latent"}
    assert mixer["linear"]["S"].shape == (5, 1, 4, 16, 16)
    assert mixer["linear"]["S"].dtype == jnp.float32
    assert mixer["linear"]["conv"].shape == (5, 1, 3, 3 * 64)
    assert mixer["latent"]["kv"].shape == (2, 1, 5120, 32 + 8)
    # The leaves by kind: a stack a kind a group, the FFN's beside them.
    params = lm_once.param_shapes(cfg)
    dense, experts = params["layers"], params["expert_layers"]
    assert set(dense["mixers"]) == {"linear"}
    assert set(experts["mixers"]) == {"linear", "latent"}
    assert dense["mixers"]["linear"]["wq"].shape == (1, 64, 64)
    assert experts["mixers"]["linear"]["wq"].shape == (4, 64, 64)
    assert experts["mixers"]["latent"]["wq"].shape == (2, 64, 4 * 24)
    assert experts["mixers"]["latent"]["w_ukv"].shape == (2, 32, 4 * 32)
    assert set(experts["mixers"]["linear"]) == {
        "ln1", "wq", "wk", "wv", "wo", "conv_w", "w_beta", "w_a", "w_og",
        "A_log", "dt_bias", "o_norm"}
    assert set(experts["mixers"]["latent"]) == {
        "ln1", "wq", "wo", "w_dkv", "w_ukv", "w_og", "kv_norm"}
    assert "ln1" not in experts and experts["ln2"].shape == (6, 64)
    assert experts["expert_limit"].shape == experts["shared_limit"].shape == (6,)
    assert decoder_lm.layers_of_kinds(cfg) == {
        "layers": {"linear": (0,)},
        "expert_layers": {"latent": (3, 6), "linear": (1, 2, 4, 5)}}


def test_the_decay_constants_span_the_written_memories():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    A_log, dt_bias = decoder_lm.kda_gate_constants(cfg)
    g = cfg.kda_lower_bound / (1.0 + np.exp(-np.exp(A_log) * dt_bias[::16]))
    np.testing.assert_allclose(1.0 / -np.expm1(g), 16.0 * 128.0 ** (
        np.arange(4) / 3.0), rtol=1e-4)
    sway, bias = ref.gate_constants(REF_CFG)
    np.testing.assert_allclose(sway, np.exp(A_log), rtol=1e-6)
    np.testing.assert_array_equal(np.repeat(bias, 16), dt_bias)


# ---- (d) a reference without a mechanism ----------------------------------

def _one_decay_a_head(q, k, v, g, beta):
    return ref_delta_rule(q, k, v, jnp.broadcast_to(
        g.mean(-1, keepdims=True), g.shape), beta)


def _no_delta_term(q, k, v, g, beta):
    """``S_t = Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``."""
    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S + k[:, :, None] * (
            beta[:, None] * v)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S)

    H, d = q.shape[1:]
    return jax.lax.scan(token, jnp.zeros((H, d, d)), (q, k, v, g, beta))[1]


def _state_zeroed_at_2048(q, k, v, g, beta):
    """The state dropped where the first segment program ends."""
    return jnp.concatenate([ref_delta_rule(*(a[:2048] for a in (q, k, v, g, beta))),
                            ref_delta_rule(*(a[2048:] for a in (q, k, v, g, beta)))])


ref_delta_rule = ref.delta_rule


def _gate_deaf_to_the_input(cfg, w, x):
    """The output gate at ``sigmoid(0)`` whatever the token."""
    return _linear_layer(cfg, dict(w, w_og=jnp.zeros_like(
        jnp.asarray(w["w_og"]))), x)


_linear_layer = ref.linear_layer
BROKEN = {
    "one decay a head": ("delta_rule", _one_decay_a_head),
    "no delta term": ("delta_rule", _no_delta_term),
    "state zeroed at a boundary": ("delta_rule", _state_zeroed_at_2048),
    "no output gate": ("linear_layer", _gate_deaf_to_the_input),
    "no clamp": None,
}


@pytest.mark.parametrize("what", list(BROKEN))
def test_a_reference_without_a_mechanism_misses_by_ten_tolerances(
        served, what):
    docs, out, _ = served
    mp = pytest.MonkeyPatch()
    cfg = dict(REF_CFG)
    if BROKEN[what] is None:
        cfg.update(expert_swiglu_limits=[], shared_swiglu_limits=[])
    else:
        mp.setattr(ref, *BROKEN[what])
    mp.setattr(ref._mla, "_JIT", {})     # its programs traced under the patch
    try:
        broken = ref.token_logprobs(cfg, "kda-a", docs[:1])
    finally:
        mp.undo()
    gap = _gaps({"block_logprob_sums": out["block_logprob_sums"][:1]}, broken)[0]
    # A state dropped at 2,048 is seen by the blocks behind it alone.
    seen = gap[2:] if what == "state zeroed at a boundary" else gap
    assert seen.max() > 10 * TOKEN_TOL * 1024, (what, gap)


# ---- (e) the shares of an expert layer --------------------------------------

_shared = jax.jit(lambda p, n: decoder_lm._swiglu(
    p, n, ("ws_gate", "ws_up", "ws_down"), jnp.float32,
    limit=p["shared_limit"]))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each, and one that holds all sixteen: the
    routed parts of the shares and the shared expert, which every chip
    computes alike, counted ONCE, are the uncut layer; an expert's weights
    are the same wherever it is held; the uncut layer is the reference's,
    clamp and all."""
    whole = decoder_lm.DecoderLMConfig(**{**TINY, "n_experts_held": 16,
                                          "expert_first": 0})
    n = jax.random.normal(jax.random.PRNGKey(5), (1, 300, 64), jnp.float32)
    layer = lambda cfg: lm_once.first_layer({  # noqa: E731
        k: v for k, v in lm_once.params(cfg, "kda-c")["expert_layers"].items()
        if k != "mixers"})
    p_whole = layer(whole)
    assert float(p_whole["expert_limit"]) == 0.5
    assert float(p_whole["shared_limit"]) == pytest.approx(0.7)
    y_whole, counted = lm_once.experts_program(whole)(p_whole, n)
    assert set(counted) == {"pairs", "tiles"}
    shared = _shared(p_whole, n)
    down_whole = np.asarray(p_whole["we_down"])
    total, pairs = 0.0, 0.0
    for first in (0, 4, 8, 12):
        cfg = decoder_lm.DecoderLMConfig(**{**TINY, "n_experts_held": 4,
                                            "expert_first": first})
        p = layer(cfg)
        np.testing.assert_array_equal(np.asarray(p["we_down"]),
                                      down_whole[first:first + 4])
        y, held = lm_once.experts_program(cfg)(p, n)
        total = total + y
        pairs += float(held["pairs"])
    assert pairs == float(counted["pairs"]) == 300 * 4    # every choice, once
    np.testing.assert_allclose(np.asarray(total - 3.0 * shared),
                               np.asarray(y_whole), atol=1e-5)
    u = n[0] * 3.0
    cfg16 = {**REF_CFG, "n_experts_held": 16, "expert_first": 0}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer_ffn(cfg16, "kda-c", 1, u)
        unclamped = ref.expert_layer_ffn(
            {**cfg16, "expert_swiglu_limits": [], "shared_swiglu_limits": []},
            "kda-c", 1, u)
    got = lm_once.expert_layer_program(whole)(p_whole, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(want - unclamped).max()) > 1e-2   # the clamp binds


def test_the_grouped_matmul_clamps_inside_the_swiglu():
    """The kernel in interpret mode at the cell's width of six lane tiles
    (768 = three steps of 256), under a limit that binds, against the same
    arithmetic in ``jax.numpy``; without a limit the kernel is the parent's."""
    assert grouped_ffn.width_step(768) == 256
    d, f, held, S, k = 256, 768, 4, 96, 2
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (S, d), jnp.float32).astype(BF16)
    w_gate = (jax.random.normal(ks[1], (held, d, f)) / 16).astype(BF16)
    w_up = (jax.random.normal(ks[2], (held, d, f)) / 16).astype(BF16)
    w_down = (jax.random.normal(ks[3], (held, f, d)) / 28).astype(BF16)
    experts = jax.random.randint(ks[4], (S, k), 0, 6)
    gates = jax.nn.softmax(jax.random.normal(ks[5], (S, k)), -1)
    limit = jnp.float32(0.5)
    run = functools.partial(moe.held_experts_ffn, x, experts, gates, w_gate,
                            w_up, w_down, 0, interpret=True)
    clamped, pairs = run(limit=limit, pallas=True)
    plain, _ = run(limit=limit, pallas=False)
    free, _ = run(pallas=True)
    assert int(pairs) == int(((experts >= 0) & (experts < held)).sum())
    np.testing.assert_allclose(np.asarray(clamped), np.asarray(plain),
                               atol=2e-2)
    assert float(jnp.abs(clamped - free).max()) > 0.1


# ---- (f) the tables, the config's rules, the control -----------------------

def test_the_tables_have_the_sixth_mixer():
    assert set(decoder_lm.MIXERS) == set(decoder_lm.MIXER_LEAVES) == set(
        decoder_lm.MIXER_FLOPS) >= {"hybrid_kda"}
    assert "hybrid_kda" in decoder_lm.MIXER_STATES
    assert "hybrid_kda" not in decoder_lm.MIXER_CACHES     # stepped, as mistral's
    assert set(decoder_lm.MIXER_KINDS) >= {"window_gqa", "hybrid_kda"}
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    assert decoder_lm.layer_kinds(cfg) == ("linear", "linear", "latent")
    assert decoder_lm.group_kinds(cfg, "dense") == ("linear",)
    assert decoder_lm.layer_kinds(decoder_lm.DecoderLMConfig()) == ()
    assert cfg.expert_swiglu_limits == (0.0, 0.5, 0.5, 0.5, 0.5, 0.0, 0.5)
    hash(cfg)
    # What the program does of a segment grows with position on the latent
    # layers alone: 2 of 7 layers, 4 heads, 2 x (24 + 16) a pair.
    grown = decoder_lm.segment_flops(cfg, 1024, 2048) - decoder_lm.segment_flops(
        cfg, 1024, 1024)
    expand = 2.0 * 32 * 4 * 32 * 1024
    assert grown == pytest.approx(2 * (1024 * 1024 * 2.0 * 4 * 40 + expand))


@pytest.mark.parametrize("over, message", [
    ({"n_layers": 8}, "whole periods"),
    ({"n_dense_layers": 3}, "whole periods"),
    ({"layer_group_size": 1}, "layer_group_size"),
    ({"kda_lower_bound": -6.0}, "kda_lower_bound"),
    ({"kda_lower_bound": 0.0}, "kda_lower_bound"),
    ({"expert_swiglu_limits": [4.0]}, "one limit"),
    ({"shared_swiglu_limits": [-1.0] * 7}, "one limit"),
    ({"qk_rope_head_dim": 7}, "even"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))


def test_a_model_of_kinds_runs_with_dense_ffns_alone():
    """No experts: one group of whole periods, every FFN the dense one."""
    over = {k: v for k, v in TINY.items() if not k.endswith("_limits")}
    over.update(n_layers=6, n_experts=0)
    cfg = decoder_lm.DecoderLMConfig(**over)
    decoder_lm.validate(cfg)
    params = lm_once.params(cfg, "kda-d")
    assert set(params) == {"embed", "head", "final_norm", "layers"}
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 3000, (1, 192)),
                      jnp.int32)
    hidden, state = lm_once.segment_program(cfg)(
        params, ids, jnp.int32(0), lm_once.state(cfg, 1, 192))
    assert set(state) == {"linear", "latent"} and hidden.shape == (1, 192, 64)
    want = ref.token_logprobs({**over, "rms_norm_eps": 1e-6}, "kda-d",
                              [np.asarray(ids[0])])[0]
    got = lm_once.blocked_logprobs(hidden[0, :-1], params["head"], ids[0, 1:])
    np.testing.assert_allclose(np.asarray(got), want, atol=TOKEN_TOL)


def test_the_int8_control_reaches_both_kinds_of_mixer():
    """``quantize_for_family`` finds the projection leaves in a kind's stack
    as it finds a group's; the small maps (beta, the output gate), the
    convolution and the decay's constants stay; the op serves the tree."""
    from agent_tpu.models import quant

    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "dtype": "bfloat16"})
    tree = quant.quantize_for_family("decoder_lm", lm_once.params(cfg, "kda-e"))
    experts = tree["expert_layers"]
    linear, latent = experts["mixers"]["linear"], experts["mixers"]["latent"]
    table = lambda leaf: isinstance(leaf, dict) and leaf["w_q"].dtype == jnp.int8  # noqa: E731
    assert all(table(linear[k]) for k in ("wq", "wk", "wv", "wo", "w_a"))
    assert all(table(latent[k]) for k in ("wq", "wo", "w_dkv", "w_ukv"))
    assert all(table(experts[k]) for k in ("w_router", "ws_gate", "we_up"))
    assert not any(isinstance(linear[k], dict) for k in (
        "w_beta", "w_og", "conv_w", "A_log", "dt_bias"))
    doc = np.random.default_rng(4).integers(0, 3000, 900).tolist()
    sums = {}
    for quant_mode in ("none", "int8"):
        reset_runtime()
        out = get_op("map_score_lm")({
            "ids": [doc], "model_path": "kda-e", "model_config": {
                **TINY, "dtype": "bfloat16", "quant": quant_mode}})
        assert out["ok"] is True, out
        sums[quant_mode] = out["logprob_sum"][0]
    reset_runtime()
    assert 0.0 < abs(sums["int8"] - sums["none"]) / 899 < 0.05, sums
