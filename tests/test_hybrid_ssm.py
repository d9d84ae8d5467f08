"""The ``hybrid_ssm`` mixer on the CPU at tiny widths: each kernel (Pallas,
interpret mode) against the same arithmetic in ``jax.numpy`` and the scan's
chunked form against the token-by-token recurrence; the family through
``map_score_lm`` against the benchmark's plain reference on documents that
cross chunk, segment and bucket boundaries; and MUTATIONS of the program, each
of which the configuration's own limits must catch: a state dropped between
segments, a branch left out, a multiplier forgotten, int8 weights.

Tolerance: ``dtype: float32`` here, so the op computes what the reference
computes in another order: 2e-5 nats a token (float32 reordering). Head counts
are no powers of two: 15 query over 3 key-value heads (five a head, as
published), scan heads 3 and 16 a group."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import causal_attention as ca
from agent_tpu.kernels import ssd
from agent_tpu.models import decoder_lm
from agent_tpu.ops import get_op, map_score_lm
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("hybrid_ssm_lm")
PUBLISHED = manifest.load_config(manifest.load_manifest(), "falcon-h1-34b")
LIMITS = PUBLISHED["check"]["limits"]
MULTIPLIERS = {k: v for k, v in PUBLISHED["model"].items()
               if k.endswith("_multiplier")}
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 15, "n_kv_heads": 3,
        "d_head": 16, "d_ff": 96, "n_layers": 2, "max_len": 262144,
        "mixer": "hybrid_ssm", "dtype": "float32", "rms_norm_eps": 1e-5,
        "rope_theta": 1e11, "ssm_n_heads": 6, "ssm_d_head": 16,
        "ssm_d_state": 24, "ssm_n_groups": 2, "ssm_d_conv": 4,
        "ssm_chunk": 128, **MULTIPLIERS}
TOKEN_TOL = 2e-5
BF16, F32 = jnp.bfloat16, jnp.float32


# ---- the kernels against the plain arithmetic -----------------------------

def _scan_operands(S, G, hg, P, N, dtype, seed=0):
    rng = np.random.default_rng(seed)
    H = G * hg
    return (jnp.asarray(rng.standard_normal((S, H * P)), dtype),
            jnp.asarray(np.exp(rng.standard_normal((S, H)) - 3), F32),
            -jnp.arange(1, H + 1, dtype=F32),
            jnp.asarray(rng.standard_normal((S, G * N)) * 0.3, dtype),
            jnp.asarray(rng.standard_normal((S, G * N)) * 0.3, dtype),
            jnp.asarray(rng.standard_normal((H, N, P)), F32))


def _recurrence_step(x_t, dt_t, A, B_t, C_t, state):
    """The kernel module's equations one token at a time, float32: x_t
    [H, P], dt_t [H], A [H], B_t, C_t [G, N], state [H, N, P] → (y_t [H, P],
    state)."""
    hg = x_t.shape[0] // B_t.shape[0]
    Bh = jnp.repeat(B_t, hg, axis=0)                         # [H, N]
    Ch = jnp.repeat(C_t, hg, axis=0)
    state = jnp.exp(dt_t * A)[:, None, None] * state + (
        dt_t[:, None, None] * Bh[:, :, None] * x_t[:, None, :])
    return jnp.einsum("hn,hnp->hp", Ch, state), state


def _token_by_token(x, dt, A, B, C, state, H, G):
    """The recurrence of the module's docstring, one token after another."""
    P, N = x.shape[1] // H, B.shape[1] // G

    def step(st, xs):
        x_t, dt_t, B_t, C_t = xs
        y_t, st = _recurrence_step(x_t.reshape(H, P), dt_t, A,
                                   B_t.reshape(G, N), C_t.reshape(G, N), st)
        return st, y_t.reshape(H * P)

    state, y = jax.lax.scan(step, state, (
        x.astype(F32), dt, B.astype(F32), C.astype(F32)))
    return y, state


# Model code runs inside programs built ONCE (``tests/README.md``): the
# kernels' plain forms and the recurrence above are ``jax.numpy``, eagerly a
# compile a primitive (the Pallas forms sit under a ``jax.jit`` of the
# package's own).
ssd_scan = jax.jit(ssd.ssd_scan, static_argnames=(
    "n_heads", "n_groups", "chunk", "pallas", "interpret"))
token_by_token = jax.jit(_token_by_token, static_argnums=(6, 7))
causal_attention = jax.jit(ca.causal_attention,
                           static_argnames=("pallas", "interpret"))


@pytest.mark.parametrize("carried", [False, True], ids=["first", "carried"])
def test_scan_kernel_equals_the_recurrence(carried):
    """Three heads a group at lane-wide heads, a state of two lane groups,
    three chunks: the kernel (bf16 operands) within bf16's rounding of the
    float32 recurrence, state in and out."""
    G, hg, P, N, S = 2, 3, 128, 256, 384
    x, dt, A, B, C, st = _scan_operands(S, G, hg, P, N, BF16)
    st = st if carried else None
    assert ssd.pallas_supported(P, N, 128, BF16)
    y, out = ssd_scan(x, dt, A, B, C, n_heads=G * hg, n_groups=G, chunk=128,
                      initial_state=st, pallas=True, interpret=True)
    want, want_state = token_by_token(
        x, dt, A, B, C, ssd.zero_state(G * hg, P, N) if st is None else st,
        G * hg, G)
    assert y.dtype == BF16 and out.dtype == F32
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(y.astype(F32) - want).max()) < 0.01 * scale
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_state),
                               atol=0.01)


@pytest.mark.parametrize("G, hg, S, chunk", [
    (3, 16, 300, 128),      # sixteen heads a group, three groups; padded
    (2, 3, 512, 128), (1, 5, 130, 64)])
def test_chunked_form_equals_the_recurrence(G, hg, S, chunk):
    """The ``jax.numpy`` chunked form in float32, with a carried state and a
    last chunk that is padded (a step of no time)."""
    P, N = 8, 24
    x, dt, A, B, C, st = _scan_operands(S, G, hg, P, N, F32, seed=1)
    y, out = ssd_scan(x, dt, A, B, C, n_heads=G * hg, n_groups=G, chunk=chunk,
                      initial_state=st, pallas=False)
    want, want_state = token_by_token(x, dt, A, B, C, st, G * hg, G)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_state),
                               atol=2e-5)
    # Given in two calls, the state handed over: the same numbers.
    cut = 2 * chunk if S > 2 * chunk else chunk
    on_host = [np.asarray(t) for t in (x, dt, B, C)]
    parts = lambda a, b: [t[a:b] for t in on_host]  # noqa: E731
    x1, d1, B1, C1 = parts(0, cut)
    y1, mid = ssd_scan(x1, d1, A, B1, C1, n_heads=G * hg, n_groups=G,
                       chunk=chunk, initial_state=st, pallas=False)
    x2, d2, B2, C2 = parts(cut, S)
    y2, end = ssd_scan(x2, d2, A, B2, C2, n_heads=G * hg, n_groups=G,
                       chunk=chunk, initial_state=mid, pallas=False)
    np.testing.assert_allclose(np.concatenate([y1, y2]), np.asarray(y),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(end), np.asarray(out), atol=2e-5)


def test_convolution_carries_its_tail():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((40, 12)), F32)
    w = jnp.asarray(rng.standard_normal((4, 12)), F32)
    b = jnp.asarray(rng.standard_normal((12,)), F32)
    whole, tail = ssd.causal_conv(u, None, w, b)
    want = np.asarray(b)[None] + sum(
        np.asarray(w)[i][None] * np.pad(np.asarray(u), ((3 - i, 0), (0, 0)))[:40]
        for i in range(4))
    np.testing.assert_allclose(np.asarray(whole), want, atol=1e-6)
    first, mid = ssd.causal_conv(u[:17], None, w, b)
    second, end = ssd.causal_conv(u[17:], mid, w, b)
    np.testing.assert_allclose(np.concatenate([first, second]), want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(u[-3:]))
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(end))


@pytest.mark.parametrize("pos0", [0, 512, 1024])
def test_attention_kernel_never_reads_past_the_segment(pos0):
    """Five query heads a key-value head, three key-value heads; NaN keys and
    values at and after ``pos0 + S``: tiles above the diagonal are neither
    fetched nor computed, and both paths give finite, equal answers."""
    Hkv, G, S, D, Lk = 3, 5, 512, 128, 2048
    rng = np.random.default_rng(pos0)
    q = jnp.asarray(rng.standard_normal((Hkv, G, S, D)) * 0.3, BF16)
    k = jnp.asarray(rng.standard_normal((Hkv, Lk, D)), BF16)
    v = jnp.asarray(rng.standard_normal((Hkv, Lk, D)), BF16)
    k = k.at[:, pos0 + S:].set(jnp.nan)
    v = v.at[:, pos0 + S:].set(jnp.nan)
    assert ca.pallas_supported(S, Lk, D, BF16)
    got = causal_attention(q, k, v, jnp.int32(pos0), pallas=True,
                           interpret=True).astype(F32)
    plain = causal_attention(q, k, v, jnp.int32(pos0),
                             pallas=False).astype(F32)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(plain).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=0.03)
    # And the plain path is the softmax of the docstring, a head at a time.
    t = pos0 + np.arange(S)
    kf, vf = (np.nan_to_num(np.asarray(a.astype(F32))) for a in (k, v))
    s = np.einsum("td,sd->ts", np.asarray(q[1, 2].astype(F32)), kf[1])
    s = np.where(np.arange(Lk)[None] <= t[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ vf[1]
    np.testing.assert_allclose(np.asarray(plain[1, 2]), want, atol=0.03)


def test_attention_kernel_reads_a_layer_of_the_stack_in_place():
    """The keys and values as the layers' STACK ``[layers, 1, Hkv, Lk, D]``
    and a layer's number: the kernel (its tiles addressed through a second
    prefetched scalar) answers to the byte what it answers on that layer's
    slice, for either layer, and agrees with the plain path, which takes the
    slice."""
    layers, Hkv, G, S, D, Lk, pos0 = 2, 2, 5, 512, 128, 1024, 512
    rng = np.random.default_rng(46)
    q = jnp.asarray(rng.standard_normal((Hkv, G, S, D)) * 0.3, BF16)
    k = jnp.asarray(rng.standard_normal((layers, 1, Hkv, Lk, D)), BF16)
    v = jnp.asarray(rng.standard_normal((layers, 1, Hkv, Lk, D)), BF16)
    at = jnp.int32(pos0)
    answers = []
    for layer in range(layers):
        got = ca.causal_attention(q, k, v, at, jnp.int32(layer), pallas=True,
                                  interpret=True)
        sliced = ca.causal_attention(q, k[layer, 0], v[layer, 0], at,
                                     pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                      np.asarray(sliced.astype(F32)))
        plain = ca.causal_attention(q, k, v, at, jnp.int32(layer),
                                    pallas=False)
        np.testing.assert_array_equal(
            np.asarray(plain.astype(F32)), np.asarray(ca._attention_jnp(
                q, k[layer, 0], v[layer, 0], at).astype(BF16).astype(F32)))
        np.testing.assert_allclose(np.asarray(got.astype(F32)),
                                   np.asarray(plain.astype(F32)), atol=0.03)
        answers.append(np.asarray(got.astype(F32)))
    assert np.abs(answers[0] - answers[1]).max() > 0.1   # two layers' keys


def test_shapes_off_the_kernels_take_the_plain_path():
    assert not ssd.pallas_supported(16, 24, 128, F32)
    assert not ssd.pallas_supported(128, 256, 128, F32)
    assert not ssd.pallas_supported(128, 256, 64, BF16)
    assert not ca.pallas_supported(1000, 4096, 128, BF16)
    assert not ca.pallas_supported(1024, 4096, 16, BF16)
    assert not ca.pallas_supported(1024, 4096, 128, F32)
    assert ca.visited_pairs(512, 512) == 512 * 1024


# ---- the family through the op against the reference ----------------------

# The op's segment sizes, halved for the CPU (a quarter of the causal pairs;
# the 4,096-token segment compiles for the chip in ``tests/test_tpu_compile.py``
# and runs in the benchmark's rehearsal of ``brumby-14b-base``).
BUCKETS = (1024, 2048)
LENGTHS = (1, 127, 129, 2048, 2049, 4500)


@pytest.fixture(scope="module")
def served():
    """Documents that end inside a chunk, a chunk past it, at a segment's
    end, a token into the next, and in the third segment's small bucket,
    through ``map_score_lm``."""
    reset_runtime()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in LENGTHS]
    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    try:
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs], "model_config": TINY,
            "model_path": "hybrid-a"})
    finally:
        mp.undo()
    reset_runtime()
    assert out["ok"] is True, out
    return docs, out


def test_documents_across_every_boundary_match_the_reference(served):
    docs, out = served
    assert out["n_tokens"] == list(LENGTHS)
    assert [len(b) for b in out["block_logprob_sums"]] == [0, 1, 1, 2, 2, 5]
    assert [map_score_lm.segment_plan(n) for n in (4096, 4097, 9000)] == [
        [(0, 4096)], [(0, 4096), (4096, 1024)],
        [(0, 4096), (4096, 4096), (8192, 1024)]]
    want = ref.token_logprobs(TINY, "hybrid-a", docs)
    for doc, blocks, lp in zip(docs, out["block_logprob_sums"], want):
        sums = ref.block_sums(lp)
        counts = ref.block_counts(len(doc))
        assert len(sums) == len(blocks)
        if len(sums):
            assert np.abs((np.asarray(blocks) - sums) / counts).max() < TOKEN_TOL
    values = ref.compare(out["block_logprob_sums"][3:],
                         [ref.block_sums(lp).tolist() for lp in want[3:]],
                         list(LENGTHS[3:]))
    assert set(LIMITS) <= set(values)
    assert all(values[k] <= 0.1 * LIMITS[k] for k in LIMITS), values
    # The model says something: a token is not scored at -log V.
    mean = np.concatenate(want[3:]).mean()
    assert abs(mean + np.log(TINY["vocab_size"])) > 0.2


# ---- mutations: each must fail the configuration's own limits -------------

DOC = 3000          # three segments of 1,024 under the halved buckets


@pytest.fixture(scope="module")
def model():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, TINY["vocab_size"], DOC).astype(np.int32)
            for _ in range(2)]
    want = [ref.block_sums(lp).tolist()
            for lp in ref.token_logprobs(TINY, "hybrid-m", docs)]
    return cfg, docs, want


@pytest.fixture(scope="module")
def params(model):
    """The sound model's weights, made once (the int8 case makes its own:
    quantizing consumes them)."""
    return lm_once.params(model[0], "hybrid-m")


def _block_sums(cfg, params, doc, mutate=None):
    """One document through the family's own functions, segment by segment
    as the op runs it (segments of 1,024), ``mutate(state, segment)`` laid
    on the state a segment takes."""
    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", (1024,))
    try:
        segments = map_score_lm._stage_document(doc)["segments"]
    finally:
        mp.undo()
    state = lm_once.state(cfg, 1, sum(s[0].shape[1] for s in segments))
    step = lm_once.segment_program(cfg)
    sums = []
    for n, (ids, targets, n_valid, pos0) in enumerate(segments):
        if mutate is not None and n:
            state = mutate(dict(state))
        hidden, state = step(params, ids, jnp.int32(pos0), state)
        sums.append(np.asarray(lm_once.segment_block_sums(
            hidden, params["head"], jnp.asarray(targets), jnp.int32(n_valid))))
    return np.concatenate(sums)[: -(-(len(doc) - 1) // 1024)].tolist()


def _verdict(cfg, params, docs, want, mutate=None):
    served = [_block_sums(cfg, params, d, mutate) for d in docs]
    values = ref.compare(served, want, [len(d) for d in docs])
    return {k: values[k] <= LIMITS[k] for k in LIMITS}, values


def test_the_sound_program_passes_every_limit(model, params):
    cfg, docs, want = model
    ok, values = _verdict(cfg, params, docs, want)
    assert all(ok.values()), values
    assert values["block_logprob_gap_max"] < TOKEN_TOL


def _zeroed(*keys):
    return lambda state: {**state, **{
        k: jnp.zeros_like(state[k]) for k in keys}}


@pytest.mark.parametrize("name, mutate", [
    ("the scan's state dropped between segments", _zeroed("ssm")),
    ("the convolution's tail dropped", _zeroed("conv")),
    ("the key and value cache of the segments before zeroed", _zeroed("k", "v")),
])
def test_a_state_lost_between_segments_fails_the_limits(model, params, name,
                                                        mutate):
    cfg, docs, want = model
    ok, values = _verdict(cfg, params, docs, want, mutate)
    assert not all(ok.values()), (name, values)


@pytest.mark.parametrize("parts", [("ssm",), ("attention",)],
                         ids=["attention_left_out", "scan_left_out"])
def test_a_branch_left_out_fails_the_limits(model, params, parts):
    """The program against the reference WITHOUT one branch: the check sees
    each."""
    cfg, docs, want = model
    served = [_block_sums(cfg, params, d) for d in docs]
    without = [ref.block_sums(lp).tolist() for lp in ref.token_logprobs(
        TINY, "hybrid-m", docs, parts=parts)]
    values = ref.compare(served, without, [len(d) for d in docs])
    assert not all(values[k] <= LIMITS[k] for k in LIMITS), values


@pytest.mark.parametrize("multiplier", sorted(
    k for k, v in MULTIPLIERS.items() if v != 1.0))
def test_a_multiplier_set_to_one_fails_the_limits(model, params, multiplier):
    """The weights of the configuration, a forward pass that forgets one
    multiplier."""
    cfg, docs, want = model
    ok, values = _verdict(dataclasses.replace(cfg, **{multiplier: 1.0}),
                          params, docs[:1], want[:1])
    assert not all(ok.values()), (multiplier, values)


def test_int8_weights_fail_the_limits(model):
    """The control's tables: the four attention projections, the scan's in-
    and out-projection, the FFN; the convolution and the norms stay."""
    from agent_tpu.models.quant import quantize_for_family

    cfg, docs, want = model
    q = quantize_for_family("decoder_lm", lm_once.params(cfg, "hybrid-m"),
                            "int8")
    layers = q["layers"]
    assert set(decoder_lm.LINEAR_LEAVES) & set(layers) == {
        "wq", "wk", "wv", "wo", "w_ssm_in", "w_ssm_out", "w_gate", "w_up",
        "w_down"}
    assert layers["w_ssm_in"]["w_q"].shape == (2, 64, 2 * 96 + 2 * 48 + 6)
    assert layers["w_ssm_in"]["w_q"].dtype == jnp.int8
    assert layers["w_ssm_in"]["w_scale"].shape == (2, 294)
    assert layers["conv_w"].dtype == q["embed"].dtype == F32
    ok, values = _verdict(cfg, q, docs, want)
    assert not all(ok.values()), values


# ---- the weight rule ------------------------------------------------------

def test_every_branch_enters_the_residual_at_the_residuals_size():
    """Under the configuration's rule (the inverse of every multiplier on
    the leaf it scales, 4 on the queries) attention, scan and feed-forward
    each enter within a factor of 4 of the residual's RMS, and the logits
    spread over about one unit; under the family's plain rule with the
    published multipliers they would enter at a hundredth and below."""
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "hybrid-m")
    ids = np.random.default_rng(3).integers(0, 3000, 1024).astype(np.int32)
    rms = lambda a: float(np.sqrt(np.mean(np.square(  # noqa: E731
        np.asarray(a, np.float32)))))
    caches = decoder_lm.MIXER_CACHES["hybrid_ssm"]

    @jax.jit
    def branches(params, ids, state):
        """The residual going in, the mixer's branches alone and together,
        the feed-forward: ONE program (a multiplier is a static field of the
        config, so the mixer is traced three times inside it)."""
        layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = decoder_lm._times(params["embed"][ids], cfg.embedding_multiplier,
                              F32)
        h = decoder_lm.rms_norm(x, layer["ln1"], cfg.rms_norm_eps)[None]
        stepped, carried = decoder_lm._caches_apart(state, caches)
        mine = decoder_lm._caches_joined(
            jax.tree_util.tree_map(lambda a: a[0], stepped), carried, caches,
            jnp.int32(0))
        branch = lambda **over: decoder_lm._hybrid_ssm_mixer(  # noqa: E731
            layer, h, jnp.arange(1024), mine,
            dataclasses.replace(cfg, **over), {})[0][0]
        attended = branch(ssm_out_multiplier=0.0)
        scanned = branch(attention_out_multiplier=0.0)
        both = branch()
        n = decoder_lm.rms_norm(x + both, layer["ln2"], cfg.rms_norm_eps)
        ffn = decoder_lm._swiglu(
            layer, n, ("w_gate", "w_up", "w_down"), F32,
            cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier)
        return x, attended, scanned, both, ffn

    x, attended, scanned, both, ffn = branches(
        params, ids, lm_once.state(cfg, 1, 1024))
    np.testing.assert_allclose(np.asarray(attended + scanned),
                               np.asarray(both), atol=1e-5)
    residual = rms(x)
    assert 0.5 < residual < 2.0
    for name, value in (("attention", attended), ("scan", scanned),
                        ("ffn", ffn)):
        assert residual / 4 < rms(value) < residual * 4, (name, rms(value))
    hidden, _ = lm_once.segment_program(cfg)(
        params, ids[None], jnp.int32(0), lm_once.state(cfg, 1, 1024))
    logits = np.asarray(hidden[0], np.float32) @ np.asarray(
        params["head"], np.float32).T
    assert 0.25 < float(logits.std()) < 4.0


def test_the_scan_constants_are_the_written_rule():
    bias = decoder_lm.ssm_dt_bias(32)
    dt = np.log1p(np.exp(bias.astype(np.float64)))
    np.testing.assert_allclose(dt[[0, -1]], [0.001, 0.1], rtol=1e-4)
    np.testing.assert_allclose(np.diff(np.log(dt)), np.log(100) / 31, rtol=1e-3)
    constants = ref.scan_constants(32)
    np.testing.assert_array_equal(constants["dt_bias"], bias)
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    layers = lm_once.params(cfg, "hybrid-m")["layers"]
    np.testing.assert_allclose(np.exp(np.asarray(layers["A_log"][1])),
                               np.arange(1, 7), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(layers["D"]), np.ones((2, 6)))
    np.testing.assert_array_equal(np.asarray(layers["dt_bias"][0]),
                                  ref.scan_constants(6)["dt_bias"])


def test_the_references_weights_are_the_programs():
    """Two statements of one rule: every drawn leaf, bit for bit."""
    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "dtype": "bfloat16"})
    rcfg = {**TINY, "dtype": "bfloat16"}
    # About the draw itself: a fresh one, not ``lm_once``'s.
    params = decoder_lm.init_params(cfg, "hybrid-w")
    for name in ("embed", "head"):
        np.testing.assert_array_equal(
            np.asarray(params[name].astype(F32)),
            np.asarray(ref.draw(rcfg, "hybrid-w", name).astype(F32)))
    for name in ref.LAYER:
        for i in range(cfg.n_layers):
            np.testing.assert_array_equal(
                np.asarray(params["layers"][name][i].astype(F32)),
                np.asarray(ref.draw(rcfg, "hybrid-w", name, i).astype(F32)),
                err_msg=name)
    assert ref.LEAVES == decoder_lm.LEAVES


# ---- the family's tables --------------------------------------------------

def test_the_new_entries_are_appended():
    assert decoder_lm.LEAVES[-4:] == ("w_ssm_in", "w_ssm_out", "conv_w",
                                      "conv_b")
    assert decoder_lm.LEAVES[:24] == ref.LEAVES[:24]
    # A later mixer (PR 40's ``dense_mla``) joins every table; none leaves.
    assert set(decoder_lm.MIXERS) == set(decoder_lm.MIXER_LEAVES) == set(
        decoder_lm.MIXER_FLOPS) >= {"power_retention", "sparse_mla",
                                    "hybrid_ssm"}
    assert set(decoder_lm.MIXER_STATES) == set(decoder_lm.MIXERS) - {
        "power_retention"}
    assert set(map_score_lm._MIXER_COUNTERS) == set(decoder_lm.MIXERS)
    assert decoder_lm.LINEAR_LEAVES[-2:] == ("w_ssm_in", "w_ssm_out")
    assert not decoder_lm.starts_from_nothing(decoder_lm.DecoderLMConfig(**TINY))


def test_two_kinds_of_state_side_by_side():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    state = lm_once.state_shapes(cfg, 1, 2048)
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "k": ((2, 1, 3, 2048, 16), F32), "v": ((2, 1, 3, 2048, 16), F32),
        "ssm": ((2, 1, 6, 24, 16), F32), "conv": ((2, 1, 3, 96 + 96), F32)}
    published = decoder_lm.DecoderLMConfig(**PUBLISHED["model"])
    shapes = lm_once.state_shapes(published, 1, 65536)
    nbytes = {k: int(np.prod(v.shape)) * v.dtype.itemsize
              for k, v in shapes.items()}
    assert nbytes["k"] + nbytes["v"] == 6 * 65536 * 2048 == 805_306_368
    assert nbytes["ssm"] == 6 * 4_194_304 and nbytes["conv"] == 6 * 3 * 5120 * 4


def test_the_op_counts_scan_tokens_and_attention_pairs():
    from agent_tpu.obs.metrics import get_registry

    def series(name):
        family = get_registry().snapshot().get(name) or {"series": []}
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in family["series"]}

    before = {n: series(n) for n in (
        "ssm_tokens_total", "causal_attention_pairs_total")}
    docs = [{"n_tokens": n, "segments": [
        (np.zeros((1, b), np.int32), None, 0, at)
        for at, b in map_score_lm.segment_plan(n)]} for n in (100, 5000)]
    map_score_lm._record_hybrid({"cfg": decoder_lm.DecoderLMConfig(**TINY),
                                 "docs": docs})
    gained = lambda name, **labels: series(name).get(  # noqa: E731
        tuple(sorted(labels.items())), 0.0) - before[name].get(
        tuple(sorted(labels.items())), 0.0)
    assert gained("ssm_tokens_total", path="first_chunk") == 100 + 128
    assert gained("ssm_tokens_total", path="state") == 5000 - 128
    assert gained("causal_attention_pairs_total", kind="causal") == (
        100 * 101 // 2 + 5000 * 5001 // 2)
    assert gained("causal_attention_pairs_total", kind="computed") == (
        ca.visited_pairs(1024, 0) + ca.visited_pairs(4096, 0)
        + ca.visited_pairs(1024, 4096))


def test_segment_flops_of_the_mixer():
    cfg = decoder_lm.DecoderLMConfig(**PUBLISHED["model"])
    d, t = 5120.0, 4096.0
    proj = 2 * d * (2 * 2560 + 2 * 512) + 2 * d * 9248 + 2 * 4096 * d
    mixer = 4 * 20 * 128 * (8192 + t / 2) + 32 * (
        2 * 128 * 128 + 4 * 256 * 128) + 2 * 2 * 128 * 256
    want = t * (6 * (proj + mixer + 6 * d * 21504) + 2 * d * 261120)
    assert decoder_lm.segment_flops(cfg, 4096, 8192) == pytest.approx(want)


@pytest.mark.parametrize("over, message", [
    ({"n_heads": 16}, "multiple of n_kv_heads"),
    ({"d_head": 15}, "d_head must be even"),
    ({"ssm_n_heads": 7}, "whole ssm_n_groups"),
    ({"ssm_d_state": 0}, "ssm_d_state"),
    ({"key_multiplier": 0.0}, "key_multiplier"),
    ({"mixer": "mamba"}, "mixer must be one of"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))
