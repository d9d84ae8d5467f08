"""``kernels/power_retention.py`` against the benchmark's plain reference
(``benchmarks/reference/retention_lm.py``, the ATTENTION form: one reference,
the file the chip check uses), on the CPU: the Pallas kernel in interpret
mode at the lane width, the ``jax.numpy`` chunked form at small head sizes.

Tolerances, and why. Inputs are float32-exact bf16 values. The jnp chunked
form is float32 throughout: it differs from the attention form only by the
order of float32 sums (1e-4 of the output's scale). The kernel rounds its MXU
operands to bf16 (weights p, the expansion, the state copy): about 2^-9
relative an operand, averaged over many terms, and the output itself is bf16: 2e-2 of scale + |value| at
worst, typically 3e-3 rms. A STATE KEPT IN bf16 loses every update below
2^-8 of what it already holds: at gates near 1 that is off by more than
either tolerance, and ``test_bf16_state_fails`` holds the tolerances to it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agent_tpu.kernels import power_retention as pr
from benchmarks.harness import manifest

ref = manifest.load_reference("retention_lm")


def _inputs(seed, L, hq, hkv, d, gate=(2.0, 6.0), B=1):
    """bf16-exact q, k (unit rms, as after the per-head norm), v and a log
    gate whose pre-activation is uniform in ``gate``."""
    rng = np.random.default_rng(seed)
    as_bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    q = as_bf16(rng.standard_normal((B, L, hq * d)))
    k = as_bf16(rng.standard_normal((B, L, hkv * d)))
    v = as_bf16(rng.standard_normal((B, L, hkv * d)))
    log_g = jax.nn.log_sigmoid(jnp.asarray(
        rng.uniform(gate[0], gate[1], (B, L, hkv)), jnp.float32))
    return q, k, v, log_g


@functools.lru_cache(maxsize=None)
def _retention(**static):
    """``pr.power_retention`` under the given static options, jitted ONCE
    (``tests/README.md``): ``(q, k, v, log_g[, initial_state=])``; traced a
    shape, and for a first and a later segment."""
    return jax.jit(functools.partial(pr.power_retention, **static))


@functools.lru_cache(maxsize=None)
def _reference_program(hq, hkv, d):
    def attention_form(q, k, v, log_g):
        L = q.shape[1]
        return jnp.stack([ref.retention_attention(
            q[b].astype(jnp.float32).reshape(L, hq, d),
            k[b].astype(jnp.float32).reshape(L, hkv, d),
            v[b].astype(jnp.float32).reshape(L, hkv, d),
            log_g[b]).reshape(L, hq * d) for b in range(q.shape[0])])
    return jax.jit(attention_form)


def _reference(q, k, v, log_g, hq, hkv, d):
    """The reference's attention form as ONE program a shape: the same
    float32 arithmetic at the highest matmul precision, which is what both
    tolerances below were taken against (the CPU has no lower one)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference_program(hq, hkv, d)(q, k, v, log_g))


def _err(y, want):
    """(largest gap, rms gap) as shares of the output's scale; the largest
    is taken against scale + |value|, since a bf16 output is off by 2^-9 of
    its own size and the first tokens' values are many times the rms."""
    scale = float(np.sqrt(np.mean(want ** 2)))
    gap = np.asarray(y, np.float32) - want
    return (float((np.abs(gap) / (scale + np.abs(want))).max()),
            float(np.sqrt(np.mean(gap ** 2))) / scale)


JNP_TOL = (1e-4, 2e-5)        # max, rms; float32 reordering only
KERNEL_TOL = (2e-2, 4e-3)     # bf16 MXU operands and a bf16 output


@pytest.mark.parametrize("L,chunk", [(64, 16), (96, 32), (100, 32), (33, 8),
                                     (64, 64), (40, None)])
def test_jnp_chunked_form_matches_attention_form(L, chunk):
    hq, hkv, d = 10, 2, 16          # 5 query heads a key-value head
    q, k, v, g = _inputs(L, L, hq, hkv, d)
    y, _ = _retention(n_kv_heads=hkv, chunk=chunk, pallas=False)(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        g)
    mx, rms = _err(y, _reference(q, k, v, g, hq, hkv, d))
    assert mx < JNP_TOL[0] and rms < JNP_TOL[1], (mx, rms)


@pytest.mark.parametrize("gate", [(-6.0, -3.0), (-1.0, 1.0), (6.0, 12.0)],
                         ids=["gates_near_0", "gates_mid", "gates_near_1"])
def test_jnp_form_over_gate_ranges(gate):
    hq, hkv, d = 5, 1, 16
    q, k, v, g = _inputs(7, 96, hq, hkv, d, gate=gate)
    y, _ = _retention(n_kv_heads=hkv, chunk=32, pallas=False)(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        g)
    mx, rms = _err(y, _reference(q, k, v, g, hq, hkv, d))
    assert mx < JNP_TOL[0] and rms < JNP_TOL[1], (mx, rms)


@pytest.mark.parametrize("L,chunk,hq,hkv", [
    (256, 128, 5, 1),       # two chunks, 5:1 grouping
    (384, 128, 4, 2),       # three chunks, two key-value heads
    (200, 128, 5, 1),       # not a multiple of the chunk: padded inside
    (128, 128, 2, 1),       # one chunk: the quadratic form alone
])
def test_kernel_interpret_matches_attention_form(L, chunk, hq, hkv):
    d = 128
    q, k, v, g = _inputs(L + hq, L, hq, hkv, d)
    y, _ = _retention(n_kv_heads=hkv, chunk=chunk, pallas=True,
                      interpret=True)(q, k, v, g)
    mx, rms = _err(y, _reference(q, k, v, g, hq, hkv, d))
    assert mx < KERNEL_TOL[0] and rms < KERNEL_TOL[1], (mx, rms)


@pytest.mark.parametrize("gate", [(-6.0, -3.0), (6.0, 12.0)],
                         ids=["gates_near_0", "gates_near_1"])
def test_kernel_interpret_over_gate_ranges(gate):
    hq, hkv, d = 5, 1, 128
    q, k, v, g = _inputs(3, 256, hq, hkv, d, gate=gate)
    y, _ = _retention(n_kv_heads=hkv, chunk=128, pallas=True,
                      interpret=True)(q, k, v, g)
    mx, rms = _err(y, _reference(q, k, v, g, hq, hkv, d))
    assert mx < KERNEL_TOL[0] and rms < KERNEL_TOL[1], (mx, rms)


@pytest.mark.parametrize("pallas,d,cuts", [
    (False, 16, (40, 72)), (False, 16, (32, 64)), (True, 128, (128,)),
])
def test_segments_with_state_handed_on_equal_whole_document(pallas, d, cuts):
    """A document as one call against the same document as segments, the
    final state of one the initial state of the next."""
    hq, hkv = 5, 1
    L = 256 if pallas else 96
    chunk = 128 if pallas else 8
    q, k, v, g = _inputs(11, L, hq, hkv, d)
    opts = dict(n_kv_heads=hkv, chunk=chunk, pallas=pallas,
                interpret=True if pallas else None)
    if not pallas:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    retention = _retention(**opts)
    whole, final = retention(q, k, v, g)
    q, k, v, g = (np.asarray(x) for x in (q, k, v, g))   # cut on the host
    parts, state, at = [], None, 0
    for cut in (*cuts, L):
        y, state = retention(
            q[:, at:cut], k[:, at:cut], v[:, at:cut], g[:, at:cut],
            initial_state=state)
        parts.append(y)
        at = cut
    got = jnp.concatenate(parts, axis=1)
    tol = KERNEL_TOL if pallas else JNP_TOL
    mx, rms = _err(got, np.asarray(whole, np.float32))
    assert mx < tol[0] and rms < tol[1], (mx, rms)
    for a, b in zip(state, final):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2 if pallas else 1e-4,
                                   atol=1e-2 if pallas else 1e-4)
    mx, rms = _err(got, _reference(q, k, v, g, hq, hkv, d))
    assert mx < tol[0] and rms < tol[1], (mx, rms)


def test_recurrent_one_token_form_matches_attention_form():
    """The recurrence S_t = g_t S_{t-1} + phi(k_t) v_t^T, one token at a
    time: a second statement of the equations."""
    hq, hkv, d, L = 10, 2, 16, 48
    q, k, v, g = _inputs(5, L, hq, hkv, d)
    state = pr.zero_state(1, hkv, d)
    step = jax.jit(pr.retention_step)      # one program, 48 calls
    qs, ks, vs, gs = (np.asarray(x.astype(jnp.float32)) for x in (q, k, v, g))
    ys = []
    for t in range(L):
        y, state = step(
            qs[:, t].reshape(1, hkv, hq // hkv, d), ks[:, t].reshape(1, hkv, d),
            vs[:, t].reshape(1, hkv, d), gs[:, t], state)
        ys.append(np.asarray(y).reshape(1, hq * d))
    mx, rms = _err(np.stack(ys, axis=1), _reference(q, k, v, g, hq, hkv, d))
    assert mx < JNP_TOL[0] and rms < JNP_TOL[1], (mx, rms)


def test_expansion_is_the_squared_dot_product():
    rng = np.random.default_rng(0)
    phi = jax.jit(pr._phi, static_argnums=1)
    for d in (8, 16, 128):
        a, b = rng.standard_normal((2, d)).astype(np.float32)
        got = float((np.asarray(phi(a, False)) * np.asarray(phi(b, True))).sum())
        assert got == pytest.approx(float(a @ b) ** 2, rel=1e-4)
        assert phi(a, False).shape == (pr.state_rows(d), d)


def test_bf16_state_fails(monkeypatch):
    """The tolerances are tight enough that a state kept in bf16 between
    chunks fails them (gates near 1, many chunks)."""
    hq, hkv, d, L = 5, 1, 16, 1024
    q, k, v, g = _inputs(9, L, hq, hkv, d, gate=(8.0, 10.0))
    step = pr._chunk_step

    def rounding_step(carry, xs, **kw):
        (S, Z), y = step(carry, xs, **kw)
        lossy = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return (lossy(S), lossy(Z)), y

    want = _reference(q, k, v, g, hq, hkv, d)
    f32 = jnp.float32
    args = (q.astype(f32), k.astype(f32), v.astype(f32), g)
    # The ``jax.numpy`` form walks its chunks in a Python loop, 256 of them
    # here: ONE program of them all is minutes of compile. The chunk's step
    # is the program (two a run: the first chunk reads no state), called 256
    # times; the walk around it stays eager.
    as_program = lambda fn: jax.jit(  # noqa: E731
        fn, static_argnames=("read_state", "eps"))
    monkeypatch.setattr(pr, "_chunk_step", as_program(step))
    sound, _ = pr.power_retention(*args, n_kv_heads=hkv, chunk=4, pallas=False)
    monkeypatch.setattr(pr, "_chunk_step", as_program(rounding_step))
    lossy, _ = pr.power_retention(*args, n_kv_heads=hkv, chunk=4, pallas=False)
    mx, rms = _err(sound, want)
    assert mx < JNP_TOL[0] and rms < JNP_TOL[1]
    mx, rms = _err(lossy, want)
    assert mx > KERNEL_TOL[0] or rms > KERNEL_TOL[1], (mx, rms)


@pytest.mark.parametrize("seq_len,carried,chunk,want", [
    (1024, False, None, False), (1025, False, None, True),
    (4096, False, None, True), (128, True, None, True),
    (16384, False, None, True), (64, False, 32, True), (32, False, 32, False),
])
def test_state_path_is_chosen_from_shapes(seq_len, carried, chunk, want):
    assert pr.selects_state_path(seq_len, carried, chunk) is want


@pytest.mark.parametrize("chunk,path", [(16, "state"), (32, "quadratic")])
def test_a_traced_mixer_is_named_in_the_compiled_text(chunk, path):
    """What replaced ``retention_blocks_traced_total{path}``: the program
    itself says where its retention is. Everything the entry function traces
    carries the part ``mixer`` in the compiled text (``runtime/executor.py:
    parts_of_text``), on either path the shapes select."""
    from agent_tpu.runtime.executor import parts_of_text

    assert pr.selects_state_path(32, False, chunk) is (path == "state")
    q, k, v, g = _inputs(1, 32, 2, 1, 16)
    text = jax.jit(lambda *a: pr.power_retention(
        *a, n_kv_heads=1, chunk=chunk, pallas=False)).lower(
            q, k, v, g).compile().as_text()
    _, parts = parts_of_text(text)
    named = {p for p in parts["instructions"].values() if p}
    assert named == {"mixer"}
    assert parts["named_share"] > 0.7
