"""Gated power retention, degree 2: the sequence mixer of the decoder
language-model family (``models/decoder_lm.py``), chunked with a carried state.

For one key-value head with its ``G`` query heads (grouped-query: 5 to 1 in
the published model), gate ``log g_t <= 0`` (one scalar a token a key-value
head), queries and keys already normed and rotated:

    w[t, s] = exp(sum_{r=s+1..t} log g_r) * (q_t . k_s)^2        s <= t
    y_t     = sum_s w[t, s] v_s / (sum_s w[t, s] + eps)

Equal to it, and linear in length: ``(q . k)^2 = phi(q) . phi(k)`` with
``phi(u)`` the products ``u_i u_j``, so ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``
and the normalizer ``Z_t = g_t Z_{t-1} + k_t k_t^T`` (``sum_s w = q^T Z q``: a
``D x D`` matrix, no expansion needed) carry everything a later token reads.
This file serves a sequence in CHUNKS of ``c`` tokens: inside a chunk the
quadratic form (a decayed, squared, causally masked ``c x c`` score block),
across chunks the state.

How ``phi`` is laid out. The ``D (D + 1) / 2`` distinct products are taken by
DIAGONALS: row ``r`` of the expansion is ``u * roll(u, r)`` for ``r = 0 ..
D/2``, ``D`` lanes each. Rows ``1 .. D/2 - 1`` hold every unordered pair once
and weigh 2 (applied on the key side, exactly, where the issue's description
puts sqrt 2 on both); row 0 is the squares; row ``D/2`` holds each of its
``D/2`` pairs twice at weight 1. That is ``(D/2 + 1) D`` rows (8,320 at
``D = 128`` for the 8,256 distinct products: 64 stored twice) and every row is
a whole 128-lane vector made by one lane rotation. The state is
``S [R, D, D]`` float32 with ``R = D/2 + 1``; the expansion lives in VMEM only.

Which program runs is read from the shapes (:func:`selects_state_path`), as
``flash_attention.selects_whole_row`` does for the encoder: a call whose
sequence fits one chunk and brings no ``initial_state`` is the quadratic form
alone; every other call reads and updates the state. No option, environment
variable or ``model_config`` key chooses. The same function serves a document
given whole and one given as segments: ``initial_state`` in, final state out.

On the chip the chunk runs as ONE Pallas kernel a layer (grid: batch x
key-value head x chunk, the chunk axis sequential, the state resident in the
output block across it); elsewhere, and for head sizes off the lane width,
the same chunked arithmetic in plain ``jax.numpy`` (float32). State and
normalizer are float32 throughout; MXU operands are bf16.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.obs.trace import part

_LANES = 128

# Tokens a chunk. Per query-head token the intra-chunk block costs 256 c
# FLOPs (causal half of 2 x 2 c D) and the state 2.54 M (read 2 R D D, a
# fifth of it again for the update shared by five query heads): at 1,024 the
# block is a tenth of the chunk's work and its c x c float32 temporaries
# (4 MiB each) leave the state, its bf16 copy and the expansion buffer room
# in VMEM. One 4,096-token call at the published heads on a v5e, state
# carried: 4.53 ms at 512, 4.31 at 1,024, 4.61 at 2,048 (PERF.md, PR 27).
RETENTION_CHUNK = 1024
# Rows of the expansion that go into one state-read matmul (K = 13 x 128).
_READ_GROUP = 13
_VMEM_LIMIT = 100 * 1024 * 1024
EPS = 1e-6

State = Tuple[jax.Array, jax.Array]     # S [B, Hkv, R, D, D], Z [B, Hkv, D, D]


def retention_chunk(seq_len: int, chunk: Optional[int] = None) -> int:
    """Tokens a chunk for a call of ``seq_len`` tokens, from the shape: the
    module's chunk, or the whole (8-aligned) sequence when that is shorter."""
    if chunk is not None:
        return int(chunk)
    return min(RETENTION_CHUNK, -(-int(seq_len) // 8) * 8)


def selects_state_path(seq_len: int, carried: bool,
                       chunk: Optional[int] = None) -> bool:
    """Shape predicate, the one place the choice is made: does a call of
    ``seq_len`` tokens (``carried``: it brings an ``initial_state``) run
    chunk-plus-state? Otherwise it is the quadratic form alone."""
    return bool(carried or seq_len > retention_chunk(seq_len, chunk))


def state_rows(d_head: int) -> int:
    """``R``: diagonals of the symmetric expansion, ``D/2 + 1``."""
    return d_head // 2 + 1


def _read_group(rows: int) -> int:
    """Expansion rows a state-read matmul takes: the module's group where
    it divides the state's rows (65 = 5 x 13), one row otherwise."""
    return _READ_GROUP if rows % _READ_GROUP == 0 else 1


def zero_state(batch: int, n_kv_heads: int, d_head: int) -> State:
    r = state_rows(d_head)
    return (jnp.zeros((batch, n_kv_heads, r, d_head, d_head), jnp.float32),
            jnp.zeros((batch, n_kv_heads, d_head, d_head), jnp.float32))


def _row_weight(r: int, d_head: int) -> float:
    return 1.0 if r in (0, d_head // 2) else 2.0


# ---- the chunked arithmetic in plain jax.numpy ---------------------------

def _phi(u: jax.Array, weighted: bool) -> jax.Array:
    """``[..., D] -> [..., R, D]``: ``u * roll(u, r)`` by diagonals; the
    key side carries the rows' weights."""
    d = u.shape[-1]
    rows = [u * jnp.roll(u, r, axis=-1) * (_row_weight(r, d) if weighted
                                           else 1.0)
            for r in range(state_rows(d))]
    return jnp.stack(rows, axis=-2)


def _chunk_step(carry, xs, *, read_state: bool, eps: float):
    """One chunk, every batch row and head at once, float32. ``xs``:
    q [B, H, G, c, D], k, v [B, H, c, D], gc [B, H, c] (cumulative log gate
    inside the chunk)."""
    S, Z = carry
    q, k, v, gc = xs
    c = q.shape[-2]
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    dec = jnp.exp(jnp.where(causal, gc[..., :, None] - gc[..., None, :],
                            -jnp.inf))                       # [B, H, c, c]
    s = jnp.einsum("bhgtd,bhsd->bhgts", q, k)
    p = s * s * dec[:, :, None]
    num = jnp.einsum("bhgts,bhsd->bhgtd", p, v)
    den = p.sum(-1)
    if read_state:
        eg = jnp.exp(gc)[:, :, None, :]                      # [B, H, 1, c]
        num = num + eg[..., None] * jnp.einsum(
            "bhgtrd,bhrde->bhgte", _phi(q, False), S)
        den = den + eg * jnp.einsum("bhgtd,bhde,bhgte->bhgt", q, Z, q)
    y = num / (den[..., None] + eps)
    g_end = gc[..., -1]
    to_end = jnp.exp(g_end[..., None] - gc)                  # [B, H, c]
    d_tot = jnp.exp(g_end)[..., None, None]
    kd = k * to_end[..., None]
    S = d_tot[..., None] * S + jnp.einsum(
        "bhsrd,bhse->bhrde", _phi(k, True) * to_end[..., None, None], v)
    Z = d_tot * Z + jnp.einsum("bhsd,bhse->bhde", kd, k)
    return (S, Z), y


def _retention_jnp(q, k, v, log_g, state, c: int, eps: float):
    """q [B, H, G, L, D], k, v [B, H, L, D], log_g [B, H, L]; L a multiple of
    ``c``. Returns y [B, H, G, L, D] float32 and the final state."""
    B, H, G, L, D = q.shape
    n = L // c
    f32 = jnp.float32
    qc = q.astype(f32).reshape(B, H, G, n, c, D)
    kc = k.astype(f32).reshape(B, H, n, c, D)
    vc = v.astype(f32).reshape(B, H, n, c, D)
    gc = jnp.cumsum(log_g.astype(f32).reshape(B, H, n, c), axis=-1)
    carried = state is not None
    carry = state if carried else zero_state(B, H, D)
    ys = []
    # A Python loop, not a scan: the first chunk of a call without a state
    # is another computation (no read), and calls have few chunks.
    for i in range(n):
        carry, y = _chunk_step(
            carry, (qc[:, :, :, i], kc[:, :, i], vc[:, :, i], gc[:, :, i]),
            read_state=carried or i > 0, eps=eps)
        ys.append(y)
    return jnp.concatenate(ys, axis=-2), carry


# ---- the Pallas kernel ----------------------------------------------------

def _retention_kernel(*refs, c: int, d: int, groups: int, n_chunks: int,
                      carried: bool, eps: float):
    """One (batch row, key-value head, chunk) step. Every loop over heads,
    expansion rows and state rows is a ``fori_loop`` with a dynamic rotation:
    unrolled, the 5 x 65 expansions made a kernel that took four minutes to
    compile."""
    if carried:
        (q_ref, kt_ref, v_ref, gcol_ref, grow_ref, s0_ref, z0_ref,
         y_ref, s_ref, z_ref, num_ref, den_ref, sbf_ref, qx_ref) = refs
    else:
        (q_ref, kt_ref, v_ref, gcol_ref, grow_ref,
         y_ref, s_ref, z_ref, num_ref, den_ref, sbf_ref, qx_ref) = refs
        s0_ref = z0_ref = None
    f32, bf16 = jnp.float32, jnp.bfloat16
    R = state_rows(d)
    rg = _read_group(R)
    ci = pl.program_id(2)
    nn = (((1,), (0,)), ((), ()))           # [m, k] x [k, n]

    @pl.when(ci == 0)
    def _():
        if carried:
            s_ref[...] = s0_ref[...]
            z_ref[...] = z0_ref[...]
        else:
            s_ref[...] = jnp.zeros(s_ref.shape, f32)
            z_ref[...] = jnp.zeros(z_ref.shape, f32)

    gcol = gcol_ref[0, 0]                                   # [c, 1]
    grow = grow_ref[0, 0]                                   # [1, c]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    dec = jnp.exp(jnp.where(s_idx <= t_idx, gcol - grow, -1e30))
    kt = kt_ref[0]                                          # [D, c] bf16
    v = v_ref[0]                                            # [c, D] bf16

    # Inside the chunk: the quadratic form.
    def intra(a, _):
        s = jax.lax.dot_general(q_ref[0, a], kt, nn,
                                preferred_element_type=f32)  # [c, c]
        p = s * s * dec
        den_ref[a] = jnp.sum(p, axis=-1, keepdims=True)
        num_ref[a] = jax.lax.dot_general(p.astype(bf16), v, nn,
                                         preferred_element_type=f32)
        return 0

    jax.lax.fori_loop(0, groups, intra, 0)

    # Across chunks: what the carried state adds, decayed to each token.
    def read_state():
        eg = jnp.exp(gcol)                                  # [c, 1]

        def cast_rows(j, _):
            rows = s_ref[0, 0, pl.ds(j * rg, rg)]           # [rg, D, D] f32
            sbf_ref[pl.ds(pl.multiple_of(j * rg * d, rg * d), rg * d), :] = (
                rows.reshape(rg * d, d).astype(bf16))
            return 0

        jax.lax.fori_loop(0, R // rg, cast_rows, 0)
        z = z_ref[0, 0]
        z_hi = z.astype(bf16)
        z_lo = (z - z_hi.astype(f32)).astype(bf16)

        def per_head(a, _):
            q_a = q_ref[0, a]
            q_f = q_a.astype(f32)

            def group(j, inter):
                for rr in range(rg):
                    rolled = pltpu.roll(q_f, j * rg + rr, 1)
                    qx_ref[:, rr * d:(rr + 1) * d] = (q_f * rolled).astype(bf16)
                rows = sbf_ref[
                    pl.ds(pl.multiple_of(j * rg * d, rg * d), rg * d), :]
                return inter + jax.lax.dot_general(
                    qx_ref[...], rows, nn, preferred_element_type=f32)

            inter = jax.lax.fori_loop(0, R // rg, group,
                                      jnp.zeros((c, d), f32))
            num_ref[a] = num_ref[a] + eg * inter
            qz = jax.lax.dot_general(
                q_a, z_hi, nn, preferred_element_type=f32
            ) + jax.lax.dot_general(q_a, z_lo, nn, preferred_element_type=f32)
            den_ref[a] = den_ref[a] + eg * jnp.sum(
                qz * q_f, axis=-1, keepdims=True)
            return 0

        jax.lax.fori_loop(0, groups, per_head, 0)

    if carried:
        read_state()
    elif n_chunks > 1:
        pl.when(ci > 0)(read_state)

    def write(a, _):
        y_ref[0, a] = (num_ref[a] / (den_ref[a] + eps)).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, groups, write, 0)

    # The state after this chunk: decayed to the chunk's end, plus its keys.
    g_end = grow[:, c - 1:c]                                # [1, 1]
    d_tot = jnp.exp(g_end)
    kt_f = kt.astype(f32)
    kt_d = kt_f * jnp.exp(g_end - grow)                     # [D, c]

    def update(r, _):
        upd = jax.lax.dot_general(
            (kt_d * pltpu.roll(kt_f, r, 0)).astype(bf16), v, nn,
            preferred_element_type=f32)                     # [D, D]
        weight = jnp.where((r == 0) | (r == d // 2), 1.0, 2.0)
        s_ref[0, 0, r] = d_tot * s_ref[0, 0, r] + weight * upd
        return 0

    jax.lax.fori_loop(0, R, update, 0)
    z_ref[0, 0] = d_tot * z_ref[0, 0] + jax.lax.dot_general(
        kt_d.astype(bf16), kt, (((1,), (1,)), ((), ())),
        preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "c", "eps", "interpret"))
def _retention_call(q, k, v, log_g, state, *, n_kv_heads: int, c: int,
                    eps: float, interpret: bool):
    """The ``pallas_call`` under a jit of its own (traced once a shape in the
    process, as ``flash_attention._whole_row_call``). q [B, L, Hq*D] bf16,
    k, v [B, L, Hkv*D] bf16, log_g [B, L, Hkv] f32; L a multiple of ``c``.
    The kernel takes q and gives y head-major ([B, Hq, L, D]: a head is a
    leading index there, which a loop may index dynamically) and k
    transposed; XLA makes those layouts in HBM."""
    B, L, HD = q.shape
    H = n_kv_heads
    d = k.shape[-1] // H
    hq = HD // d
    groups = hq // H
    n_chunks = L // c
    R = state_rows(d)
    carried = state is not None
    gc = jnp.cumsum(log_g.astype(jnp.float32).reshape(B, n_chunks, c, H),
                    axis=2).reshape(B, L, H).transpose(0, 2, 1)   # [B, H, L]
    kt = k.transpose(0, 2, 1)                                     # [B, H*D, L]
    qh = q.reshape(B, L, hq, d).transpose(0, 2, 1, 3)             # [B, Hq, L, D]
    s_block = pl.BlockSpec((1, 1, R, d, d), lambda b, h, i: (b, h, 0, 0, 0))
    z_block = pl.BlockSpec((1, 1, d, d), lambda b, h, i: (b, h, 0, 0))
    q_block = pl.BlockSpec((1, groups, c, d), lambda b, h, i: (b, h, i, 0))
    in_specs = [
        q_block,
        pl.BlockSpec((1, d, c), lambda b, h, i: (b, h, i)),
        pl.BlockSpec((1, c, d), lambda b, h, i: (b, i, h)),
        pl.BlockSpec((1, 1, c, 1), lambda b, h, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, 1, c), lambda b, h, i: (b, h, 0, i)),
    ]
    args = [qh, kt, v, gc[..., None], gc[:, :, None, :]]
    if carried:
        in_specs += [s_block, z_block]
        args += [state[0], state[1]]
    rg = _read_group(R)
    tokens = B * L * H
    read_flops = 2 * groups * R * d * d * tokens
    kernel = functools.partial(
        _retention_kernel, c=c, d=d, groups=groups, n_chunks=n_chunks,
        carried=carried, eps=eps)
    y, s_out, z_out = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=in_specs,
        out_specs=[q_block, s_block, z_block],
        out_shape=[
            jax.ShapeDtypeStruct(qh.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, R, d, d), jnp.float32),
            jax.ShapeDtypeStruct((B, H, d, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((groups, c, d), jnp.float32),       # numerator
            pltpu.VMEM((groups, c, 1), jnp.float32),       # denominator
            pltpu.VMEM((R * d, d), jnp.bfloat16),          # the state, bf16
            pltpu.VMEM((c, rg * d), jnp.bfloat16),         # expansion rows
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * groups * c * d * tokens + read_flops
            + read_flops // groups,
            bytes_accessed=2 * (2 * q.size + k.size + v.size)
            + 8 * B * H * (R + 1) * d * d,
            transcendentals=tokens * c,
        ),
        name="power_retention",
        interpret=interpret,
    )(*args)
    return y.transpose(0, 2, 1, 3).reshape(B, L, HD), (s_out, z_out)


def pallas_supported(d_head: int, chunk: int, dtype) -> bool:
    """Shapes the kernel takes on the chip: lane-wide heads, a chunk of whole
    128-lane groups, bf16 operands."""
    return bool(d_head == _LANES and chunk % _LANES == 0
                and jnp.dtype(dtype) == jnp.bfloat16)


@part("mixer")
def power_retention(
    q: jax.Array,          # [B, L, Hq*D]   normed, rotated
    k: jax.Array,          # [B, L, Hkv*D]  normed, rotated
    v: jax.Array,          # [B, L, Hkv*D]
    log_g: jax.Array,      # [B, L, Hkv]    log sigmoid of the gate, <= 0
    *,
    n_kv_heads: int,
    initial_state: Optional[State] = None,
    chunk: Optional[int] = None,
    eps: float = EPS,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, State]:
    """Gated power retention of degree 2 → ``(y [B, L, Hq*D], final state)``.

    ``initial_state`` is what an earlier call of the same document returned
    (``None``: the document starts here). ``chunk`` overrides the chunk the
    shapes give, for tests and sweeps. ``pallas=None`` takes the kernel on a
    TPU backend for the shapes :func:`pallas_supported` accepts and the
    ``jax.numpy`` form elsewhere; ``interpret`` as in ``flash_attention``."""
    B, L, HD = q.shape
    H = int(n_kv_heads)
    d = k.shape[-1] // H
    groups = HD // (H * d)
    c = retention_chunk(L, chunk)
    pad = -L % c
    if pad:
        # Padding tokens sit after the real ones: zero keys and values and a
        # gate of 1 leave both the real outputs and the state as they are.
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    if pallas and pallas_supported(d, c, q.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        y, state = _retention_call(
            q, k, v, log_g, initial_state, n_kv_heads=H, c=c,
            eps=float(eps), interpret=resolve_interpret(interpret))
    else:
        Lp = L + pad
        q5 = q.reshape(B, Lp, H, groups, d).transpose(0, 2, 3, 1, 4)
        k4 = k.reshape(B, Lp, H, d).transpose(0, 2, 1, 3)
        v4 = v.reshape(B, Lp, H, d).transpose(0, 2, 1, 3)
        y5, state = _retention_jnp(q5, k4, v4, log_g.transpose(0, 2, 1),
                                   initial_state, c, float(eps))
        y = y5.transpose(0, 3, 1, 2, 4).reshape(B, Lp, HD).astype(q.dtype)
    return (y[:, :L] if pad else y), state


@part("mixer")
def retention_step(q_t, k_t, v_t, log_g_t, state: State, eps: float = EPS):
    """The recurrent one-token form, float32: a second statement of the
    equations for the tests (nothing serves decode). q_t [B, H, G, D], k_t,
    v_t [B, H, D], log_g_t [B, H]. Returns ``(y_t [B, H, G, D], state)``."""
    S, Z = state
    g = jnp.exp(log_g_t.astype(jnp.float32))
    k_t, v_t, q_t = (x.astype(jnp.float32) for x in (k_t, v_t, q_t))
    S = g[..., None, None, None] * S + jnp.einsum(
        "bhrd,bhe->bhrde", _phi(k_t, True), v_t)
    Z = g[..., None, None] * Z + jnp.einsum("bhd,bhe->bhde", k_t, k_t)
    num = jnp.einsum("bhgrd,bhrde->bhge", _phi(q_t, False), S)
    den = jnp.einsum("bhgd,bhde,bhge->bhg", q_t, Z, q_t)
    return num / (den[..., None] + eps), (S, Z)
