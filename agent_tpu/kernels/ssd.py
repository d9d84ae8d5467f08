"""The Mamba-2 state-space scan, chunked with a carried state, and the causal
depthwise convolution in front of it: the state-space half of the
``hybrid_ssm`` mixer of the decoder language-model family
(``models/decoder_lm.py``), for one document's segment.

For head ``j`` of group ``g(j)`` (the heads of a group share ``B`` and ``C``;
``x`` is a head's own), step ``dt_t > 0`` and ``A_j < 0``:

    S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t B_t^T          S in R^{P x N}
    y_t = S_t C_t

(the skip ``D_j x_t`` is the caller's: it fuses with the gate that follows).
Served in CHUNKS of ``c`` tokens (the state-space duality): with ``cs_t`` the
running sum of ``dt_r A_j`` inside a chunk,

    inside   Y  = ((C B^T) o L) (dt o X)        L[t, s] = exp(cs_t - cs_s), s <= t
    across   Y += exp(cs_t) C_t S_prev
    state    S  = exp(cs_end) S_prev + sum_s exp(cs_end - cs_s) dt_s x_s B_s^T

``C B^T`` is computed once a group and meets every head of it. The state is
float32 and kept TRANSPOSED, ``[H, N, P]``: both of a head's matmuls then take
their operands as stored. The same function serves a document given whole and
one given as segments: ``initial_state`` in, final state out.

On the chip one Pallas kernel a layer (grid: group x chunk, the chunk axis
sequential, a group's states resident in the output block across it, every
head of the group in one step: a (head, chunk) step would be a tenth of a
microsecond of matmuls under a third of a microsecond of step overhead);
elsewhere, and for shapes off the lane width, the same chunked arithmetic in
plain ``jax.numpy`` (float32). Which runs is read from shapes and platform
(:func:`pallas_supported`); no option, environment variable or
``model_config`` key chooses. MXU operands are bf16, sums float32.
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
_VMEM_LIMIT = 100 * 1024 * 1024


def zero_state(n_heads: int, d_head: int, d_state: int) -> jax.Array:
    """``[H, N, P]`` float32: the state before a document's first token."""
    return jnp.zeros((n_heads, d_state, d_head), jnp.float32)


# ---- the causal depthwise convolution -------------------------------------

@part("around")
def causal_conv(u: jax.Array, tail: Optional[jax.Array], w: jax.Array,
                b: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """``out_t = b + sum_i w[i] u_{t - (K-1) + i}`` a channel, float32:
    u [S, C], ``tail`` the ``K - 1`` rows before the segment ([K-1, C];
    ``None``: the document starts here, zeros), w [K, C], b [C] (``None``:
    the convolution has no bias, and its sum starts at the first tap). Returns the
    convolved rows and the tail the NEXT segment needs: the last ``K - 1``
    rows of ``[tail; u]``. ``K`` shifted multiply-adds, which XLA fuses into
    the pass that reads ``u``: a kernel has nothing to win on 0.2 % of a
    segment's bytes."""
    f32 = jnp.float32
    S, C = u.shape
    K = w.shape[0]
    u = u.astype(f32)
    if tail is None:
        tail = jnp.zeros((K - 1, C), f32)
    ext = jnp.concatenate([tail.astype(f32), u], axis=0)     # [S + K - 1, C]
    out = None if b is None else b.astype(f32)[None, :]
    for i in range(K):
        tap = w[i].astype(f32)[None, :] * ext[i:i + S]
        out = tap if out is None else out + tap
    return out, ext[S:]


# ---- the chunked arithmetic in plain jax.numpy ---------------------------

def _ssd_jnp(x, dt, A, B, C, state, c: int):
    """x [S, H, P], dt [S, H], A [H], B, C [S, G, N] (float32), state
    [H, N, P]; S a multiple of ``c``. Returns y [S, H, P] and the state."""
    S, H, P = x.shape
    G, N = B.shape[1:]
    hg = H // G
    n = S // c
    f32 = jnp.float32
    xc = x.reshape(n, c, G, hg, P)
    dtc = dt.reshape(n, c, G, hg)
    cs = jnp.cumsum(dtc * A.reshape(G, hg), axis=1)          # [n, c, G, hg]
    Bc, Cc = B.reshape(n, c, G, N), C.reshape(n, c, G, N)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]

    def chunk(St, xs):
        x, dt, cs, B, C = xs
        St = St.reshape(G, hg, N, P)
        cb = jnp.einsum("tgn,sgn->gts", C, B)                # once a group
        dec = jnp.exp(jnp.where(causal[:, :, None, None],
                                cs[:, None] - cs[None, :], -jnp.inf))
        xdt = x * dt[..., None]
        y = jnp.einsum("gts,tsgh,sghp->tghp", cb, dec, xdt)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum("tgn,ghnp->tghp", C, St)
        end = cs[-1]                                         # [G, hg]
        St = jnp.exp(end)[..., None, None] * St + jnp.einsum(
            "sgn,sghp->ghnp", B, xdt * jnp.exp(end[None] - cs)[..., None])
        return St.reshape(H, N, P), y

    state, y = jax.lax.scan(chunk, state.astype(f32), (xc, dtc, cs, Bc, Cc))
    return y.reshape(S, H, P), state


# ---- the Pallas kernel ----------------------------------------------------

def _ssd_kernel(end_ref, tot_ref, x_ref, dt_ref, csc_ref, csr_ref, bt_ref,
                c_ref, s0_ref, y_ref, s_ref, *, c: int, hg: int, p: int):
    """One (group, chunk) step: every head of the group. A chunk's whole
    decay a head comes in as two prefetched scalars (the sum and its
    exponential): a [1, 1] vector does not broadcast over a state."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    nn = (((1,), (0,)), ((), ()))           # [m, k] x [k, n]
    base = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * hg

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    bt = bt_ref[...]                                        # [N, c] bf16
    cc = c_ref[...]                                         # [c, N] bf16
    cb = jax.lax.dot_general(cc, bt, nn, preferred_element_type=f32)  # [c, c]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    causal = s_idx <= t_idx
    dt = dt_ref[0]                                          # [c, hg] f32
    csc = csc_ref[0]                                        # [c, hg]
    csr = csr_ref[0]                                        # [hg, c]
    for a in range(hg):
        col = csc[:, a:a + 1]                               # [c, 1]
        row = csr[a:a + 1, :]                               # [1, c]
        end = end_ref[base + a]
        dec = jnp.exp(jnp.where(causal, col - row, -1e30))
        x = x_ref[:, a * p:(a + 1) * p].astype(f32)         # [c, P]
        xdt = x * dt[:, a:a + 1]
        st = s_ref[a]                                       # [N, P] f32
        y = jax.lax.dot_general((cb * dec).astype(bf16), xdt.astype(bf16), nn,
                                preferred_element_type=f32)
        y = y + jnp.exp(col) * jax.lax.dot_general(
            cc, st.astype(bf16), nn, preferred_element_type=f32)
        y_ref[:, a * p:(a + 1) * p] = y.astype(y_ref.dtype)
        s_ref[a] = tot_ref[base + a] * st + jax.lax.dot_general(
            bt, (xdt * jnp.exp(end - col)).astype(bf16), nn,
            preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _ssd_call(x, dt, A, B, C, state, *, c: int, interpret: bool):
    """x [S, H*P] bf16, dt [S, H] f32, A [H], B, C [S, G*N] bf16, state
    [H, N, P] f32; S a multiple of ``c``. The running sums of ``dt A`` inside
    a chunk are XLA's (a [S, H] float32 array), handed to the kernel with the
    chunk's tokens in the sublanes and, transposed, in the lanes."""
    S, HP = x.shape
    H, N, P = state.shape
    G = B.shape[1] // N
    hg = H // G
    n_chunks = S // c
    f32 = jnp.float32
    dt = dt.astype(f32)
    cs = jnp.cumsum((dt * A.astype(f32)).reshape(n_chunks, c, H),
                    axis=1).reshape(S, H)
    by_group = lambda a: a.reshape(S, G, hg).transpose(1, 0, 2)  # noqa: E731
    csc = by_group(cs)                                       # [G, S, hg]
    # [G, chunks, hg] flat: a chunk's last running sum a head.
    ends = cs.reshape(n_chunks, c, G, hg)[:, -1].transpose(1, 0, 2).reshape(-1)
    s_block = pl.BlockSpec((hg, N, P), lambda g, i, *_: (g, 0, 0))
    x_block = pl.BlockSpec((c, hg * P), lambda g, i, *_: (i, g))
    col_block = pl.BlockSpec((1, c, hg), lambda g, i, *_: (g, i, 0))
    tokens = S * H
    y, s_out = pl.pallas_call(
        functools.partial(_ssd_kernel, c=c, hg=hg, p=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, n_chunks),
            in_specs=[
                x_block, col_block, col_block,
                pl.BlockSpec((1, hg, c), lambda g, i, *_: (g, 0, i)),
                pl.BlockSpec((N, c), lambda g, i, *_: (g, i)),
                pl.BlockSpec((c, N), lambda g, i, *_: (i, g)),
                s_block,
            ],
            out_specs=[x_block, s_block],
        ),
        out_shape=[jax.ShapeDtypeStruct((S, HP), x.dtype),
                   jax.ShapeDtypeStruct((H, N, P), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=tokens * (2 * c * P + 4 * N * P) + 2 * S * G * c * N,
            bytes_accessed=2 * 2 * x.size + 2 * (B.size + C.size)
            + 12 * dt.size + 8 * state.size,
            transcendentals=tokens * c,
        ),
        name="ssd_scan",
        interpret=interpret,
    )(ends, jnp.exp(ends), x, by_group(dt), csc, csc.transpose(0, 2, 1), B.T,
      C, state)
    return y, s_out


def pallas_supported(d_head: int, d_state: int, chunk: int, dtype) -> bool:
    """Shapes the kernel takes on the chip: lane-wide heads, a state and a
    chunk of whole 128-lane groups, bf16 operands."""
    return bool(d_head == _LANES and d_state % _LANES == 0
                and chunk % _LANES == 0
                and jnp.dtype(dtype) == jnp.bfloat16)


@part("mixer")
def ssd_scan(
    x: jax.Array,          # [S, H*P]   a head's inputs, after conv and SiLU
    dt: jax.Array,         # [S, H]     softplus(dt + bias) > 0, float32
    A: jax.Array,          # [H]        -exp(A_log) < 0, float32
    B: jax.Array,          # [S, G*N]   a group's input map
    C: jax.Array,          # [S, G*N]   a group's output map
    *,
    n_heads: int,
    n_groups: int,
    chunk: int,
    initial_state: Optional[jax.Array] = None,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The scan of one document's segment → ``(y [S, H*P], state [H, N, P]
    float32)``. ``initial_state`` is what the call on the segment before
    returned (``None``: the document starts here). A segment that is not
    whole chunks is padded behind its last token with ``dt = 0``: a step of
    no time leaves the state as it is."""
    S, HP = x.shape
    H = int(n_heads)
    P = HP // H
    G = int(n_groups)
    N = B.shape[1] // G
    c = int(chunk)
    state = zero_state(H, P, N) if initial_state is None else initial_state
    pad = -S % c
    if pad:
        x, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, B, C))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    if pallas and pallas_supported(P, N, c, x.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        y, state = _ssd_call(x, dt, A, B, C, state, c=c,
                             interpret=resolve_interpret(interpret))
    else:
        f32 = jnp.float32
        Sp = S + pad
        y, state = _ssd_jnp(
            x.astype(f32).reshape(Sp, H, P), dt.astype(f32), A.astype(f32),
            B.astype(f32).reshape(Sp, G, N), C.astype(f32).reshape(Sp, G, N),
            state, c)
        y = y.reshape(Sp, HP).astype(x.dtype)
    return (y[:S] if pad else y), state
