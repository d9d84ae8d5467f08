"""The gated delta rule with a decay A CHANNEL (Kimi delta attention, KDA),
chunked with a carried state: the linear-attention layers of the
``hybrid_kda`` mixer of the decoder language-model family
(``models/decoder_lm.py``), for one document's segment.

For one head (``d`` key and ``d`` value channels), queries and keys already
convolved, L2-normalised and the query scaled; ``beta_t`` in (0, 1); the
log-decay ``g_t <= 0`` one a KEY CHANNEL (``[d]``), never under
``lower_bound`` a token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                      S in R^{d x d}, float32

Neither ``power_retention.py`` (the state is only ever added to) nor
``ssd.py`` (one decay a head, no delta term) computes it. Served in CHUNKS of
``CHUNK`` tokens. With ``G_t`` the running sum of ``g`` inside a chunk (a
channel) and ``u_s = beta_s (v_s - k_s^T Diag(exp g_s) S_{s-1})`` what token
``s`` really writes,

    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <  t
    B[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp G) S_0)
    O  = (Q * exp G) S_0 + B U
    S' = Diag(exp G_end) S_0 + (K * exp(G_end - G))^T U

``exp(G_t - G_s)`` is no product of a row's and a column's factor that float32
holds over a whole chunk (``exp(5 x 64)``), so a chunk is ``CHUNK / SUB``
SUB-BLOCKS of ``SUB = 16`` tokens and only DIFFERENCES of running sums are
ever exponentiated: a row of sub-block ``r`` carries ``exp(G_t - G_r)``
(``G_r`` the running sum where the sub-block starts: at most 1) and a column
``exp(G_r - G_s)``: at most 1 for a column of an earlier sub-block, and at
most ``exp(-lower_bound x SUB)`` = ``e^80`` inside the row's own, which is
what ``lower_bound >= -5`` is for (:func:`pallas_supported`,
``decoder_lm.validate``). The unit lower-triangular system is solved in
blocks: the ``SUB x SUB`` diagonal blocks are inverted by the finite product
``(I - N)(I + N^2)(I + N^4)(I + N^8)`` (``N^16 = 0``); the rest is
nilpotent of degree ``CHUNK / SUB`` over those blocks and is solved exactly
either way: in the kernel by forward substitution over the sub-blocks,
``U_r = (I + N_rr)^-1 (R_r - sum_{s<r} N_rs U_s)``, in the ``jax.numpy`` form
by ``CHUNK / SUB - 1`` turns of ``Y = Y0 - M Y`` (:func:`_solve_blocks`). A
product over the whole chunk at once would sum binomially large terms of
alternating sign where keys repeat.

The state is float32, ``[H, d (keys), d (values)]``. The same function serves
a document given whole and one given as segments: ``initial_state`` in,
final state out. On the chip one Pallas kernel a layer (grid: head block x
chunk, the chunk axis sequential, the heads' states resident in the output
block across it); elsewhere, and for shapes off the lane width, the same
chunked arithmetic in plain ``jax.numpy`` (float32). Which runs is read from
shapes and platform (:func:`pallas_supported`); no option, environment
variable or ``model_config`` key chooses. On the chip the matmuls that meet
the state or the values take bf16 operands, the triangular system's float32
ones (the MXU's float32 product is ONE pass at bf16's rows a cycle on this
chip; bf16 operands there bought nothing when tried); sums are float32.

The kernel's step, and why it is written as it is (PERF.md, PR 50):

- A step holds ``gcd(H, HEADS_A_STEP)`` heads. Their products are stated IN
  TURN, every head's first, then every head's second: an MXU takes its
  products in the order the program states them and a product's result
  comes some 130 cycles after its last row went in, so a head's chain of
  dependent products (the inverse's six, the substitution's seven) waits
  under the other heads' products only if those stand between its own.
  Stated a head after the other the same arithmetic took four times as long.
- The diagonal blocks never stand in a ``[CHUNK, CHUNK]`` matrix of mostly
  zeros: a head's four lie side by side, ``[SUB, CHUNK]``, and where the
  step holds an even number of heads a PAIR's eight, ``[SUB, 128]``: a
  vreg's lanes full. ``x @ spread(y)`` (``y``'s blocks down the diagonal of
  a ``[128, 128]`` matrix) multiplies block by block, so the finite product
  is six products of 16 rows a pair where it was twelve of 64. A step of an
  odd number of heads (``H`` odd) inverts each head's four blocks on 64
  lanes.
- The substitution's products are ``[SUB, SUB] x [SUB, d]`` and
  ``[SUB, r SUB] x [r SUB, d]``: the rows the system's structure has, where
  ``Td (N - Nd)``, ``Td R`` and three turns of ``Y0 - M U`` were five
  products of 64 rows.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.obs.trace import part

_LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024
# Tokens a chunk and a sub-block (see the module docstring).
CHUNK = 64
SUB = 16
# The largest exponent a column's factor may take: ``-lower_bound x SUB``.
_MAX_EXPONENT = 80.0
# The most heads a grid step holds.
HEADS_A_STEP = 16


def zero_state(n_heads: int, d_head: int) -> jax.Array:
    """``[H, d, d]`` float32: the state before a document's first token."""
    return jnp.zeros((n_heads, d_head, d_head), jnp.float32)


def chunks_of(n_tokens: int) -> int:
    """Chunks a segment of ``n_tokens`` runs as (its padding included)."""
    return -(-int(n_tokens) // CHUNK)


# ---- one token ------------------------------------------------------------

def kda_step(q, k, v, g, beta, state):
    """One token of every head, the recurrence as written: q, k, v, g [H, d]
    float32, beta [H], state [H, d, d] → ``(o [H, d], state)``. What a
    decoding step would run; here only tests call it."""
    state = jnp.exp(g)[..., None] * state
    written = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, state))
    state = state + k[..., None] * written[:, None, :]
    return jnp.einsum("hk,hkv->hv", q, state), state


# ---- the chunked arithmetic in plain jax.numpy ---------------------------

def _solve_blocks(N, R):
    """``(I + N)^-1 R`` for ``N [..., c, c]`` strictly lower triangular, by
    blocks of ``SUB`` (the module docstring's two levels: the blocks' finite
    product, then the turns), float32."""
    c = N.shape[-1]
    eye = jnp.eye(c, dtype=N.dtype)
    block = jnp.arange(c) // SUB
    diagonal = block[:, None] == block[None, :]
    Nd = jnp.where(diagonal, N, 0.0)
    Td, power = eye - Nd, Nd
    for _ in range(3):                     # (I + N^2)(I + N^4)(I + N^8)
        power = power @ power
        Td = Td + Td @ power
    M = Td @ (N - Nd)                      # nilpotent over the sub-blocks
    Y0 = Td @ R
    Y = Y0
    for _ in range(c // SUB - 1):          # (I + M)^-1 Y0, a sub-block a turn
        Y = Y0 - M @ Y
    return Y


def _kda_jnp(q, k, v, g, beta, state):
    """q, k, v, g [n, c, H, d] float32 (whole chunks), beta [n, c, H], state
    [H, d, d] → ``(o [n, c, H, d], state)``: a ``lax.scan`` over chunks,
    every head at once."""
    c = q.shape[1]
    t = jnp.arange(c)
    lower, strict = t[:, None] >= t[None, :], t[:, None] > t[None, :]

    def chunk(S, xs):
        q, k, v, g, beta = xs                                  # [c, H, d]
        G = jnp.cumsum(g, axis=0)
        # exp(G_t - G_s) a channel, masked BEFORE the exponential: float32
        # on [c, c, H, d] is what the kernel's sub-blocks are there to avoid.
        dec = jnp.exp(jnp.where(lower[:, :, None, None],
                                G[:, None] - G[None, :], -jnp.inf))
        A = jnp.einsum("thc,shc,tshc->hts", k, k, dec)
        B = jnp.einsum("thc,shc,tshc->hts", q, k, dec)
        eg = jnp.exp(G)
        R = beta[..., None] * (v - jnp.einsum("thk,hkv->thv", k * eg, S))
        N = jnp.where(strict, A, 0.0) * beta.T[:, :, None]
        U = _solve_blocks(N, R.transpose(1, 0, 2))             # [H, c, d]
        o = jnp.einsum("thk,hkv->thv", q * eg, S) + jnp.einsum(
            "hts,hsv->thv", jnp.where(lower, B, 0.0), U)
        S = jnp.exp(G[-1])[..., None] * S + jnp.einsum(
            "shk,hsv->hkv", k * jnp.exp(G[-1][None] - G), U)
        return S, o

    state, o = jax.lax.scan(chunk, state.astype(jnp.float32),
                            (q, k, v, g, beta))
    return o, state


# ---- the Pallas kernel ----------------------------------------------------

def _kda_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref,
                *, heads: int, d: int):
    """One (head block, chunk) step: ``heads`` heads, each on its own but for
    the lanes its diagonal blocks share with a neighbour's (the module
    docstring's "the triangular system")."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    c, n_sub = CHUNK, CHUNK // SUB
    side = 2 if heads % 2 == 0 else 1       # heads whose blocks share lanes
    wide = side * c                         # lanes of their blocks
    nn = (((1,), (0,)), ((), ()))           # [m, k] x [k, n]
    nt = (((1,), (1,)), ((), ()))           # [m, k] x [n, k]
    tn = (((0,), (0,)), ((), ()))           # [k, m] x [k, n]

    def dot(a, b, dims=nn, dtype=bf16):
        return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                                   preferred_element_type=f32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    lower = iota((c, c), 1) <= iota((c, c), 0)
    # A sub-block's rows against the chunk's columns: the strictly lower part
    # of the block they make with the sub-block's own columns.
    row, col = iota((SUB, c), 0), iota((SUB, c), 1)
    own_strict = [(col >= r * SUB) & (col - r * SUB < row)
                  for r in range(n_sub)]
    # ``wide`` lanes of SUB x SUB blocks: the identity in each, and which
    # entries of a [wide, wide] matrix lie in a block of its diagonal.
    eye = (iota((SUB, wide), 1) % SUB == iota((SUB, wide), 0)).astype(f32)
    on_diagonal = (iota((wide, wide), 0) // SUB
                   == iota((wide, wide), 1) // SUB)
    sub_of_row = iota((c, d), 0) // SUB
    beta_all = beta_ref[0]                                   # [c, heads] f32

    def spread(blocks):
        """Blocks side by side, [SUB, wide] → the same blocks down the
        diagonal of a [wide, wide] matrix (zeros elsewhere), so that
        ``x @ spread(y)`` multiplies block by block."""
        return jnp.where(on_diagonal, jnp.concatenate(
            [blocks] * (wide // SUB), axis=0), 0.0)

    def before_the_system(a):
        """Head ``a`` of the step up to the triangular system: what the
        system's right-hand side, its matrix and the output need."""
        lanes = slice(a * d, (a + 1) * d)
        q = q_ref[:, lanes].astype(f32)                      # [c, d]
        k = k_ref[:, lanes].astype(f32)
        v = v_ref[:, lanes].astype(f32)
        G = g_ref[:, lanes]                                  # running sums
        beta = beta_all[:, a:a + 1]                          # [c, 1]
        S = s_ref[a]                                         # [d, d] f32

        # The running sum where each row's sub-block starts.
        starts = [jnp.zeros((1, d), f32)] + [
            G[r * SUB - 1:r * SUB, :] for r in range(1, n_sub)]
        start_of_row = jnp.zeros((c, d), f32)
        for r in range(1, n_sub):
            start_of_row = jnp.where(sub_of_row == r, starts[r], start_of_row)
        inside = jnp.exp(G - start_of_row)                   # <= 1
        rows = jnp.concatenate([q * inside, k * inside], axis=0)  # [2c, d]
        # Sub-block r's rows of A and B: its rows against the columns seen
        # from where it starts. Of N = Diag(beta) A the system wants the
        # columns of EARLIER sub-blocks as they lie (``earlier[r]``,
        # [SUB, r SUB]) and the sub-block's own, strictly lower, side by side
        # with the other sub-blocks' (``blocks``, [SUB, c]).
        b_rows, earlier = [], [None]
        blocks = jnp.zeros((SUB, c), f32)
        for r in range(n_sub):
            mine = slice(r * SUB, (r + 1) * SUB)
            cols = k * jnp.exp(jnp.minimum(starts[r] - G, _MAX_EXPONENT))
            both = dot(jnp.concatenate([rows[mine], rows[c:][mine]], axis=0),
                       cols, nt)                             # [2 SUB, c]
            b_rows.append(both[:SUB])
            n_rows = both[SUB:] * beta[mine]
            blocks = blocks + jnp.where(own_strict[r], n_rows, 0.0)
            if r:
                earlier.append(n_rows[:, :r * SUB])
        B = jnp.concatenate(b_rows, axis=0)                  # [c, c]

        eg = jnp.exp(G)
        from_state = dot(jnp.concatenate([q * eg, k * eg], axis=0), S)
        R = beta * (v - from_state[c:])                      # [c, d]
        return dict(a=a, lanes=lanes, k=k, G=G, S=S, B=B, R=R, blocks=blocks,
                    earlier=earlier, o=from_state[:c])

    # Every head of the step advances TOGETHER, a product of each after the
    # same product of the one before: an MXU takes its products in the order
    # the program states them, so a head's chain of dependent products waits
    # out its latency under the other heads' only if theirs stand between.
    step = [before_the_system(a) for a in range(heads)]
    groups = [step[first:first + side] for first in range(0, heads, side)]

    # (I + N_rr)^-1 of every diagonal block of a group at once, by the finite
    # product: ``power`` and ``inverse`` are [SUB, wide], a block every SUB
    # lanes.
    powers = [jnp.concatenate([h["blocks"] for h in group], axis=1)
              for group in groups]
    inverses = [eye - power for power in powers]
    spreads = [spread(power) for power in powers]
    for _ in range(3):                     # (I + N^2)(I + N^4)(I + N^8)
        powers = [dot(power, across, dtype=f32)
                  for power, across in zip(powers, spreads)]
        spreads = [spread(power) for power in powers]
        inverses = [inverse + dot(inverse, across, dtype=f32)
                    for inverse, across in zip(inverses, spreads)]
    # A head's four [SUB, SUB] inverses, in the order of ``step``.
    inverse_of = [
        [inverse[:, at:at + SUB] for at in range(j * c, (j + 1) * c, SUB)]
        for inverse in inverses for j in range(side)]

    # U_r = (I + N_rr)^-1 (R_r - sum_{s<r} N_rs U_s), a sub-block after the
    # other.
    solved = [[] for _ in step]
    for r in range(n_sub):
        mine = slice(r * SUB, (r + 1) * SUB)
        sides = [h["R"][mine] for h in step]
        if r:
            sides = [rhs - dot(h["earlier"][r], jnp.concatenate(us, axis=0),
                               dtype=f32)
                     for rhs, h, us in zip(sides, step, solved)]
        for rhs, blocks, us in zip(sides, inverse_of, solved):
            us.append(dot(blocks[r], rhs, dtype=f32))

    for h, us in zip(step, solved):
        U = jnp.concatenate(us, axis=0)                      # [c, d]
        k, G, S = h["k"], h["G"], h["S"]
        o = h["o"] + dot(jnp.where(lower, h["B"], 0.0), U)
        o_ref[:, h["lanes"]] = o.astype(o_ref.dtype)
        end = G[c - 1:c, :]                                  # [1, d]
        s_ref[h["a"]] = jnp.exp(end).reshape(d, 1) * S + dot(
            k * jnp.exp(end - G), U, tn)


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def _kda_call(q, k, v, g, beta, state, *, n_heads: int, interpret: bool):
    """q, k, v [S, H*d] bf16, g [S, H*d] float32 (a token's log-decays),
    beta [S, H] float32, state [H, d, d] float32; S whole chunks. The running
    sums of ``g`` inside a chunk are XLA's."""
    S, HD = q.shape
    H = n_heads
    d = HD // H
    hb = math.gcd(H, HEADS_A_STEP)
    n_chunks = S // CHUNK
    f32 = jnp.float32
    G = jnp.cumsum(g.astype(f32).reshape(n_chunks, CHUNK, HD),
                   axis=1).reshape(S, HD)
    # beta [H / hb, S, hb]: a head block's column a token.
    beta = beta.astype(f32).reshape(S, H // hb, hb).transpose(1, 0, 2)
    x_block = pl.BlockSpec((CHUNK, hb * d), lambda h, i: (i, h))
    s_block = pl.BlockSpec((hb, d, d), lambda h, i: (h, 0, 0))
    tokens = S * H
    o, s_out = pl.pallas_call(
        functools.partial(_kda_kernel, heads=hb, d=d),
        grid=(H // hb, n_chunks),
        in_specs=[x_block, x_block, x_block, x_block,
                  pl.BlockSpec((1, CHUNK, hb), lambda h, i: (h, i, 0)),
                  s_block],
        out_specs=[x_block, s_block],
        out_shape=[jax.ShapeDtypeStruct((S, HD), q.dtype),
                   jax.ShapeDtypeStruct((H, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=tokens * kernel_flops_per_token(d),
            bytes_accessed=kernel_bytes(S, H, d),
            transcendentals=tokens * d * (3 + CHUNK // SUB),
        ),
        name="kda_chunks",
        interpret=interpret,
    )(q, k, v, G, beta, state)
    return o, s_out


def kernel_flops_per_token(d: int) -> int:
    """Matmul FLOPs a token a head as the chunked form NEEDS them: the
    state read for queries and keys (``4 d^2``) and its update (``2 d^2``),
    the causal half of ``A`` and ``B`` over a chunk (``2 CHUNK d``), ``B U``
    (``CHUNK d``) and the triangular solve taken as one causal product
    (``CHUNK d``). The inversion's own small matmuls are how, not what."""
    return 6 * d * d + 4 * CHUNK * d


def kernel_bytes(n_tokens: int, n_heads: int, d: int) -> int:
    """q, k, v in and o out (bf16), the log-decays (float32), beta, the
    state in and out."""
    return n_tokens * n_heads * (4 * 2 * d + 4 * d + 4) + 8 * n_heads * d * d


def pallas_supported(d_head: int, n_tokens: int, lower_bound: float,
                     dtype) -> bool:
    """Shapes the kernel takes on the chip: lane-wide heads, whole chunks,
    bf16 operands, and a log-decay a token that a sub-block's float32 holds
    (``-lower_bound x SUB <= 80``)."""
    return bool(d_head == _LANES and n_tokens % CHUNK == 0
                and -float(lower_bound) * SUB <= _MAX_EXPONENT
                and jnp.dtype(dtype) == jnp.bfloat16)


@part("mixer")
def kda_chunks(
    q: jax.Array,          # [S, H*d]  L2-normalised a head, scaled
    k: jax.Array,          # [S, H*d]  L2-normalised a head
    v: jax.Array,          # [S, H*d]
    g: jax.Array,          # [S, H*d]  log-decay a key channel, float32, <= 0
    beta: jax.Array,       # [S, H]    in (0, 1), float32
    *,
    n_heads: int,
    lower_bound: float,
    initial_state: Optional[jax.Array] = None,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The delta rule over one document's segment → ``(o [S, H*d], state
    [H, d, d] float32)``. ``initial_state`` is what the call on the segment
    before returned (``None``: the document starts here). A segment that is
    not whole chunks is padded behind its last token with ``beta = 0`` and
    ``g = 0``: a token that writes nothing and forgets nothing."""
    S, HD = q.shape
    H = int(n_heads)
    d = HD // H
    state = zero_state(H, d) if initial_state is None else initial_state
    pad = -S % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    if pallas and pallas_supported(d, S + pad, lower_bound, q.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        o, state = _kda_call(q, k, v, g, beta, state, n_heads=H,
                             interpret=resolve_interpret(interpret))
    else:
        f32 = jnp.float32
        n = (S + pad) // CHUNK
        by_chunk = lambda a: a.astype(f32).reshape(n, CHUNK, H, -1)  # noqa: E731
        o, state = _kda_jnp(by_chunk(q), by_chunk(k), by_chunk(v),
                            by_chunk(g), beta.astype(f32).reshape(n, CHUNK, H),
                            state)
        o = o.reshape(S + pad, HD).astype(q.dtype)
    return (o[:S] if pad else o), state
