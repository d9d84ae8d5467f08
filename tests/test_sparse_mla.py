"""The ``sparse_mla`` mixer and the held share of an expert layer on the CPU
at tiny widths: the kernels (Pallas, interpret mode) against the same
arithmetic in ``jax.numpy``; the family through ``map_score_lm`` against the
benchmark's plain reference on documents LONGER than the selection and than
one segment program; the reference WITHOUT the selection (it must miss: the
check sees the mechanism) and WITH the served selection (what of a gap the
selection's near-ties carry); the shares of an expert layer against the uncut
layer; and that the family's longer ``LEAVES`` left the first mixer's weights
as they were.

Tolerance: ``dtype: float32`` here, so the op computes what the reference
computes in another order: 2e-5 nats a token (float32 reordering), 0.02 on a
block sum of 1,024 tokens, where both attend the same keys. A query whose
``index_topk``-th and next scores lie closer than that reordering may keep the
other key: one token's log-probability moves, by up to a few tenths."""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import grouped_ffn, sparse_mla
from agent_tpu.models import decoder_lm, moe
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("sparse_mla_lm")

# 16 index heads: a score is a sum of 16 rectified products, so an exact 0
# (every head negative), the one tie a tiny model can make, is a 2^-16 event.
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 4, "d_ff": 96,
        "n_layers": 2, "max_len": 163840, "mixer": "sparse_mla",
        "dtype": "float32", "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "index_n_heads": 16, "index_head_dim": 16, "index_topk": 16,
        "rope_theta": 10000.0, "rope_factor": 40.0,
        "rope_original_max_len": 4096, "n_dense_layers": 1, "n_experts": 16,
        "n_experts_held": 4, "expert_first": 0, "n_experts_per_token": 4,
        "n_expert_groups": 4, "n_groups_per_token": 2, "d_expert": 32,
        "n_shared_experts": 1, "routed_scale": 2.5}
REF_CFG = {**TINY, "rms_norm_eps": 1e-6}
TOKEN_TOL = 2e-5
BF16 = jnp.bfloat16

# Model code runs inside programs built ONCE (``tests/README.md``).
# The kernels' plain forms are eager ``jax.numpy``, a compile a primitive:
# one program a form and shape instead (the Pallas forms sit under a
# ``jax.jit`` of the package's own).
index_select = jax.jit(sparse_mla.index_select, static_argnums=4,
                       static_argnames=("pallas", "interpret"))
masked_attention = jax.jit(sparse_mla.masked_attention,
                           static_argnames=("pallas", "interpret"))
expand_latents = jax.jit(sparse_mla.expand_latents, static_argnums=3,
                         static_argnames=("pallas", "interpret"))


def _held_program(first, **opts):
    """``moe.held_experts_ffn`` of the experts from ``first`` as ONE program
    (its tables are thirty ``jax.numpy`` operations: eagerly, a compile
    each): ``(x, experts, gates, w_gate, w_up, w_down[, layer=])``. Built
    where it is called, so traced under the caller's patches."""
    return jax.jit(lambda x, experts, gates, *ws, **layer: (
        moe.held_experts_ffn(x, experts, gates, *ws, first, **layer, **opts)))


# ---- the kernels against the plain arithmetic -----------------------------

def _attention_operands(H, S, Lk, dr=64, D=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    qn = jax.random.normal(ks[3], (H, S, D), BF16) * 0.1
    qr = jax.random.normal(ks[4], (H, S, dr), BF16) * 0.1
    kn = jax.random.normal(ks[5], (H, Lk, D), BF16)
    kr = jax.random.normal(ks[6], (Lk, dr), BF16)
    v = jax.random.normal(ks[7], (H, Lk, D), BF16)
    return ks, (qn, qr, kn, kr, v)


def _mask_layout(dense):
    """[S, Lk] bool → the kernels' [Lk / 1024, S, 1024] int8."""
    S, Lk = dense.shape
    return dense.reshape(S, Lk // 1024, 1024).transpose(1, 0, 2).astype(
        np.int8)


# Queries, pos0, cache keys, heads, kept keys, and whether the segment's last
# 128 rows keep nothing but keys of the cache's last 128 they can see. A
# segment of 512 is one query tile a grid step, one of 1,024 a whole step.
KERNEL_CASES = {
    "first_segment": (512, 0, 1024, 4, 64, False),
    "second_segment": (512, 512, 1024, 4, 64, False),
    # Four key tiles; the query tile's last key tile is half causal, the one
    # before it whole, the two after it never fetched.
    "half_causal_last_tile": (512, 1536, 4096, 4, 64, False),
    # Every sub-block and tile before a row's first kept key is fully
    # masked: the row carries exp(0) a key until that key's alpha of 0.
    "kept_keys_in_the_last_sub_block": (512, 1536, 4096, 4, 64, True),
    "two_head_groups": (512, 512, 1024, 8, 64, False),
    "fewer_kept_than_a_sub_block": (512, 1536, 4096, 4, 16, False),
    "every_key_tile": (512, 3584, 4096, 4, 200, False),
    "a_whole_step_of_queries": (1024, 1536, 4096, 4, 64, False),
    "a_whole_step_kept_keys_late": (1024, 1024, 4096, 4, 64, True),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kernels_equal_the_plain_arithmetic(case):
    """One segment of 512 or 1,024 tokens against a cache of one or four
    1,024-key tiles at lane-wide heads, from a document's first segment (half
    the cache not yet there) to its last: the selection key for key, the
    attention to bf16 rounding."""
    S, pos0, Lk, H, topk, late = KERNEL_CASES[case]
    Hi, D = 16, 128
    ks, operands = _attention_operands(H, S, Lk)
    qi = jax.random.normal(ks[0], (S, Hi, D), BF16)
    w = jax.random.normal(ks[1], (S, Hi), jnp.float32)
    ki = jax.random.normal(ks[2], (Lk, D), BF16)
    p = jnp.int32(pos0)
    assert sparse_mla.index_supported(S, Lk, Hi, D, BF16)
    assert sparse_mla.attention_supported(S, Lk, H, D, D, BF16)
    kernel = np.asarray(index_select(qi, w, ki, p, topk, pallas=True,
                                     interpret=True))
    plain = np.asarray(index_select(qi, w, ki, p, topk, pallas=False))
    tiles = Lk // 1024
    assert kernel.shape == (tiles, S, 1024) and kernel.dtype == np.int8
    np.testing.assert_array_equal(kernel, plain)
    dense = plain.transpose(1, 0, 2).reshape(S, Lk)
    t = pos0 + np.arange(S)
    np.testing.assert_array_equal(dense.sum(axis=1), np.minimum(t + 1, topk))
    assert not dense[np.arange(Lk)[None, :] > t[:, None]].any()

    if late:
        key = np.arange(Lk)[None, :]
        dense = dense.copy()
        dense[S - 128:] = ((key >= pos0 + S - 128)
                           & (key <= t[S - 128:, None]))
        assert dense[S - 128:, :pos0 + S - 128].sum() == 0
        plain = _mask_layout(dense)
    args = (*operands, jnp.asarray(plain), p)
    got = masked_attention(*args, pallas=True, interpret=True)
    want = masked_attention(*args, pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("S", [512, 1024])
def test_the_attention_never_reads_past_the_segments_last_key(S):
    """Keys, rotary keys and values at and after ``pos0 + S`` hold NaN (the
    expansion does not write them) and the mask there says "kept": the
    output is finite, and bit for bit what clean operands give."""
    pos0, Lk, H = 2048 - S, 4096, 4
    _, (qn, qr, kn, kr, v) = _attention_operands(H, S, Lk, seed=3)
    key = np.arange(Lk)
    dense = (key[None, :] <= pos0 + np.arange(S)[:, None]) & (
        (key[None, :] * 7 + np.arange(S)[:, None]) % 11 == 0)
    mask = _mask_layout(dense)
    p = jnp.int32(pos0)
    clean = masked_attention(qn, qr, kn, kr, v, jnp.asarray(mask), p,
                             pallas=True, interpret=True)
    end = pos0 + S
    poisoned = mask.copy()
    poisoned[end // 1024:] = 1
    got = masked_attention(
        qn, qr, kn.at[:, end:].set(jnp.nan), kr.at[end:].set(jnp.nan),
        v.at[:, end:].set(jnp.nan), jnp.asarray(poisoned), p,
        pallas=True, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    want = masked_attention(qn, qr, kn, kr, v, jnp.asarray(mask), p,
                            pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("n_keys", [512, 700, 2048])
def test_expansion_writes_the_keys_a_segment_can_see(n_keys):
    """Per-head keys and values of the first ``n_keys`` latents (whole
    1,024-key tiles of them) equal the plain einsum's; what lies past them is
    the kernel's not to write."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    c = jax.random.normal(ks[0], (2048, 512), BF16)
    w = (jax.random.normal(ks[1], (8, 512, 256)) / 22.0).astype(BF16)
    k, v = expand_latents(c, w, jnp.int32(n_keys), 128, pallas=True,
                          interpret=True)
    k_plain, v_plain = expand_latents(c, w, jnp.int32(n_keys), 128,
                                      pallas=False)
    assert k.shape == v.shape == (8, 2048, 128) and k.dtype == BF16
    seen = -(-n_keys // 1024) * 1024
    for got, want in ((k, k_plain), (v, v_plain)):
        np.testing.assert_allclose(np.asarray(got[:, :seen], np.float32),
                                   np.asarray(want[:, :seen], np.float32),
                                   atol=2e-2)


def test_shapes_off_the_kernels_take_the_plain_path():
    index, attend = sparse_mla.index_supported, sparse_mla.attention_supported
    assert index(4096, 32768, 64, 128, BF16)
    assert attend(4096, 32768, 128, 128, 128, BF16)
    assert not index(4096, 32768, 64, 128, jnp.float32)
    assert not index(4096, 65536, 64, 128, BF16)         # keys past VMEM
    assert not attend(4096, 65536, 128, 128, 128, BF16)
    assert not index(4096, 32768, 64, 64, BF16)
    assert not attend(4096, 32768, 128, 64, 128, BF16)
    assert not attend(4000, 32768, 128, 128, 128, BF16)
    assert sparse_mla.key_tile(256) == 256 and sparse_mla.key_tile(32768) == 1024


@pytest.mark.parametrize("first", [0, 4, 28])
def test_grouped_matmul_equals_every_expert_on_every_token(monkeypatch, first):
    """The sorted, tile-padded grouped matmul (interpret mode, 64-row tiles
    so that experts span tiles and leave tails) against the held experts
    applied densely; the pair count is the router's."""
    monkeypatch.setattr(grouped_ffn, "ROW_TILE", 64)
    S, d, fe, E, held, k = 512, 256, 256, 32, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (S, d), BF16)
    experts, gates = moe.route_sigmoid_grouped(
        jax.random.normal(ks[1], (S, E), jnp.float32), jnp.zeros(E),
        n_groups=4, groups_kept=2, top_k=k, scale=2.5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    group = np.asarray(experts) // (E // 4)
    assert all(len(set(row)) <= 2 for row in group)          # 2 groups kept
    ws = [(jax.random.normal(key, shape) / np.sqrt(shape[1])).astype(BF16)
          for key, shape in zip(ks[2:], [(held, d, fe), (held, d, fe),
                                         (held, fe, d)])]
    plain, n_plain = _held_program(first, pallas=False)(x, experts, gates,
                                                        *ws)
    kernel, n_kernel = _held_program(first, pallas=True, interpret=True)(
        x, experts, gates, *ws)
    in_share = (np.asarray(experts) >= first) & (np.asarray(experts) < first + held)
    assert int(n_plain) == int(n_kernel) == int(in_share.sum()) > 0
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                               atol=3e-2)


def _rows_in_place(x, tile_expert, n_tiles, *weights, tm=64):
    """The grouped kernel on rows that already lie sorted and tile-padded:
    row ``r`` reads row ``r`` of ``x`` and writes row ``r`` of the result
    (what the layer gathered in XLA before the kernel addressed its rows)."""
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    tile_rows = jnp.where(jnp.arange(tile_expert.size) < n_tiles, tm, 0)
    return grouped_ffn.unpack_rows(grouped_ffn.grouped_swiglu(
        x, rows, rows, tile_expert, tile_rows, *weights,
        n_slots=x.shape[0], interpret=True))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_grouped_matmul_reads_a_layer_of_the_stack_in_place(layer):
    """The kernel on the layers' stack ``[3, E, ...]`` with the layer's number
    (interpret mode, 64-row tiles, the last tile idle) is, bit for bit, the
    kernel on that layer's own slice: one more block index, no arithmetic."""
    L, E, d, fe, tm = 3, 4, 256, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    stacks = [(jax.random.normal(key, shape) / np.sqrt(shape[2])).astype(BF16)
              for key, shape in zip(ks[1:], [(L, E, d, fe), (L, E, d, fe),
                                             (L, E, fe, d)])]
    tile_expert = jnp.array([0, 0, 1, 3, 3, 3, 3], jnp.int32)
    x = jax.random.normal(ks[0], (tile_expert.size * tm, d), BF16)
    whole = _rows_in_place(x, tile_expert, 6, *stacks, jnp.int32(layer))
    sliced = _rows_in_place(x, tile_expert, 6, *(w[layer] for w in stacks))
    written = 6 * tm
    assert float(jnp.abs(sliced[:written].astype(jnp.float32)).max()) > 0.1
    np.testing.assert_array_equal(np.asarray(whole[:written]),
                                  np.asarray(sliced[:written]))
    if layer:                 # and it is THAT layer's weights it read
        first = _rows_in_place(x, tile_expert, 6, *stacks)
        assert not np.array_equal(np.asarray(whole[:written]),
                                  np.asarray(first[:written]))


@functools.lru_cache(maxsize=None)
def _routed(form, S, E, k):
    logits = jax.random.normal(jax.random.PRNGKey(3), (S, E), jnp.float32)
    if form == "softmax":
        return moe.route_softmax(logits, top_k=k, scale=1.0)
    return moe.route_sigmoid_grouped(logits, jnp.zeros(E), n_groups=8,
                                     groups_kept=4, top_k=k, scale=2.5)


def _by_hand(S, k, counts, first):
    """Pairs dealt by hand: ``counts[e]`` of them to held expert ``e`` (at
    most one a token), the rest to an expert held elsewhere."""
    experts = np.full((S, k), first + len(counts), np.int32)
    at = 0
    for e, n in enumerate(counts):
        for i in range(n):
            experts[(at + i) % S, (at + i) // S] = first + e
        at += n
    gates = jax.random.uniform(jax.random.PRNGKey(4), (S, k), jnp.float32,
                               0.1, 1.0)
    return jnp.asarray(experts), gates


# name: (the router's pairs, k, held experts, the first of them)
ROWS_HELD_CASES = {
    # the two cells' routing forms at their own k and held counts
    "sigmoid_8_of_16_held": (lambda: _routed("sigmoid", 256, 64, 8), 16, 0),
    "sigmoid_first_not_0": (lambda: _routed("sigmoid", 256, 64, 8), 16, 32),
    "softmax_4_of_32_held": (lambda: _routed("softmax", 256, 128, 4), 32, 0),
    "softmax_first_not_0": (lambda: _routed("softmax", 256, 128, 4), 32, 64),
    # an expert with no row, with exactly one tile, with one row over a tile
    "no_row_one_tile_one_over": (
        lambda: _by_hand(256, 2, [0, 64, 65, 3], 8), 4, 8),
    # the worst case the shape is fixed for: every pair to ONE held expert
    "every_pair_to_one_expert": (
        lambda: (jnp.full((128, 4), 5, jnp.int32), jax.random.uniform(
            jax.random.PRNGKey(5), (128, 4), jnp.float32, 0.05, 0.5)), 4, 4),
    # no pair held at all: no tile holds a row
    "no_pair_held": (lambda: _by_hand(128, 4, [0, 0, 0, 0], 8), 4, 8),
}


@pytest.mark.parametrize("fe", [512, 896], ids=["two_width_steps",
                                                "one_width_step"])
@pytest.mark.parametrize("case", ROWS_HELD_CASES)
def test_the_expert_layer_moves_the_rows_it_holds(monkeypatch, case, fe):
    """``held_experts_ffn`` on the kernel path (interpret mode, 64-row
    tiles): the kernel fetches the real rows of every tile from ``x`` by its
    own table and writes them to slot ``j * S + token``; the combine reads
    the slots. Every slot the kernel did NOT write is poisoned with NaNs
    before the combine reads it: nothing unwritten reaches the result.
    Against ``_held_dense`` within the file's tolerance, and every pair's row
    bit for bit the row of the parent's form: the rows gathered in XLA, the
    kernel run in place on them, the rows gathered back by their place. At
    a width walked in two steps (the rows' copies in rolled loops, a share a
    step) and in ONE (PR 45: the copies static, under the tile's matmuls):
    with a share of the experts held elsewhere the tiles are mostly half
    empty and most slots are never written."""
    monkeypatch.setattr(grouped_ffn, "ROW_TILE", 64)
    route, n_held, first = ROWS_HELD_CASES[case]
    experts, gates = route()
    S, k = experts.shape
    d, tm = 256, 64
    assert grouped_ffn.width_step(fe) == (fe if fe == 896 else 256)
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (S, d), BF16)
    ws = [(jax.random.normal(key, shape) / np.sqrt(shape[1])).astype(BF16)
          for key, shape in zip(ks[1:], [(n_held, d, fe), (n_held, d, fe),
                                         (n_held, fe, d)])]
    grouped_swiglu, seen = grouped_ffn.grouped_swiglu, {}

    def poisoned(x, token, slot, tile_expert, tile_rows, *rest, **kwargs):
        words = grouped_swiglu(x, token, slot, tile_expert, tile_rows, *rest,
                               **kwargs)
        real = (jnp.arange(token.size) % tm) < jnp.repeat(tile_rows, tm)
        written = jnp.zeros(words.shape[0], bool).at[
            jnp.where(real, slot, words.shape[0])].set(True, mode="drop")
        seen.update(token=token, slot=slot, tile_expert=tile_expert,
                    tile_rows=tile_rows, real=real, written=written,
                    rows=grouped_ffn.unpack_rows(words))
        return jnp.where(written[:, None, None], words,
                         jnp.uint32(0x7FC07FC0))

    monkeypatch.setattr(grouped_ffn, "grouped_swiglu", poisoned)

    def layer(x, experts, gates, *ws):
        y, pairs = moe.held_experts_ffn(x, experts, gates, *ws, first,
                                        pallas=True, interpret=True)
        return y, pairs, dict(seen)     # what the spy saw, as results

    kernel, n_kernel, tables = jax.jit(layer)(x, experts, gates, *ws)
    plain, n_plain = _held_program(first, pallas=False)(x, experts, gates,
                                                        *ws)
    held = (np.asarray(experts) >= first) & (np.asarray(experts)
                                             < first + n_held)
    assert int(n_kernel) == int(n_plain) == int(held.sum())
    assert int(tables["written"].sum()) == int(held.sum())   # each slot once
    assert tables["tile_rows"].size == S * k // tm + n_held  # the worst case
    assert np.isfinite(np.asarray(kernel)).all()
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                               atol=3e-2)
    if not held.any():
        assert int((tables["tile_rows"] > 0).sum()) == 0
        np.testing.assert_array_equal(np.asarray(kernel), 0.0)
        return
    assert float(jnp.abs(kernel).max()) > 0.05
    # The parent's form on the same sorted order (the call passes the spy
    # too: ``tables`` is what the layer's own call showed).
    real, slots = np.asarray(tables["real"]), np.asarray(tables["slot"])
    x_rows = x[jnp.where(tables["real"], tables["token"], 0)]
    y_rows = _rows_in_place(x_rows, tables["tile_expert"],
                            int((tables["tile_rows"] > 0).sum()), *ws)
    np.testing.assert_array_equal(np.asarray(tables["rows"])[slots[real]],
                                  np.asarray(y_rows)[real])
    # ... and that slot IS the pair's: row j * S + token, expert by expert.
    token, j = slots[real] % S, slots[real] // S
    np.testing.assert_array_equal(token, np.asarray(tables["token"])[real])
    np.testing.assert_array_equal(
        np.asarray(experts)[token, j] - first,
        np.repeat(np.asarray(tables["tile_expert"]), tm)[real])


def _parents_ffn_kernel(token_ref, slot_ref, tile_expert_ref, tile_rows_ref,
                        n_tiles_ref, layer_ref, x_hbm, wg_ref, wu_ref, wd_ref,
                        y_hbm, rows_in, x_ref, acc_ref, rows_out, sem):
    """``kernels/grouped_ffn.py: _ffn_kernel`` as PR 42 left it, to the
    letter: one wait a ROW, every copy in a rolled loop, a tile's write-back
    at its own tail."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    t, f = pl.program_id(0), pl.program_id(1)
    n_f, n_tiles = pl.num_programs(1), n_tiles_ref[0]
    tm, d = x_ref.shape
    nn = (((1,), (0,)), ((), ()))
    OUT = 2

    def fetch(tile, lo, hi):
        def one(j, carry):
            pltpu.make_async_copy(x_hbm.at[token_ref[tile * tm + j]],
                                  rows_in.at[tile % 2, j],
                                  sem.at[tile % 2]).start()
            return carry
        jax.lax.fori_loop(lo, hi, one, 0)

    def wait(n, s):
        def one(j, carry):
            pltpu.make_async_copy(rows_out.at[0], rows_out.at[0],
                                  sem.at[s]).wait()
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(t < n_tiles)
    def _():
        @pl.when(f == 0)
        def _():
            @pl.when(t == 0)
            def _():
                fetch(0, 0, tile_rows_ref[0])

            wait(tile_rows_ref[t], t % 2)
            low, high = grouped_ffn._halves(rows_in[t % 2, :, 0, :])
            x_ref[:, :d // 2] = low.astype(x_ref.dtype)
            x_ref[:, d // 2:] = high.astype(x_ref.dtype)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        @pl.when(t + 1 < n_tiles)
        def _():
            share = -(-tm // n_f)
            fetch(t + 1, f * share,
                  jnp.minimum((f + 1) * share, tile_rows_ref[t + 1]))

        x = x_ref[...]
        gate = jax.lax.dot_general(x, wg_ref[0, 0], nn,
                                   preferred_element_type=f32)
        up = jax.lax.dot_general(x, wu_ref[0, 0], nn,
                                 preferred_element_type=f32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(h, wd_ref[0, 0], nn,
                                            preferred_element_type=f32)

        @pl.when(f == n_f - 1)
        def _():
            @pl.when(t > 0)
            def _():
                wait(tile_rows_ref[t - 1], OUT)

            y = acc_ref[...].astype(x_ref.dtype).astype(f32)
            rows_out[:, 0, :] = grouped_ffn._words(y[:, :d // 2],
                                                   y[:, d // 2:])

            def one(j, carry):
                pltpu.make_async_copy(rows_out.at[j],
                                      y_hbm.at[slot_ref[t * tm + j]],
                                      sem.at[OUT]).start()
                return carry
            jax.lax.fori_loop(0, tile_rows_ref[t], one, 0)

            @pl.when(t == n_tiles - 1)
            def _():
                wait(tile_rows_ref[t], OUT)


def _parents_grouped_swiglu(x, token, slot, tile_expert, tile_rows, w_gate,
                            w_up, w_down, layer=0, limit=None, *, n_slots,
                            interpret):
    """``grouped_swiglu``'s own call as PR 42 left it, around the kernel
    above (no clamp then: ``limit`` is taken and has to be ``None``)."""
    assert limit is None
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, d = x.shape
    fe = w_gate.shape[-1]
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    tile_rows = i32(tile_rows)
    tm = token.shape[0] // tile_rows.shape[0]
    tf = grouped_ffn.width_step(fe)
    n_f = fe // tf
    if w_gate.ndim == 3:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
    n_tiles = (tile_rows > 0).sum(dtype=jnp.int32)

    def tile(t, n):
        return jnp.minimum(t, jnp.maximum(n[0] - 1, 0))

    def width(t, f, n):
        return jnp.where(t < n[0], f, n_f - 1)

    def w_in(t, f, tok, sl, te, tr, n, ly):
        return ly[0], te[tile(t, n)], 0, width(t, f, n)

    def w_out(t, f, tok, sl, te, tr, n, ly):
        return ly[0], te[tile(t, n)], width(t, f, n), 0

    return pl.pallas_call(
        _parents_ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(tile_rows.shape[0], n_f),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, 1, d, tf), w_in),
                      pl.BlockSpec((1, 1, d, tf), w_in),
                      pl.BlockSpec((1, 1, tf, d), w_out)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tm, 1, d // 2), jnp.uint32),
                            pltpu.VMEM((tm, d), x.dtype),
                            pltpu.VMEM((tm, d), jnp.float32),
                            pltpu.VMEM((tm, 1, d // 2), jnp.uint32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, d // 2), jnp.uint32),
        name="moe_grouped_swiglu",
        interpret=interpret,
    )(i32(token), i32(slot), i32(tile_expert), tile_rows, n_tiles.reshape(1),
      i32(layer).reshape(1),
      grouped_ffn._pack_rows(x, interpret).reshape(S, 1, d // 2), w_gate,
      w_up, w_down)


def _layer_around(monkeypatch, kernel, first):
    """``held_experts_ffn`` around one grouped kernel, as ONE program:
    ``(y, pairs, the kernel's tile_rows, its words, which slots it wrote)``;
    every slot the kernel did not write is poisoned with NaNs before the
    combine reads it."""
    def run(x, experts, gates, *ws, **opts):
        seen = {}

        def spy(x, token, slot, tile_expert, tile_rows, *rest, **kwargs):
            words = kernel(x, token, slot, tile_expert, tile_rows, *rest,
                           **kwargs)
            tm = token.size // tile_rows.size
            real = (jnp.arange(token.size) % tm) < jnp.repeat(tile_rows, tm)
            written = jnp.zeros(words.shape[0], bool).at[
                jnp.where(real, slot, words.shape[0])].set(True, mode="drop")
            seen.update(tile_rows=tile_rows, words=words, written=written)
            return jnp.where(written[:, None, None], words,
                             jnp.uint32(0x7FC07FC0))

        with monkeypatch.context() as traced:
            traced.setattr(grouped_ffn, "grouped_swiglu", spy)
            y, pairs = moe.held_experts_ffn(
                x, experts, gates, *ws, first, pallas=True, interpret=True,
                **opts)
        return y, pairs, seen["tile_rows"], seen["words"], seen["written"]
    return jax.jit(run)


@pytest.mark.parametrize("stack", [False, True],
                         ids=["one_layer", "stack_in_place"])
@pytest.mark.parametrize("fe", [896, 512], ids=["one_width_step",
                                                "two_width_steps"])
def test_the_layer_alone_returns_the_parents_bytes(monkeypatch, fe, stack):
    """PR 45 changed WHEN a tile's rows move (waits by size; at one width
    step every copy a static descriptor under the matmuls, a tile's
    write-back under the next tile's), not what is computed: the layer alone
    on the kernel path (interpret mode, 64-row tiles) returns, bit for bit,
    what it returns around the parent's kernel, on tables whose tiles hold 1
    row, an odd count, a tile less one, whole tiles and one row over, an
    expert with none, and idle tiles behind the last; so do the kernel's own
    words, every slot of them: a padded row wrote nothing."""
    monkeypatch.setattr(grouped_ffn, "ROW_TILE", 64)
    S, k, d, n_held, first = 256, 2, 512, 6, 8     # two lane tiles a half row
    experts, gates = _by_hand(S, k, [0, 1, 37, 63, 64, 65], first)
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(ks[0], (S, d), BF16)
    lead = (3,) if stack else ()
    ws = [(jax.random.normal(key, lead + shape) / np.sqrt(shape[1])).astype(
        BF16) for key, shape in zip(ks[1:], [(n_held, d, fe), (n_held, d, fe),
                                             (n_held, fe, d)])]
    layer = {"layer": jnp.int32(2)} if stack else {}

    change, pairs, tile_rows, words, _ = _layer_around(
        monkeypatch, grouped_ffn.grouped_swiglu, first)(
            x, experts, gates, *ws, **layer)
    parents = _layer_around(monkeypatch, _parents_grouped_swiglu, first)
    parent, _, _, parents_words, _ = parents(x, experts, gates, *ws, **layer)
    assert int(pairs) == 230
    tile_rows = np.asarray(tile_rows)
    assert sorted(tile_rows[tile_rows > 0]) == [1, 1, 37, 63, 64, 64]
    assert (tile_rows[6:] == 0).all() and tile_rows.size == S * k // 64 + 6
    assert float(jnp.abs(change).max()) > 0.05
    np.testing.assert_array_equal(np.asarray(words), np.asarray(parents_words))
    np.testing.assert_array_equal(np.asarray(change), np.asarray(parent))
    if stack:                 # and it is THAT layer's weights it read
        other = parents(x, experts, gates, *ws, layer=jnp.int32(0))[0]
        assert not np.array_equal(np.asarray(other), np.asarray(parent))


# Rows an expert holds: none, a row, a sub-block less one, a sub-block, one
# row over, a tile less one, a tile, and a tile and a row (a spill tile).
SUB_BLOCK_ROWS = [0, 1, 127, 128, 129, 255, 256, 257]


@pytest.mark.parametrize("stack", [False, True],
                         ids=["one_layer", "stack_in_place"])
@pytest.mark.parametrize("fe, limit", [(896, None), (768, 0.5)],
                         ids=["one_width_step", "three_width_steps_clamped"])
def test_a_tile_computes_the_sub_blocks_that_hold_rows(monkeypatch, fe, limit,
                                                       stack):
    """PR 51: a tile's unpack, matmuls and pack run over its ``SUB_ROWS``
    sub-blocks that hold a real row, not over the tile. The layer alone on
    the kernel path (interpret mode, tiles of 256 rows as on the chip) on
    tables whose tiles hold 1, 127 and 128 rows (one sub-block), 129 and 255
    (two), 256 (a full tile), a full tile and a spill tile of one row, and an
    expert with none: the kernel's words, every slot of them, and the
    layer's result EQUAL to the bit what the kernel with the sub-block set to
    the whole tile gives (the parent's body: every row of a tile computed),
    with every slot neither wrote poisoned before the combine reads it. At a
    width walked in one step and in three under the clamp (ling's form), one
    layer and a stack read in place."""
    tm, sub = grouped_ffn.ROW_TILE, grouped_ffn.SUB_ROWS
    assert (tm, sub) == (256, 128)
    S, k, d, first = 512, 3, 512, 8                # two lane tiles a half row
    n_held = len(SUB_BLOCK_ROWS)
    experts, gates = _by_hand(S, k, SUB_BLOCK_ROWS, first)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    x = jax.random.normal(ks[0], (S, d), BF16)
    lead = (3,) if stack else ()
    ws = [(jax.random.normal(key, lead + shape) / np.sqrt(shape[1])).astype(
        BF16) for key, shape in zip(ks[1:], [(n_held, d, fe), (n_held, d, fe),
                                             (n_held, fe, d)])]
    opts = {"layer": jnp.int32(2)} if stack else {}
    if limit is not None:
        opts["limit"] = jnp.float32(limit)
    grouped_swiglu = grouped_ffn.grouped_swiglu

    def whole_tiles(*args, **kwargs):
        """The kernel traced with one sub-block a tile (under the jit's own
        cache a second trace of the same shapes would not happen)."""
        with monkeypatch.context() as traced:
            traced.setattr(grouped_ffn, "SUB_ROWS", tm)
            return grouped_swiglu.__wrapped__(*args, **kwargs)

    change, pairs, tile_rows, words, written = _layer_around(
        monkeypatch, grouped_swiglu, first)(x, experts, gates, *ws, **opts)
    whole, _, _, whole_words, _ = _layer_around(
        monkeypatch, whole_tiles, first)(x, experts, gates, *ws, **opts)
    assert int(pairs) == int(written.sum()) == sum(SUB_BLOCK_ROWS)
    tile_rows = np.asarray(tile_rows)
    assert list(tile_rows[:9]) == [1, 127, 128, 129, 255, 256, 256, 1, 0]
    assert tile_rows.size == S * k // tm + n_held
    assert np.isfinite(np.asarray(change)).all()
    assert float(jnp.abs(change).max()) > 0.05
    np.testing.assert_array_equal(np.asarray(words), np.asarray(whole_words))
    np.testing.assert_array_equal(np.asarray(change), np.asarray(whole))
    # ... and what the counter says the matmuls took, on the same tables.
    work = moe.held_work(experts, first, n_held)
    assert int(work["visited"]) == 8
    assert int(work["rows"]) == 128 * 3 + 256 * 3 + (256 + 128) == 1536
    with monkeypatch.context() as whole_tile:
        whole_tile.setattr(grouped_ffn, "SUB_ROWS", tm)
        assert int(moe.held_work(experts, first, n_held)["rows"]) == 8 * tm


# Two expert layers behind one dense: the layer scan has a second step to
# get wrong. ``kernel``: widths the grouped matmul takes (the mixer's stay off
# their kernels), so the stack reaches the ``pallas_call`` itself.
TWO_EXPERT_LAYERS = {**TINY, "n_layers": 3}
STACK_CASES = {
    # name: (config, kernel options, how the expert leaves are stored,
    #        whether the scan leaves them whole)
    "plain_arithmetic": (TWO_EXPERT_LAYERS, {}, "as_drawn", True),
    "kernel": ({**TWO_EXPERT_LAYERS, "dtype": "bfloat16", "d_model": 256,
                "d_expert": 256}, {"pallas": True, "interpret": True},
               "as_drawn", True),
    "stored_in_another_dtype": ({**TWO_EXPERT_LAYERS, "dtype": "bfloat16"},
                                {}, "float32", False),
    "int8_table": ({**TWO_EXPERT_LAYERS, "dtype": "bfloat16"}, {}, "int8",
                   False),
}


@pytest.mark.parametrize("case", STACK_CASES)
def test_the_layer_scan_reads_the_expert_stack_in_place(monkeypatch, case):
    """``forward_segment`` with the held experts' leaves left whole beside
    the scan, against the same leaves scanned a layer's slice at a time (what
    it did before, and still does for a leaf it has to cast or dequantize):
    hidden states, state and ``pairs`` equal bit for bit. An int8 table and a
    leaf stored in another dtype take the sliced path by themselves; the
    int8 tree agrees with its own dequantized stacks read in place."""
    from agent_tpu.models.quant import quantize_for_family

    monkeypatch.setattr(grouped_ffn, "ROW_TILE", 64)
    config, opts, stored, in_place = STACK_CASES[case]
    cfg = decoder_lm.DecoderLMConfig(**config)
    dtype = cfg.compute_dtype
    params = lm_once.params(cfg, "sparse-stack")
    if stored == "int8":
        params = quantize_for_family("decoder_lm", params, "int8")
    elif stored == "float32":
        params["expert_layers"] = {
            k: v.astype(jnp.float32) if k in decoder_lm.EXPERT_LEAVES else v
            for k, v in params["expert_layers"].items()}
    ids = np.random.default_rng(6).integers(0, 3000, (1, 256)).astype(np.int32)
    layers_seen = []
    held_experts_ffn = moe.held_experts_ffn

    def spy(*args, layer=None, **kwargs):
        layers_seen.append((layer is not None, args[3].ndim))
        return held_experts_ffn(*args, layer=layer, **kwargs)

    monkeypatch.setattr(moe, "held_experts_ffn", spy)

    def run(tree):
        # Its own ``jax.jit`` a call: each is traced under another patch (or
        # another tree) and the spy has to see the trace.
        del layers_seen[:]
        hidden, state = jax.jit(lambda p, i, s: decoder_lm.forward_segment(
            p, i, jnp.int32(0), s, cfg, **opts))(
                tree, ids, lm_once.state(cfg, 1, 256))
        return jax.tree_util.tree_leaves((hidden, state)), list(layers_seen)

    got, seen = run(params)
    assert seen == [(in_place, 4 if in_place else 3)], seen   # one scan body
    with monkeypatch.context() as scanned:
        scanned.setattr(decoder_lm, "_read_in_place",
                        lambda leaves, dtype: (leaves, {}))
        want, seen = run(params)
    assert seen == [(False, 3)], seen
    assert float(want[-1]) > 0, "no pair was routed to the held experts"
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if stored == "int8":
        plain = {k: decoder_lm._plain_weights(v, dtype)
                 if k in decoder_lm.EXPERT_LEAVES else v
                 for k, v in params["expert_layers"].items()}
        whole, seen = run({**params, "expert_layers": plain})
        assert seen == [(True, 4)], seen
        for a, b in zip(whole, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the family against the reference -------------------------------------

LONG = 4200             # 2,048 + 2,048 + 1,024 program tokens under BUCKETS
# The op's segment sizes, halved for the CPU: three segment programs a
# document at a quarter of the causal pairs (the 4,096-token segment compiles
# for the chip in ``tests/test_tpu_compile.py`` and runs in the benchmark's
# rehearsal of ``brumby-14b-base``).
BUCKETS = (1024, 2048)


def _short_segments() -> pytest.MonkeyPatch:
    from agent_tpu.ops import map_score_lm

    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    return mp


@pytest.fixture(scope="module")
def served():
    """One document of three segments and a short one through
    ``map_score_lm``: ``(documents, result)``."""
    reset_runtime()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in (LONG, 37)]
    mp = _short_segments()
    try:
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs], "model_config": TINY,
            "model_path": "sparse-a"})
    finally:
        mp.undo()
    reset_runtime()
    assert out["ok"] is True, out
    return docs, out


@pytest.fixture(scope="module")
def selection(served):
    """The long document once more through the family's own functions, segment
    by segment as the op runs it, with every selection the program makes
    kept aside: ``(block sums, one boolean [L, L] array a layer)``."""
    from agent_tpu.ops.map_score_lm import _stage_document

    cfg = decoder_lm.DecoderLMConfig(**TINY)
    doc = served[0][0]
    kept = []
    real = sparse_mla.index_select

    def recording(qi, w, ki, pos0, topk, **kw):
        mask = real(qi, w, ki, pos0, topk, **kw)
        jax.debug.callback(lambda m, p: kept.append(
            (int(p), np.asarray(m))), mask, pos0, ordered=True)
        return mask

    params = lm_once.params(cfg, "sparse-a")
    mp = _short_segments()
    try:
        segments = _stage_document(doc)["segments"]
    finally:
        mp.undo()
    padded = sum(seg[0].shape[1] for seg in segments)
    state = lm_once.state(cfg, 1, padded)
    sums = []
    mp = pytest.MonkeyPatch()
    mp.setattr(sparse_mla, "index_select", recording)
    # Traced under the patch, so its own; built once: a program a bucket.
    step = jax.jit(lambda p, i, at, st: decoder_lm.forward_segment(
        p, i, at, st, cfg))
    try:
        for ids, targets, n_valid, pos0 in segments:
            hidden, state = step(params, ids, jnp.int32(pos0), state)
            sums.append(np.asarray(lm_once.segment_block_sums(
                hidden, params["head"], jnp.asarray(targets),
                jnp.int32(n_valid))))
        jax.effects_barrier()
    finally:
        mp.undo()
    n_layers = cfg.n_layers
    assert len(kept) == len(segments) * n_layers  # a call a layer a segment
    keep = [np.zeros((padded, padded), bool) for _ in range(n_layers)]
    for call, (pos0, mask) in enumerate(kept):
        rows = mask.transpose(1, 0, 2).reshape(mask.shape[1], padded) != 0
        keep[call % n_layers][pos0:pos0 + len(rows)] = rows
    return np.concatenate(sums), [k[:LONG, :LONG] for k in keep]


def _gaps(result, logprobs, docs):
    return [np.abs(np.asarray(blocks) - ref.block_sums(lp))
            for blocks, lp, _ in zip(result["block_logprob_sums"], logprobs, docs)]


def test_documents_longer_than_the_selection_match_the_reference(
        served, selection):
    docs, out = served
    sums, keep = selection
    assert out["n_tokens"] == [LONG, 37]
    assert [len(b) for b in out["block_logprob_sums"]] == [5, 1]
    # The op's programs and the family's functions are one computation.
    np.testing.assert_allclose(out["block_logprob_sums"][0], sums[:5],
                               atol=1e-3)
    # Every query past the first 16 selected: 16 of up to 4,200 keys.
    assert all(k.sum(axis=1).max() == 16 and k.sum() < 16 * LONG for k in keep)
    want = ref.token_logprobs(REF_CFG, "sparse-a", docs)
    long_gap, short_gap = _gaps(out, want, docs)
    assert short_gap.max() < TOKEN_TOL * 37
    # The reference's own selection: blocks agree but where a near-tie fell
    # the other way; no block is off by what a wrong mechanism would give.
    assert np.median(long_gap) < TOKEN_TOL * 1024
    assert long_gap.max() < 0.5
    # Given the selection the program made, the reference agrees everywhere:
    # all of the gap above is the selection's near-ties.
    given = ref.token_logprobs(REF_CFG, "sparse-a", docs[:1], attend=keep)
    np.testing.assert_allclose(out["block_logprob_sums"][0],
                               ref.block_sums(given[0]),
                               atol=TOKEN_TOL * 1024)


def test_the_reference_without_the_selection_misses_by_ten_tolerances(served):
    docs, out = served
    causal = ref.token_logprobs(REF_CFG, "sparse-a", docs, attend="causal")
    long_gap, short_gap = _gaps(out, causal, docs)
    assert long_gap.min() > 10 * TOKEN_TOL * 1024
    assert short_gap.max() > 10 * TOKEN_TOL * 37


def test_logits_of_a_segment_match_the_reference():
    """``forward_segment`` on one 200-token document (a 256-token cache, the
    plain path) against the reference's whole-vocabulary logits."""
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "sparse-b")
    ids = np.random.default_rng(3).integers(0, 3000, 200).astype(np.int32)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :200] = ids
    hidden, state = lm_once.segment_program(cfg)(
        params, padded, jnp.int32(0), lm_once.state(cfg, 1, 256))
    got = np.asarray(hidden[0, :200] @ params["head"].T)
    want = ref.logits(REF_CFG, "sparse-b", ids, range(200))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert set(state) == {"mixer", "pairs"}
    assert state["mixer"]["kv"].shape == (2, 1, 256, 40)
    assert state["mixer"]["ki"].shape == (2, 1, 256, 16)
    assert 0 < float(state["pairs"]) <= 256 * 4


def test_bf16_is_near_the_reference_and_the_int8_control_further_off():
    """The control's tables: every projection leaf (the router's too), the
    feed-forwards and the held experts ``[layers, experts, in, out]``; its
    logits lie further from the reference than the bf16 program's."""
    from agent_tpu.models.quant import quantize_for_family

    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "dtype": "bfloat16"})
    ids = np.random.default_rng(4).integers(0, 3000, 256).astype(np.int32)
    want = ref.logits({**REF_CFG, "dtype": "bfloat16"}, "sparse-q", ids,
                      range(256))

    step = lm_once.segment_program(cfg)     # traced a tree: bf16, int8

    def gap(params):
        hidden, _ = step(params, ids[None], jnp.int32(0),
                         lm_once.state(cfg, 1, 256))
        got = hidden[0].astype(jnp.float32) @ params["head"].astype(
            jnp.float32).T
        return float(np.abs(np.asarray(got) - want).mean())

    sound = gap(lm_once.params(cfg, "sparse-q"))
    q = quantize_for_family("decoder_lm", lm_once.params(cfg, "sparse-q"),
                            "int8")
    experts = q["expert_layers"]
    assert experts["we_up"]["w_q"].shape == (1, 4, 64, 32)
    assert experts["we_up"]["w_q"].dtype == jnp.int8
    assert experts["we_up"]["w_scale"].shape == (1, 4, 32)
    assert experts["w_router"]["w_scale"].shape == (1, 16)
    assert q["layers"]["w_ukv"]["w_q"].shape == (1, 32, 4 * 32)
    assert q["layers"]["wi_w"].dtype == q["embed"].dtype == jnp.bfloat16
    control = gap(q)
    assert sound < 0.05 and control > 1.5 * sound, (sound, control)


# ---- an expert layer and its shares ---------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each, and one that holds all sixteen: the
    routed parts of the shares, with the shared expert counted once, are the
    uncut layer; an expert's weights are the same wherever it is held; the
    uncut layer is the reference's."""
    whole = decoder_lm.DecoderLMConfig(**{**TINY, "n_experts_held": 16})
    n = jax.random.normal(jax.random.PRNGKey(5), (1, 300, 64), jnp.float32)
    layer = lambda cfg: lm_once.first_layer(  # noqa: E731
        lm_once.params(cfg, "sparse-c")["expert_layers"])
    p_whole = layer(whole)
    y_whole, pairs_whole = lm_once.experts_program(whole)(p_whole, n)
    shared = lm_once.shared_expert(p_whole, n)
    down_whole = np.asarray(p_whole["we_down"])
    total, pairs = shared, 0.0
    for first in (0, 4, 8, 12):
        cfg = decoder_lm.DecoderLMConfig(**{**TINY, "expert_first": first})
        p = layer(cfg)
        np.testing.assert_array_equal(np.asarray(p["we_down"]),
                                      down_whole[first:first + 4])
        y, held_pairs = lm_once.experts_program(cfg)(p, n)
        total = total + (y - shared)
        pairs += float(held_pairs)
    assert pairs == float(pairs_whole) == 300 * 4     # every choice, once
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole),
                               atol=1e-5)
    # The reference's layer, uncut (it adds the residual; n is normed there).
    u = n[0] * 3.0
    cfg_ref = {**REF_CFG, "n_experts_held": 16}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer_ffn(cfg_ref, "sparse-c", 1, u)
    got = lm_once.expert_layer_program(whole)(p_whole, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---- what the longer LEAVES left alone ------------------------------------

def test_the_first_mixers_weights_are_what_they_were():
    """``LEAVES`` grew at its end: leaf j keeps its key. The digest is of the
    tree the parent of this change built for the same config and id."""
    assert decoder_lm.LEAVES[:10] == (
        "embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
        "w_down")
    cfg = decoder_lm.DecoderLMConfig(vocab_size=300, d_model=64, n_heads=10,
                                     n_kv_heads=2, d_head=16, d_ff=96,
                                     n_layers=3)
    # About the draw itself: a fresh one, not ``lm_once``'s.
    params = decoder_lm.init_params(cfg, "digest-model")
    assert set(params) == {"embed", "head", "final_norm", "layers"}
    flat = {"/".join(str(k.key) for k in path): np.asarray(
        leaf.astype(np.float32))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    digest = hashlib.sha256()
    for name in sorted(flat):
        digest.update(name.encode())
        digest.update(flat[name].tobytes())
    assert digest.hexdigest() == (
        "f1233add1f94f8dae23074661cb866350e859602ab0b303c9cb7d1a7f31ecc34")


@pytest.mark.parametrize("over, message", [
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
    ({"index_head_dim": 4}, "index_head_dim"),
    ({"expert_first": 14}, "experts held"),
    ({"n_experts": 18}, "whole groups"),
    ({"n_experts_per_token": 9}, "cannot choose"),
    ({"n_dense_layers": 3}, "n_dense_layers"),
    ({"index_topk": 0}, "index_topk"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))
