"""Shared autoregressive decode engines: greedy and beam scans.

One ``lax.scan`` program per decode (static step count, no per-step retrace,
KV caches threaded through the carry) — the pattern SURVEY.md §7 calls the
hard part of decode-under-jit. The model supplies a step function and its
caches; the engine supplies the control flow, EOS bookkeeping, and (for
beam) the joint top-K + cache reordering. Both the in-house seq2seq family
and the imported BART family run on these engines, so generation semantics
can never drift between families.

``step_fn(tok [B], step scalar, caches) -> (logits [B, V] f32, caches)``.
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.models.layers import NEG_INF

StepFn = Callable[[jax.Array, jax.Array, Any], Tuple[jax.Array, Any]]

# The continuous engine's per-row step: positions are a [rows] vector (each
# running-batch slot sits at its own decode depth) and the encoder state is
# an argument (slots join with their own prefill output). So are the model
# parameters (first): a step that closed over them would compile every
# weight into the program as a constant — at 768 wide a 393 MB executable
# that no compile cache holds, and a second copy of the decoder in HBM.
PositionalStepFn = Callable[
    [Any, jax.Array, jax.Array, Any, jax.Array, jax.Array],
    Tuple[jax.Array, Any],
]


def _state_sharding(params: Any) -> Optional[jax.sharding.Sharding]:
    """Replicated over the mesh ``params`` are placed on; ``None`` for
    parameters nobody placed (plain arrays, as the engine tests pass)."""
    leaves = jax.tree_util.tree_leaves(params)
    placed = getattr(leaves[0], "sharding", None) if leaves else None
    if isinstance(placed, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(
            placed.mesh, jax.sharding.PartitionSpec()
        )
    return None


class KVPoolExhausted(Exception):
    """A request's worst-case KV reservation exceeds the whole pool: it can
    NEVER be seated, no matter how long it waits — the serving layer's 429.
    (A request that merely has to wait for blocks stays in the backlog; the
    engine reserves a request's full ``ceil(limit / block_size)`` blocks per
    beam row at seat time, so a seated request can never run out of blocks
    mid-decode and is never forced to emit a wrong token.)"""



def _ban_eos_before(scores, step, min_length: int, eos_id: int):
    """HF ``MinLengthLogitsProcessor``: EOS masked to ``NEG_INF`` while the
    decoder sequence (start token + generated, HF's counting = step+1) is
    below ``min_length``. Single-sourced so greedy and beam can never drift.
    ``scores``: [..., V] logits or logprobs."""
    if min_length <= 0:
        return scores
    v = scores.shape[-1]
    lead = (1,) * (scores.ndim - 1)
    return jnp.where(
        (step + 1 < min_length)
        & (jnp.arange(v) == eos_id).reshape(lead + (v,)),
        NEG_INF, scores,
    )


def _ban_eos_before_rows(scores, pos, min_length: int, eos_id: int):
    """Per-row variant of :func:`_ban_eos_before` for the continuous engine:
    ``scores`` [S, ..., V], ``pos`` [S] per-slot step indices. Same masking
    values per row as the scalar version at that row's step."""
    if min_length <= 0:
        return scores
    v = scores.shape[-1]
    cond = (pos + 1 < min_length).reshape(
        (scores.shape[0],) + (1,) * (scores.ndim - 1)
    )
    return jnp.where(
        cond & (jnp.arange(v) == eos_id).reshape(
            (1,) * (scores.ndim - 1) + (v,)
        ),
        NEG_INF, scores,
    )


def _bank_hypotheses(K: int, fin_scores, fin_toks, cand_norm, cand_toks):
    """Merge candidate hypotheses into the K-slot finished store (shared by
    ``beam_scan`` and the continuous engine so banking can never drift).
    ``cand_norm`` [B, n] (``-inf`` = ineligible — it must be -inf, see the
    ``beam_scan`` initializer note), ``cand_toks`` [B, n, T]."""
    all_scores = jnp.concatenate([fin_scores, cand_norm], axis=1)
    all_toks = jnp.concatenate([fin_toks, cand_toks], axis=1)
    new_scores, sel = jax.lax.top_k(all_scores, K)          # [B, K]
    new_toks = jnp.take_along_axis(all_toks, sel[:, :, None], axis=1)
    return new_scores, new_toks


def greedy_scan(
    step_fn: StepFn,
    caches: Any,
    batch: int,
    max_new_tokens: int,
    *,
    start_id: int,
    eos_id: int,
    pad_id: int = 0,
    min_length: int = 0,
    forced_first_id: Optional[int] = None,
    forced_last_id: Optional[int] = None,
    early_exit: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy decode → (tokens [B, T], lengths [B]).

    Rows emit ``pad_id`` after their EOS; ``forced_first_id`` (e.g. BART's
    ``forced_bos_token_id``) overrides the step-0 argmax, and
    ``forced_last_id`` (``forced_eos_token_id``) the final step's, when set.
    ``min_length`` bans EOS while the sequence (decoder start + generated,
    HF's counting) is shorter — HF ``MinLengthLogitsProcessor``; a forced
    last token still wins, matching HF's processor order.

    ``early_exit=True`` (default) runs the decode as a ``lax.while_loop``
    that stops once EVERY row has emitted EOS — identical outputs (the
    untouched tail of the token buffer is already ``pad_id``, exactly what
    the full-length scan would write), but a batch of short summaries pays
    for its longest row, not for ``max_new_tokens``. ``False`` keeps the
    fixed-trip ``lax.scan`` (marginally better for batches that always run
    full length, and the differentiable choice if a scoring path ever
    backprops through decode — ``while_loop`` has no reverse rule).
    """
    bos = jnp.full((batch,), start_id, dtype=jnp.int32)
    done0 = jnp.zeros((batch,), dtype=jnp.bool_)
    last = max_new_tokens - 1

    def step_tok(tok, done, caches, step):
        logits, caches = step_fn(tok, step, caches)
        logits = _ban_eos_before(logits, step, min_length, eos_id)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if forced_first_id is not None:
            nxt = jnp.where(step == 0, jnp.int32(forced_first_id), nxt)
        if forced_last_id is not None:
            nxt = jnp.where(step == last, jnp.int32(forced_last_id), nxt)
        nxt = jnp.where(done, jnp.full_like(nxt, pad_id), nxt)
        return nxt, done | (nxt == eos_id), caches

    if early_exit:
        toks0 = jnp.full((batch, max_new_tokens), pad_id, dtype=jnp.int32)

        def cond(carry):
            step, _, done, _, _ = carry
            return jnp.logical_and(step < max_new_tokens, ~jnp.all(done))

        def body(carry):
            step, tok, done, toks, caches = carry
            nxt, done, caches = step_tok(tok, done, caches, step)
            toks = toks.at[:, step].set(nxt)
            return step + 1, nxt, done, toks, caches

        _, _, _, toks, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), bos, done0, toks0, caches)
        )
    else:
        def body(carry, step):
            tok, done, caches = carry
            nxt, done, caches = step_tok(tok, done, caches, step)
            return (nxt, done, caches), nxt

        (_, _, _), toks = jax.lax.scan(
            body, (bos, done0, caches),
            jnp.arange(max_new_tokens, dtype=jnp.int32),
        )
        toks = toks.T  # [B, T]
    lengths = jnp.sum((toks != pad_id) & (toks != eos_id), axis=1)
    return toks, lengths


def beam_scan(
    step_fn: StepFn,
    caches: Any,
    batch: int,
    vocab_size: int,
    max_new_tokens: int,
    *,
    num_beams: int,
    start_id: int,
    eos_id: int,
    pad_id: int = 0,
    length_penalty: float = 1.0,
    early_stopping: bool = False,
    min_length: int = 0,
    forced_first_id: Optional[int] = None,
    forced_last_id: Optional[int] = None,
    cache_reorder: str = "delta",
) -> Tuple[jax.Array, jax.Array]:
    """Beam-search decode → (tokens [B, T], lengths [B]); static shapes.

    HF ``BeamSearchScorer`` semantics, differential-tested token-exact
    against ``transformers`` beam generation (tests/test_bart.py; the
    engine-level invariants — beam1 == greedy, determinism, score
    dominance — live in tests/test_map_summarize.py): each step takes the
    top-2K candidates of
    the joint ``[B, K·V]`` scores; EOS candidates ranked < K bank their
    hypothesis into a static K-slot finished store (normalized by HF's
    length convention — sequence length INCLUDING the decoder start, i.e.
    ``(step+1) ** length_penalty``); the K best non-EOS candidates continue
    (gathering the KV caches along the beam axis). A row stops improving
    once its store holds K hypotheses and — with ``early_stopping=False``,
    the HF default — the best running candidate can no longer beat the
    worst banked one; ``early_stopping=True`` stops at K banked outright.
    After the scan, still-running beams of unfinished rows are banked at
    full length, and each row emits its best hypothesis.

    Beams flatten into the batch dim, so the model's step executable is
    shared with greedy at ``B*K`` rows. ``num_beams=1`` degenerates to
    greedy-with-banking: same emitted tokens as ``greedy_scan``.

    ``cache_reorder`` picks the KV-cache beam-reorder scheme, bit-identical
    outputs either way (regression-tested):

    - ``"delta"`` (default): the per-step gather of every KV cache along the
      beam axis runs under ``lax.cond``, skipped entirely on steps where the
      selected continuation is the identity permutation (each beam extends
      its own parent — ``beam_idx == arange(K)`` for every row, the common
      case once beam frontiers stabilize and for frozen rows). The gather
      moves the FULL [B·K, H, T, D] cache per layer; skipping identity steps
      removes that HBM round trip from most of a long decode.
    - ``"gather"``: the unconditional per-step gather (the pre-delta
      behavior), kept as the equivalence-test reference.
    """
    if cache_reorder not in ("delta", "gather"):
        raise ValueError(
            f"cache_reorder must be 'delta' or 'gather', got {cache_reorder!r}"
        )
    B, K, V, T = batch, num_beams, vocab_size, max_new_tokens
    K2 = 2 * K
    tok0 = jnp.full((B * K,), start_id, dtype=jnp.int32)
    # Step 0: all K beams are identical, so only beam 0 may survive top-K.
    scores0 = jnp.tile(
        jnp.array([0.0] + [NEG_INF] * (K - 1), dtype=jnp.float32), (B, 1)
    )
    toks0 = jnp.full((B, K, T), pad_id, dtype=jnp.int32)
    # Empty finished slots are -inf, NOT the finite NEG_INF: with a negative
    # length_penalty a real hypothesis can normalize below -1e9, and an
    # empty all-pad slot must never outrank a real hypothesis.
    _EMPTY = jnp.float32(-jnp.inf)
    fin_scores0 = jnp.full((B, K), _EMPTY, dtype=jnp.float32)  # normalized
    fin_toks0 = jnp.full((B, K, T), pad_id, dtype=jnp.int32)
    row_done0 = jnp.zeros((B,), dtype=jnp.bool_)
    forced_only = (
        jnp.full((V,), NEG_INF, dtype=jnp.float32).at[forced_first_id].set(0.0)
        if forced_first_id is not None
        else None
    )
    forced_last = (
        jnp.full((V,), NEG_INF, dtype=jnp.float32).at[forced_last_id].set(0.0)
        if forced_last_id is not None
        else None
    )
    lp = jnp.float32(length_penalty)

    def bank(fin_scores, fin_toks, cand_norm, cand_toks):
        """``_bank_hypotheses`` at this decode's K (see module level)."""
        return _bank_hypotheses(K, fin_scores, fin_toks, cand_norm, cand_toks)

    def body(carry, step):
        tok, scores, toks, fin_scores, fin_toks, row_done, caches = carry
        logits, caches = step_fn(tok, step, caches)   # [B*K, V]
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
        # Applied BEFORE the forced substitutions, which replace the whole
        # distribution — HF's processor order, so a forced EOS wins.
        logp = _ban_eos_before(logp, step, min_length, eos_id)
        if forced_only is not None:
            logp = jnp.where(step == 0, forced_only[None, None, :], logp)
        if forced_last is not None:
            logp = jnp.where(step == T - 1, forced_last[None, None, :], logp)
        flat = (scores[:, :, None] + logp).reshape(B, K * V)
        cand_scores, idx = jax.lax.top_k(flat, K2)    # [B, 2K]
        cand_beam = idx // V                          # [B, 2K] parent beam
        cand_tok = (idx % V).astype(jnp.int32)
        is_eos = cand_tok == eos_id

        # --- bank EOS candidates (HF: only ranks < K are eligible, and
        # only while the row is still open). Hypothesis length follows
        # HF's convention: decoder start + step generated tokens, the EOS
        # itself excluded from the count → (step + 1).
        hyp_len = (step + 1).astype(jnp.float32)
        eligible = is_eos & (jnp.arange(K2)[None, :] < K) & ~row_done[:, None]
        cand_norm = jnp.where(
            eligible, cand_scores / hyp_len ** lp, _EMPTY
        )
        # Candidate token buffers: parent prefix + EOS written at `step`.
        par_toks = jnp.take_along_axis(toks, cand_beam[:, :, None], axis=1)
        eos_col = jnp.full((B, K2, 1), eos_id, dtype=jnp.int32)
        cand_toks = jax.lax.dynamic_update_slice(par_toks, eos_col,
                                                 (0, 0, step))
        fin_scores, fin_toks = bank(fin_scores, fin_toks, cand_norm,
                                    cand_toks)

        # --- continue with the K best non-EOS candidates. cand_scores are
        # already sorted descending and top_k tie-breaks by index, so this
        # masked top_k returns the first K non-EOS columns in score order.
        # EOS appears at most once per parent beam → at most K of the 2K
        # candidates are EOS → K non-EOS always exist, except at a
        # forced-last step (all mass on EOS) where the selection is
        # irrelevant: the scan ends and every row's store just filled.
        _, gather_pos = jax.lax.top_k(
            jnp.where(is_eos, -jnp.inf, cand_scores), K
        )
        new_scores = jnp.take_along_axis(cand_scores, gather_pos, axis=1)
        new_tok = jnp.take_along_axis(cand_tok, gather_pos, axis=1)
        beam_idx = jnp.take_along_axis(cand_beam, gather_pos, axis=1)

        # Rows already done freeze: emit pad, scores frozen, and the beams
        # keep THEIR OWN slots (identity, not collapse-to-beam-0): a done
        # row's running beams never reach the output (their final-bank
        # normalization is _EMPTY), so any permutation is output-equivalent
        # — identity is the one that lets the delta reorder below skip the
        # cache gather for frozen rows.
        arange_k = jnp.arange(K, dtype=jnp.int32)[None, :]
        new_scores = jnp.where(row_done[:, None], scores, new_scores)
        new_tok = jnp.where(row_done[:, None], pad_id, new_tok)
        beam_idx = jnp.where(row_done[:, None], arange_k, beam_idx)

        toks = jnp.take_along_axis(toks, beam_idx[:, :, None], axis=1)
        toks = jax.lax.dynamic_update_slice(
            toks, new_tok[:, :, None], (0, 0, step)
        )  # frozen rows write pad over pad — a no-op by construction

        # --- HF is_done: store full AND (early_stopping, or the best
        # RUNNING beam — EOS candidates excluded, HF's
        # `_check_early_stop_heuristic` uses the post-selection running
        # scores — can no longer beat the banked worst under the
        # current-length normalization).
        full = jnp.isfinite(fin_scores[:, K - 1])
        if early_stopping:
            newly_done = full
        else:
            best_running = new_scores[:, 0] / hyp_len ** lp
            newly_done = full & (best_running <= fin_scores[:, K - 1])
        row_done = row_done | newly_done

        def reorder(c):
            x = c.reshape(B, K, *c.shape[1:])
            ix = beam_idx.reshape(B, K, *([1] * (c.ndim - 1)))
            return jnp.take_along_axis(x, ix, axis=1).reshape(c.shape)

        def reorder_all(cs):
            return jax.tree_util.tree_map(reorder, cs)

        if cache_reorder == "gather":
            caches = reorder_all(caches)
        else:
            # Delta reorder: gather only when some beam actually switches
            # parent. The identity branch is a pass-through lax.cond arm —
            # no [B·K, H, T, D] gather, no HBM round trip — and shapes stay
            # scan-stable because both arms return the same pytree.
            caches = jax.lax.cond(
                jnp.all(beam_idx == arange_k),
                lambda cs: cs, reorder_all, caches,
            )
        return (
            new_tok.reshape(B * K), new_scores, toks,
            fin_scores, fin_toks, row_done, caches,
        ), None

    # while_loop, not scan: once every row is done further steps are pure
    # frozen no-ops, so a batch of short summaries pays for its longest
    # row, not for max_new_tokens — the same early exit greedy_scan makes.
    # (Nothing backprops through beam decode, so the missing reverse rule
    # costs nothing.)
    def cond(carry):
        return jnp.logical_and(carry[0] < T, ~jnp.all(carry[6]))

    def wbody(carry):
        step = carry[0]
        new_carry, _ = body(carry[1:], step)
        return (step + 1,) + new_carry

    (_, _, scores, toks, fin_scores, fin_toks, row_done, _) = (
        jax.lax.while_loop(
            cond, wbody,
            (jnp.int32(0), tok0, scores0, toks0,
             fin_scores0, fin_toks0, row_done0, caches),
        )
    )

    # Finalize (HF): rows that never closed bank their running beams,
    # normalized by their GENERATED length T — HF's unified rule is
    # "normalize by the hypothesis's generated token count" (an in-scan
    # banked hypothesis has step generated tokens + its EOS = step+1;
    # a run-to-the-end beam has exactly T).
    run_norm = jnp.where(
        row_done[:, None], _EMPTY,
        scores / jnp.float32(T) ** lp,
    )
    fin_scores, fin_toks = bank(fin_scores, fin_toks, run_norm, toks)

    out = fin_toks[:, 0]                                        # [B, T]
    out_len = jnp.sum((out != pad_id) & (out != eos_id), axis=1)
    return out, out_len


# ---------------------------------------------------------------------------
# Iteration-level continuous batching (ISSUE 15)
# ---------------------------------------------------------------------------

class DecodeTicket:
    """One request's seat in the continuous engine: the prefill handoff in,
    the emitted tokens (and TTFT/occupancy bookkeeping) out.

    Per-slot lifecycle telemetry (ISSUE 17): beyond the admit/join/first-
    token/done walls the ticket records how long it waited on KV-block
    availability (``kv_wait_s`` — the paged pool's FIFO head-of-line wait),
    the engine step count at join, the running-batch occupancy the moment
    it was seated, and an ordered ``events`` list of ``(name, wall)``
    lifecycle stamps (``admit``/``kv_wait``/``seat``/``first_token``/
    ``exit``) for the request trace."""

    __slots__ = (
        "data", "limit", "enc_row", "mask_row", "slot",
        "admitted_wall", "joined_wall", "first_token_wall", "done_wall",
        "tokens", "length", "steps",
        "kv_wait_start", "kv_wait_s", "join_step", "occupancy_at_join",
        "events",
    )

    def __init__(self, enc_row, mask_row, limit: int, data: Any = None):
        self.data = data
        self.limit = int(limit)
        self.enc_row = enc_row
        self.mask_row = mask_row
        self.slot: Optional[int] = None
        self.admitted_wall: Optional[float] = None
        self.joined_wall: Optional[float] = None
        self.first_token_wall: Optional[float] = None
        self.done_wall: Optional[float] = None
        self.tokens: Optional[np.ndarray] = None
        self.length: int = 0
        self.steps: int = 0
        self.kv_wait_start: Optional[float] = None
        self.kv_wait_s: float = 0.0
        self.join_step: int = 0
        self.occupancy_at_join: int = 0
        self.events: List[Tuple[str, float]] = []


class ContinuousBatcher:
    """Iteration-level continuous batching over a fixed-capacity slot batch.

    The scan engines above compile ONE program per decode: a batch enters
    together and (early exit aside) pays for its slowest row. Serving traffic
    is the opposite shape — requests arrive continuously — so this engine
    keeps a *running* batch of ``slots`` requests (× ``num_beams`` beam rows
    each) and drives ONE jitted step program per decode iteration:

    - finished sequences **exit between steps** (their slot frees the moment
      the per-slot done flag trips — EOS/banked-full for beam, EOS or the
      per-slot token ``limit`` for greedy);
    - queued sequences **join between steps** via a jitted slot-insertion
      (``dynamic_update_slice`` of the new request's prefill output + a
      zeroed KV block — the same delta-style "touch only what changed"
      discipline as the PR 1 cache reorder, so a join never rewrites the
      running batch);
    - every slot carries its own position vector, so the decode math per
      slot is bit-identical to a solo ``greedy_scan``/``beam_scan`` of that
      request (regression-tested in tests/test_serving.py).

    Prefill is NOT this engine's job: callers encode (batched, as its own
    step — the ``summarize_mpmd`` encoded handoff) and admit
    ``(enc_row, mask_row)`` per request. ``step_fn`` is a
    :data:`PositionalStepFn` (e.g. ``seq2seq.make_positional_step``) and
    ``params`` the model parameters it is called with — an argument of the
    jitted step, never donated (they stay the params store's). Everything
    else the jitted programs take (the state built here, the pushed block
    table, the admitted rows) is placed on the params' mesh, replicated:
    what a program over mesh-placed params returns is typed with that mesh,
    and an input typed otherwise would retrace step and insert at every
    join (``tests/test_serving.py`` counts the executables).

    Host loop by design: one jitted step per iteration, state threaded
    through with buffer donation where the backend supports it. That trades
    the scan engines' zero host round-trips for the ability to mutate batch
    membership — the defining trade of continuous-batching serving stacks.
    """

    def __init__(
        self,
        step_fn: PositionalStepFn,
        cache_factory: Callable[[int], Any],
        *,
        params: Any,
        slots: int,
        vocab_size: int,
        max_tokens: int,
        enc_len: int,
        d_model: int,
        start_id: int,
        eos_id: int,
        pad_id: int = 0,
        num_beams: int = 1,
        min_length: int = 0,
        length_penalty: float = 1.0,
        early_stopping: bool = False,
        cache_reorder: str = "delta",
        enc_dtype: Any = jnp.float32,
        micro_steps: int = 1,
        clock: Callable[[], float] = time.time,
    ) -> None:
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if num_beams < 1:
            raise ValueError("num_beams must be >= 1")
        if micro_steps < 1:
            raise ValueError("micro_steps must be >= 1")
        if cache_reorder not in ("delta", "gather"):
            raise ValueError(
                f"cache_reorder must be 'delta' or 'gather', "
                f"got {cache_reorder!r}"
            )
        self.step_fn = step_fn
        self._params = params
        self._sharding = _state_sharding(params)
        self.slots = int(slots)
        self.K = int(num_beams)
        self.V = int(vocab_size)
        self.T = int(max_tokens)
        self.enc_len = int(enc_len)
        self.start_id = int(start_id)
        self.eos_id = int(eos_id)
        self.pad_id = int(pad_id)
        self.min_length = int(min_length)
        self.length_penalty = float(length_penalty)
        self.early_stopping = bool(early_stopping)
        self.cache_reorder = cache_reorder
        self.beam = self.K > 1
        # Decode iterations fused per dispatch: 1 (default) is pure
        # iteration-level batching — membership can change between every
        # step. Dispatch-overhead-bound deployments (small models, CPU
        # smoke; not measured on a directly attached chip) raise it: N
        # iterations run as one jitted ``fori_loop`` program (XLA reuses
        # buffers across the chained updates, recovering most of the scan
        # engines' zero-overhead stepping), and joins/exits happen between
        # CHUNKS — completed slots ride out the remainder of a chunk
        # frozen, exactly like empty slots, so per-request outputs are
        # unchanged.
        self.micro_steps = int(micro_steps)
        self._clock = clock
        S, K, T, R = self.slots, self.K, self.T, self.slots * self.K
        # State is split DYNAMIC vs STATIC: the jitted step returns only the
        # dynamic part, so per-iteration buffer traffic on backends without
        # donation (CPU) excludes the encoder block and per-slot limits —
        # they change only at joins, through the insert program.
        caches = cache_factory(R)
        # Paged KV (ISSUE 16), detected structurally from the factory's
        # pytree (``make_paged_cache_factory``): layer caches are shared
        # block pools addressed through a per-row block table. The device
        # side is pure dataflow; allocation lives HERE, on the host — a
        # numpy table mirror plus a free list, pushed to the device (one
        # tiny [R, MAXB] int32 upload) whenever seats/releases change it.
        self.paged = isinstance(caches, dict) and "table" in caches
        if self.paged:
            table = caches["table"]
            if table.shape[0] != R:
                raise ValueError(
                    f"paged cache table has {table.shape[0]} rows, engine "
                    f"needs slots*num_beams={R}"
                )
            self.kv_block_size = int(caches["layers"][0]["k"].shape[2])
            self.kv_max_blocks = int(table.shape[1])
            self.kv_pool_blocks = int(caches["layers"][0]["k"].shape[0])
            self._table_np = np.zeros(
                (R, self.kv_max_blocks), dtype=np.int32
            )
            # Block 0 is the trash block: released/unallocated table entries
            # point there so frozen rows' steady rewrites at their final
            # position can never corrupt a reallocated block.
            self._free_blocks: List[int] = list(
                range(1, self.kv_pool_blocks)
            )
            self._slot_blocks: Dict[int, List[int]] = {}
            self._table_dirty = False
        dyn: Dict[str, Any] = {
            "tok": jnp.full((R,), self.start_id, dtype=jnp.int32),
            "pos": jnp.zeros((S,), dtype=jnp.int32),
            # Empty slots are frozen rows (`row_done`): they ride every step
            # as pads + identity reorders and reset on insertion.
            "row_done": jnp.ones((S,), dtype=jnp.bool_),
            "caches": caches,
        }
        if self.beam:
            dyn["scores"] = jnp.tile(
                jnp.array([0.0] + [NEG_INF] * (K - 1), dtype=jnp.float32),
                (S, 1),
            )
            dyn["toks"] = jnp.full((S, K, T), self.pad_id, dtype=jnp.int32)
            dyn["fin_scores"] = jnp.full(
                (S, K), -jnp.inf, dtype=jnp.float32
            )
            dyn["fin_toks"] = jnp.full(
                (S, K, T), self.pad_id, dtype=jnp.int32
            )
        else:
            dyn["toks"] = jnp.full((S, T), self.pad_id, dtype=jnp.int32)
        self._dyn = self._put(dyn)
        self._stat: Dict[str, Any] = self._put({
            "limit": jnp.ones((S,), dtype=jnp.int32),
            "enc_out": jnp.zeros((R, self.enc_len, d_model), dtype=enc_dtype),
            "enc_mask": jnp.zeros((R, self.enc_len), dtype=jnp.int32),
        })
        # Buffer donation makes the step/insert updates in-place on backends
        # that support it; CPU copies and warns — silence the known-benign
        # warning rather than fork the code path.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        step_impl = self._step_beam if self.beam else self._step_greedy
        if self.micro_steps > 1:
            n = self.micro_steps

            def chunk(dyn, stat, params):
                return jax.lax.fori_loop(
                    0, n, lambda _i, d: step_impl(d, stat, params), dyn
                )

            self._jstep = jax.jit(chunk, donate_argnums=0)
        else:
            self._jstep = jax.jit(step_impl, donate_argnums=0)
        self._jinsert = jax.jit(self._insert, donate_argnums=(0, 1))
        self._live: Dict[int, DecodeTicket] = {}
        self._free: List[int] = list(range(S))
        self._backlog: List[DecodeTicket] = []
        # Occupancy accounting (the `serve_batch_occupancy` gauge feed).
        self.steps_run = 0
        self.occupancy_sum = 0
        self.max_occupancy = 0
        self.tokens_emitted = 0

    def _put(self, tree: Any) -> Any:
        """Host or device arrays → device arrays where the params live (the
        default device, uncommitted, for params nobody placed)."""
        return jax.device_put(tree, self._sharding)

    # ---- jitted programs ----

    def _step_greedy(
        self, state: Dict[str, Any], stat: Dict[str, Any], params: Any
    ) -> Dict[str, Any]:
        S, T = self.slots, self.T
        pos, row_done = state["pos"], state["row_done"]
        logits, caches = self.step_fn(
            params, state["tok"], pos, state["caches"],
            stat["enc_out"], stat["enc_mask"],
        )
        logits = _ban_eos_before_rows(
            logits, pos, self.min_length, self.eos_id
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(row_done, jnp.int32(self.pad_id), nxt)
        # Frozen slots write out of bounds → dropped (their buffers must
        # survive untouched until the host extracts / the slot reseats).
        col = jnp.where(row_done, jnp.int32(T), pos)
        toks = state["toks"].at[jnp.arange(S), col].set(nxt, mode="drop")
        new_pos = jnp.where(row_done, pos, pos + 1)
        new_done = row_done | (nxt == self.eos_id) | (new_pos >= stat["limit"])
        return dict(
            state, tok=nxt, pos=new_pos, row_done=new_done, toks=toks,
            caches=caches,
        )

    def _step_beam(
        self, state: Dict[str, Any], stat: Dict[str, Any], params: Any
    ) -> Dict[str, Any]:
        """One continuous-batching beam step — ``beam_scan``'s body with the
        scalar step replaced by the per-slot ``pos`` vector, plus the
        per-slot limit banking the scan engine does after its loop."""
        S, K, V, T = self.slots, self.K, self.V, self.T
        K2 = 2 * K
        lp = jnp.float32(self.length_penalty)
        _EMPTY = jnp.float32(-jnp.inf)
        pos, row_done = state["pos"], state["row_done"]
        scores, toks = state["scores"], state["toks"]
        fin_scores, fin_toks = state["fin_scores"], state["fin_toks"]

        pos_rows = jnp.repeat(pos, K)
        logits, caches = self.step_fn(
            params, state["tok"], pos_rows, state["caches"],
            stat["enc_out"], stat["enc_mask"],
        )
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(S, K, V)
        logp = _ban_eos_before_rows(logp, pos, self.min_length, self.eos_id)
        flat = (scores[:, :, None] + logp).reshape(S, K * V)
        cand_scores, idx = jax.lax.top_k(flat, K2)    # [S, 2K]
        cand_beam = idx // V
        cand_tok = (idx % V).astype(jnp.int32)
        is_eos = cand_tok == self.eos_id

        # Bank EOS candidates (HF: ranks < K, open rows only); hypothesis
        # length is per-slot now — (pos + 1) generated tokens incl. the EOS's
        # predecessor, the same counting beam_scan uses.
        hyp_len = (pos + 1).astype(jnp.float32)       # [S]
        eligible = (
            is_eos & (jnp.arange(K2)[None, :] < K) & ~row_done[:, None]
        )
        cand_norm = jnp.where(
            eligible, cand_scores / hyp_len[:, None] ** lp, _EMPTY
        )
        par_toks = jnp.take_along_axis(toks, cand_beam[:, :, None], axis=1)
        col = jnp.where(row_done, jnp.int32(T), pos)  # frozen → dropped write
        cand_toks = par_toks.at[jnp.arange(S), :, col].set(
            jnp.int32(self.eos_id), mode="drop"
        )
        fin_scores, fin_toks = _bank_hypotheses(
            K, fin_scores, fin_toks, cand_norm, cand_toks
        )

        # Continue with the K best non-EOS candidates (see beam_scan for why
        # K always exist); frozen slots keep their own beams (identity).
        _, gather_pos = jax.lax.top_k(
            jnp.where(is_eos, -jnp.inf, cand_scores), K
        )
        new_scores = jnp.take_along_axis(cand_scores, gather_pos, axis=1)
        new_tok = jnp.take_along_axis(cand_tok, gather_pos, axis=1)
        beam_idx = jnp.take_along_axis(cand_beam, gather_pos, axis=1)
        arange_k = jnp.arange(K, dtype=jnp.int32)[None, :]
        new_scores = jnp.where(row_done[:, None], scores, new_scores)
        new_tok = jnp.where(
            row_done[:, None], jnp.int32(self.pad_id), new_tok
        )
        beam_idx = jnp.where(row_done[:, None], arange_k, beam_idx)

        toks = jnp.take_along_axis(toks, beam_idx[:, :, None], axis=1)
        toks = toks.at[jnp.arange(S), :, col].set(new_tok, mode="drop")

        # HF is_done, per slot (beam_scan's rule verbatim).
        full = jnp.isfinite(fin_scores[:, K - 1])
        if self.early_stopping:
            newly_done = full
        else:
            best_running = new_scores[:, 0] / hyp_len ** lp
            newly_done = full & (best_running <= fin_scores[:, K - 1])
        row_done2 = row_done | newly_done

        # Per-slot limit: a slot that ran out of budget banks its running
        # beams normalized by its OWN generated length — exactly the
        # post-loop banking a solo beam_scan(max_new=limit) performs.
        new_pos = jnp.where(row_done, pos, pos + 1)
        reached = (new_pos >= stat["limit"]) & ~row_done2
        run_norm = jnp.where(
            reached[:, None],
            new_scores / stat["limit"].astype(jnp.float32)[:, None] ** lp,
            _EMPTY,
        )
        fin_scores, fin_toks = _bank_hypotheses(
            K, fin_scores, fin_toks, run_norm, toks
        )
        row_done2 = row_done2 | reached

        def reorder(c):
            x = c.reshape(S, K, *c.shape[1:])
            ix = beam_idx.reshape(S, K, *([1] * (c.ndim - 1)))
            return jnp.take_along_axis(x, ix, axis=1).reshape(c.shape)

        def reorder_all(cs):
            return jax.tree_util.tree_map(reorder, cs)

        def reorder_paged(cs):
            # Paged beam reorder: blocks are row-exclusive (two sibling
            # beams must be free to diverge after inheriting one parent),
            # so the reorder COPIES the parent rows' block contents into
            # each child row's own blocks — the table itself is unchanged.
            # Logical block j of child row r gets logical block j of its
            # parent row: the same positions a dense row-gather would move.
            # Unallocated entries copy trash→trash (all dst duplicates land
            # on block 0, whose content is never attended unmasked).
            table = cs["table"]
            parent = (
                jnp.arange(S, dtype=jnp.int32)[:, None] * K + beam_idx
            ).reshape(-1)                              # [S*K] parent rows
            src = jnp.take(table, parent, axis=0).reshape(-1)
            dst = table.reshape(-1)

            def copy_pool(c):
                return c.at[dst].set(jnp.take(c, src, axis=0))

            return {
                "table": table,
                "layers": [
                    {"k": copy_pool(lc["k"]), "v": copy_pool(lc["v"])}
                    for lc in cs["layers"]
                ],
            }

        reorder_fn = reorder_paged if self.paged else reorder_all
        if self.cache_reorder == "gather":
            caches = reorder_fn(caches)
        else:
            # Delta reorder (PR 1): frozen/empty slots are identity, so a
            # steady-state running batch frequently skips the full-cache
            # gather — the property that keeps joins cheap.
            caches = jax.lax.cond(
                jnp.all(beam_idx == arange_k),
                lambda cs: cs, reorder_fn, caches,
            )
        return dict(
            state, tok=new_tok.reshape(S * K), pos=new_pos,
            row_done=row_done2, scores=new_scores, toks=toks,
            fin_scores=fin_scores, fin_toks=fin_toks, caches=caches,
        )

    def _insert(self, state, stat, slot, enc_row, mask_row, limit):
        """Seat one request in ``slot``: prefill output in, KV block zeroed,
        per-slot decode state reset. All `dynamic_update_slice`/scatter —
        the running batch's other slots are never touched."""
        K, T = self.K, self.T
        r0 = slot * K
        enc_out = jax.lax.dynamic_update_slice(
            stat["enc_out"],
            jnp.broadcast_to(
                enc_row[None], (K,) + enc_row.shape
            ).astype(stat["enc_out"].dtype),
            (r0, 0, 0),
        )
        enc_mask = jax.lax.dynamic_update_slice(
            stat["enc_mask"],
            jnp.broadcast_to(
                mask_row[None], (K,) + mask_row.shape
            ).astype(jnp.int32),
            (r0, 0),
        )
        new_stat = dict(
            stat, enc_out=enc_out, enc_mask=enc_mask,
            limit=stat["limit"].at[slot].set(limit),
        )

        def zero_rows(c):
            z = jnp.zeros((K,) + c.shape[1:], dtype=c.dtype)
            return jax.lax.dynamic_update_slice(
                c, z, (r0,) + (0,) * (c.ndim - 1)
            )

        if self.paged:
            # No cache zeroing: position j is written (with real K/V) at
            # step j, before the first step that unmasks it — stale block
            # content is never attended. The block table itself is host
            # state, pushed separately by the seat/release bookkeeping.
            caches = state["caches"]
        else:
            caches = jax.tree_util.tree_map(zero_rows, state["caches"])
        tok = jax.lax.dynamic_update_slice(
            state["tok"],
            jnp.full((K,), self.start_id, dtype=jnp.int32),
            (r0,),
        )
        out = dict(state, caches=caches, tok=tok)
        out["pos"] = state["pos"].at[slot].set(0)
        out["row_done"] = state["row_done"].at[slot].set(False)
        if self.beam:
            out["scores"] = state["scores"].at[slot].set(
                jnp.array(
                    [0.0] + [NEG_INF] * (K - 1), dtype=jnp.float32
                )
            )
            out["toks"] = state["toks"].at[slot].set(
                jnp.full((K, T), self.pad_id, dtype=jnp.int32)
            )
            out["fin_scores"] = state["fin_scores"].at[slot].set(
                jnp.full((K,), -jnp.inf, dtype=jnp.float32)
            )
            out["fin_toks"] = state["fin_toks"].at[slot].set(
                jnp.full((K, T), self.pad_id, dtype=jnp.int32)
            )
        else:
            out["toks"] = state["toks"].at[slot].set(
                jnp.full((T,), self.pad_id, dtype=jnp.int32)
            )
        return out, new_stat

    # ---- host loop ----

    @property
    def occupancy(self) -> int:
        """Requests currently seated in the running batch."""
        return len(self._live)

    @property
    def backlog(self) -> int:
        return len(self._backlog)

    def has_work(self) -> bool:
        return bool(self._live or self._backlog)

    def mean_occupancy(self) -> float:
        if not self.steps_run:
            return 0.0
        return self.occupancy_sum / self.steps_run

    def executables(self) -> Dict[str, int]:
        """Executables compiled so far for the two jitted programs. A warm
        engine holds one of each however many requests have joined; more
        means an input's type or shape changed between calls."""
        return {
            "step": self._jstep._cache_size(),
            "insert": self._jinsert._cache_size(),
        }

    # ---- paged-KV host allocator (ISSUE 16) ----

    @property
    def kv_blocks_total(self) -> int:
        """Usable KV pool blocks (trash block excluded); 0 when dense."""
        return (self.kv_pool_blocks - 1) if self.paged else 0

    @property
    def kv_blocks_free(self) -> int:
        return len(self._free_blocks) if self.paged else 0

    def _blocks_needed(self, limit: int) -> int:
        """Seat-time reservation: the request's WORST CASE, every beam row
        filled to ``limit`` — a seated request can never stall mid-decode."""
        return self.K * (-(-limit // self.kv_block_size))

    def _allocate_blocks(self, slot: int, limit: int) -> None:
        per_row = -(-limit // self.kv_block_size)
        ids: List[int] = []
        for i in range(self.K):
            r = slot * self.K + i
            row_ids = [self._free_blocks.pop() for _ in range(per_row)]
            self._table_np[r, :] = 0
            self._table_np[r, :per_row] = row_ids
            ids.extend(row_ids)
        self._slot_blocks[slot] = ids
        self._table_dirty = True

    def _release_blocks(self, slot: int) -> None:
        ids = self._slot_blocks.pop(slot, None)
        if ids is None:
            return
        self._free_blocks.extend(ids)
        # Repoint the freed rows to the trash block BEFORE their blocks can
        # be reallocated: the freed slot's rows stay frozen in the batch and
        # keep rewriting K/V at their final position every step.
        self._table_np[slot * self.K:(slot + 1) * self.K, :] = 0
        self._table_dirty = True

    def _push_table(self) -> None:
        if self.paged and self._table_dirty:
            self._dyn["caches"]["table"] = self._put(self._table_np)
            self._table_dirty = False

    def admit(
        self, enc_row, mask_row, limit: int, data: Any = None
    ) -> DecodeTicket:
        """Queue one request (prefill output + per-request token budget).
        Joins the running batch immediately if a slot is free, else waits in
        the backlog and joins between steps as slots free up. Paged mode
        raises :class:`KVPoolExhausted` for a request whose worst-case block
        reservation exceeds the whole pool — it could never be seated."""
        limit = max(1, min(int(limit), self.T))
        if self.paged and self._blocks_needed(limit) > self.kv_blocks_total:
            raise KVPoolExhausted(
                f"request needs {self._blocks_needed(limit)} KV blocks "
                f"(limit={limit} × {self.K} beams, block_size="
                f"{self.kv_block_size}), pool has {self.kv_blocks_total}"
            )
        ticket = DecodeTicket(enc_row, mask_row, limit, data=data)
        ticket.admitted_wall = self._clock()
        ticket.events.append(("admit", ticket.admitted_wall))
        self._backlog.append(ticket)
        self._fill_slots()
        return ticket

    def _fill_slots(self) -> None:
        while self._free and self._backlog:
            if self.paged and (
                self._blocks_needed(self._backlog[0].limit)
                > len(self._free_blocks)
            ):
                # Head-of-line wait: FIFO admission order is part of the
                # bit-identity contract (a later short request must not
                # overtake), so the queue waits for releases, not for a
                # smaller request. Stamp the KV-wait start once (ISSUE 17)
                # — the wait ends when the head finally seats below.
                head = self._backlog[0]
                if head.kv_wait_start is None:
                    head.kv_wait_start = self._clock()
                    head.events.append(("kv_wait", head.kv_wait_start))
                break
            ticket = self._backlog.pop(0)
            slot = self._free.pop(0)
            if self.paged:
                self._allocate_blocks(slot, ticket.limit)
            self._dyn, self._stat = self._jinsert(
                self._dyn, self._stat, np.int32(slot),
                self._put(ticket.enc_row), self._put(ticket.mask_row),
                np.int32(ticket.limit),
            )
            ticket.slot = slot
            ticket.joined_wall = self._clock()
            if ticket.kv_wait_start is not None:
                ticket.kv_wait_s = max(
                    0.0, ticket.joined_wall - ticket.kv_wait_start
                )
            ticket.join_step = self.steps_run
            ticket.enc_row = ticket.mask_row = None  # joined: free the host copy
            self._live[slot] = ticket
            # Occupancy the moment this request was seated (itself
            # included) — the "how crowded was the batch I joined" signal.
            ticket.occupancy_at_join = len(self._live)
            ticket.events.append(("seat", ticket.joined_wall))

    def _extract(self, slot: int) -> Tuple[np.ndarray, int]:
        if self.beam:
            out = np.asarray(self._dyn["fin_toks"][slot, 0])
        else:
            out = np.asarray(self._dyn["toks"][slot])
        length = int(
            ((out != self.pad_id) & (out != self.eos_id)).sum()
        )
        return out, length

    def step(self) -> List[DecodeTicket]:
        """One decode iteration of the running batch. Returns the tickets
        that finished this step (their slots are already reseated from the
        backlog — the join happens between steps, never inside one)."""
        if not self._live:
            self._fill_slots()
            if not self._live:
                return []
        self._push_table()
        self._dyn = self._jstep(self._dyn, self._stat, self._params)
        self.steps_run += self.micro_steps
        self.occupancy_sum += len(self._live) * self.micro_steps
        self.max_occupancy = max(self.max_occupancy, len(self._live))
        pos = np.asarray(self._dyn["pos"])
        done = np.asarray(self._dyn["row_done"])
        now = self._clock()
        finished: List[DecodeTicket] = []
        for slot, ticket in list(self._live.items()):
            if ticket.first_token_wall is None and pos[slot] >= 1:
                ticket.first_token_wall = now
                ticket.events.append(("first_token", now))
            if done[slot]:
                ticket.steps = int(pos[slot])
                ticket.tokens, ticket.length = self._extract(slot)
                ticket.done_wall = now
                ticket.events.append(("exit", now))
                self.tokens_emitted += max(ticket.steps, ticket.length)
                del self._live[slot]
                self._free.append(slot)
                if self.paged:
                    self._release_blocks(slot)
                finished.append(ticket)
        if finished:
            self._fill_slots()
        return finished

    def run(self, tickets: List[DecodeTicket]) -> None:
        """Pump until every ticket in ``tickets`` finished — the monolithic
        (non-pipelined) path; the pipelined serving loop interleaves
        :meth:`step` with admissions instead."""
        pending = {id(t) for t in tickets if t.done_wall is None}
        while pending:
            for t in self.step():
                pending.discard(id(t))
            if not self.has_work() and pending:
                raise RuntimeError(
                    "continuous engine drained with tickets outstanding"
                )
