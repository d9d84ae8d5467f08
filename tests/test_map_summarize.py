"""map_summarize: scan-decode seq2seq on the virtual mesh.

VERDICT item 7 acceptance: registry entry real, output deterministic on CPU
backend, decode does not retrace per step.
"""

import jax
import numpy as np
import pytest

from agent_tpu.models import seq2seq
from agent_tpu.models.tokenizer import pad_batch, ByteTokenizer
from agent_tpu.ops import get_op
from agent_tpu.runtime.context import OpContext
from agent_tpu.runtime.runtime import get_runtime

SMALL = {"d_model": 64, "n_heads": 4, "n_enc_layers": 2, "n_dec_layers": 2,
         "d_ff": 128, "max_src_len": 64, "max_tgt_len": 32}


@pytest.fixture(scope="module")
def summarize():
    return get_op("map_summarize")


@pytest.fixture(scope="module")
def ctx():
    return OpContext(runtime=get_runtime())


def test_contract_and_determinism(summarize, ctx):
    payload = {"text": "a long document " * 4, "model_config": SMALL,
               "max_length": 16}
    a = summarize(payload, ctx)
    b = summarize(payload, ctx)
    assert a["ok"] is True
    assert isinstance(a["summary"], str)
    assert a["model"] == "summarize-default"
    assert a["device"] in ("cpu", "tpu", "gpu")
    assert a["summary"] == b["summary"]


def test_batched(summarize, ctx):
    out = summarize(
        {"texts": ["first doc", "second doc", "third doc"],
         "model_config": SMALL, "max_length": 8},
        ctx,
    )
    assert out["ok"] is True
    assert len(out["summaries"]) == 3
    assert out["summary"] == out["summaries"][0]


def test_bad_inputs(summarize, ctx):
    assert summarize({}, ctx)["ok"] is False
    assert summarize({"text": ""}, ctx)["ok"] is False
    assert summarize({"texts": []}, ctx)["ok"] is False
    assert summarize({"text": "x", "max_length": 0}, ctx)["ok"] is False


def test_decode_single_trace():
    """The whole generate (encode + N decode steps) is ONE traced program:
    tracing the model function runs it exactly once regardless of step count."""
    cfg = seq2seq.Seq2SeqConfig(**SMALL)
    params = seq2seq.init_params(cfg, "trace-test")
    ids, mask = pad_batch([[1, 5, 6, 7, 2]])
    traces = {"n": 0}

    def fn(p, i, m):
        traces["n"] += 1
        return seq2seq.greedy_generate(p, i, m, cfg, 16)

    jitted = jax.jit(fn)
    toks, _ = jitted(params, ids, mask)
    toks2, _ = jitted(params, ids, mask)
    assert traces["n"] == 1  # one trace for 16 decode steps, and no retrace
    assert toks.shape == (1, 16)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))


def test_incremental_decode_matches_full_attention():
    """KV-cache decode must equal TRUE full-sequence decoder attention: the
    reference below reruns the whole prefix through the decoder blocks with a
    causal mask and NO cache, so a cache-update bug (e.g. a wrong
    dynamic_update_slice index) cannot cancel out between the two sides."""
    import jax.numpy as jnp

    from agent_tpu.models import layers

    cfg = seq2seq.Seq2SeqConfig(**SMALL, dtype="float32")
    params = seq2seq.init_params(cfg, "equiv-test")
    tok = ByteTokenizer()
    src = tok.encode("check equivalence", add_bos=True, add_eos=True)
    ids, mask = pad_batch([src])
    T = 8
    toks, _ = jax.jit(
        lambda p, i, m: seq2seq.greedy_generate(p, i, m, cfg, T)
    )(params, ids, mask)
    toks = np.asarray(toks)[0]

    @jax.jit
    def full_prefix_logits(prefix_ids):
        """Decoder over the whole prefix, full causal attention, cache-free.
        ONE program for every prefix: the prefix comes padded to ``T``, and
        the causal mask hides the padding from every position read (eagerly,
        each new length was a compile a primitive)."""
        dtype = cfg.compute_dtype
        L = prefix_ids.shape[1]
        x = params["embed"].astype(dtype)[prefix_ids] + \
            params["pos"][:L].astype(dtype)[None]
        causal = jnp.asarray(layers.causal_mask(L))                  # [1,1,L,L]
        enc_attn = jnp.asarray(mask)[:, None, None, :]
        enc_out = seq2seq.encode(params, jnp.asarray(ids), jnp.asarray(mask), cfg)
        for block in params["dec"]:
            x, _ = layers.decoder_block(block, x, causal, enc_out, enc_attn, dtype)
        x = layers.layer_norm(params["ln_dec"], x)
        logits = jnp.dot(x.astype(dtype), params["embed"].astype(dtype).T)
        return logits.astype(jnp.float32)                            # [1,L,V]

    prefix = [1]  # BOS
    for t in range(T):
        padded = np.zeros((1, T), np.int32)
        padded[0, :len(prefix)] = prefix
        logits = np.asarray(full_prefix_logits(padded))
        nxt = int(np.argmax(logits[0, len(prefix) - 1]))
        if toks[t] == 0:  # post-EOS padding
            break
        assert nxt == toks[t], f"step {t}: full-attn {nxt} != cached {toks[t]}"
        prefix.append(nxt)


class TestBeamSearch:
    CFG_KW = dict(
        vocab_size=64, d_model=32, n_heads=4, n_enc_layers=2, n_dec_layers=2,
        d_ff=64, max_src_len=16, max_tgt_len=8, dtype="float32",
    )

    def _setup(self):
        import numpy as np
        import jax.numpy as jnp

        from agent_tpu.models import seq2seq

        cfg = seq2seq.Seq2SeqConfig(**self.CFG_KW)
        params = seq2seq.init_params(cfg, model_id="beam-test")
        rng = np.random.default_rng(7)
        src = jnp.asarray(rng.integers(4, 64, size=(3, 16)), dtype=jnp.int32)
        mask = jnp.ones((3, 16), dtype=jnp.int32)
        return seq2seq, cfg, params, src, mask

    def test_beam1_equals_greedy(self):
        import numpy as np

        seq2seq, cfg, params, src, mask = self._setup()
        g_toks, g_len = seq2seq.greedy_generate(params, src, mask, cfg, 8)
        b_toks, b_len = seq2seq.beam_generate(
            params, src, mask, cfg, 8, num_beams=1
        )
        np.testing.assert_array_equal(np.asarray(g_toks), np.asarray(b_toks))
        np.testing.assert_array_equal(np.asarray(g_len), np.asarray(b_len))

    def test_beam4_runs_and_is_deterministic(self):
        import numpy as np

        seq2seq, cfg, params, src, mask = self._setup()
        t1, l1 = seq2seq.beam_generate(params, src, mask, cfg, 8, num_beams=4)
        t2, l2 = seq2seq.beam_generate(params, src, mask, cfg, 8, num_beams=4)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
        assert np.asarray(t1).shape == (3, 8)
        assert (np.asarray(l1) <= 8).all() and (np.asarray(l1) >= 0).all()
        # Valid token range and PAD-after-EOS structure per row.
        toks = np.asarray(t1)
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all()

    def test_beam_improves_or_matches_sum_logprob(self):
        """With length_penalty=0 the chosen beam's raw sum-logprob must be at
        least greedy's (greedy's path stays in the beam at every step until
        pruned only by K strictly better prefixes)."""
        import numpy as np
        import jax
        import jax.numpy as jnp

        seq2seq, cfg, params, src, mask = self._setup()

        def score_of(toks):
            """Sum logprob of forced decode along `toks` (teacher forcing)."""
            from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID

            B, T = toks.shape
            enc = seq2seq.encode(params, src, mask, cfg)
            caches = seq2seq._empty_cache(cfg, B)
            tok = jnp.full((B,), BOS_ID, dtype=jnp.int32)
            total = np.zeros(B, dtype=np.float64)
            alive = np.ones(B, dtype=bool)
            for t in range(T):
                logits, caches = seq2seq._decode_step(
                    params, tok, jnp.int32(t), enc, mask, caches, cfg
                )
                logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
                nxt = np.asarray(toks[:, t])
                for b in range(B):
                    if alive[b] and nxt[b] != PAD_ID:
                        total[b] += logp[b, nxt[b]]
                        if nxt[b] == EOS_ID:
                            alive[b] = False
                    elif nxt[b] == PAD_ID:
                        alive[b] = False
                tok = jnp.asarray(nxt, dtype=jnp.int32)
            return total

        g_toks, _ = seq2seq.greedy_generate(params, src, mask, cfg, 8)
        b_toks, _ = seq2seq.beam_generate(
            params, src, mask, cfg, 8, num_beams=4, length_penalty=0.0
        )
        gs = score_of(np.asarray(g_toks))
        bs = score_of(np.asarray(b_toks))
        assert (bs >= gs - 1e-4).all(), (bs, gs)

    def test_cache_reorder_delta_equals_gather(self):
        """The delta (lax.cond identity-skip) KV-cache reorder must emit
        BIT-IDENTICAL tokens to the unconditional per-step gather it
        replaced — same beam_idx, the only difference is whether identity
        permutations move cache bytes. Run across length penalties so both
        early-banking and run-to-the-end hypotheses are covered."""
        import numpy as np
        import jax.numpy as jnp

        from agent_tpu.models.decoding import beam_scan
        from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID

        seq2seq, cfg, params, src, mask = self._setup()
        B, K, T = src.shape[0], 4, 8
        enc_out = seq2seq.encode(params, src, mask, cfg)
        enc_out = jnp.repeat(enc_out, K, axis=0)
        enc_mask = jnp.repeat(mask, K, axis=0)

        def step_fn(tok, step, caches):
            return seq2seq._decode_step(
                params, tok, step, enc_out, enc_mask, caches, cfg
            )

        for lp in (0.0, 1.0, 2.0):
            outs = {}
            for scheme in ("gather", "delta"):
                toks, lens = beam_scan(
                    step_fn, seq2seq._empty_cache(cfg, B * K), B,
                    cfg.vocab_size, T, num_beams=K,
                    start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
                    length_penalty=lp, cache_reorder=scheme,
                )
                outs[scheme] = (np.asarray(toks), np.asarray(lens))
            np.testing.assert_array_equal(
                outs["delta"][0], outs["gather"][0],
                err_msg=f"token mismatch at length_penalty={lp}",
            )
            np.testing.assert_array_equal(outs["delta"][1], outs["gather"][1])

    def test_cache_reorder_rejects_unknown_scheme(self):
        import pytest

        from agent_tpu.models.decoding import beam_scan

        with pytest.raises(ValueError, match="cache_reorder"):
            beam_scan(
                lambda t, s, c: (None, c), None, 1, 8, 4,
                num_beams=2, start_id=1, eos_id=2,
                cache_reorder="sometimes",
            )

    def test_op_accepts_num_beams(self):
        from agent_tpu.ops import get_op

        summarize = get_op("map_summarize")
        payload = {
            "texts": ["beam search document " * 5] * 2,
            "max_length": 6,
            "num_beams": 4,
            "model_config": self.CFG_KW,
        }
        out = summarize(payload)
        assert out["ok"] is True and out["num_beams"] == 4
        assert len(out["summaries"]) == 2
        bad = summarize({**payload, "num_beams": 0})
        assert bad["ok"] is False


def test_summarize_from_csv_shard(tmp_csv):
    """source_uri shard addressing — the summarize half of the drain story."""
    import pytest as _pytest

    from agent_tpu.ops import get_op

    summarize = get_op("map_summarize")
    cfg_kw = {"vocab_size": 260, "d_model": 32, "n_heads": 4,
              "n_enc_layers": 2, "n_dec_layers": 2, "d_ff": 64,
              "max_src_len": 64, "max_tgt_len": 8, "dtype": "float32"}
    out = summarize({"source_uri": tmp_csv, "start_row": 1, "shard_size": 3,
                     "text_field": "text", "max_length": 4,
                     "model_config": cfg_kw})
    assert out["ok"] is True and len(out["summaries"]) == 3
    # Shard problems raise loudly (drain semantics), same as classify.
    with _pytest.raises(RuntimeError):
        summarize({"source_uri": tmp_csv, "start_row": 10_000,
                   "model_config": cfg_kw})
    with _pytest.raises(RuntimeError):
        summarize({"source_uri": tmp_csv, "text_field": "nope",
                   "model_config": cfg_kw})


def test_op_timings_flow_through_context():
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext

    ctx = OpContext()
    out = get_op("map_classify_tpu")({"texts": ["timing check"], "topk": 2}, ctx)
    assert out["ok"] is True
    t = ctx.tags["timings"]
    assert t["stage_ms"] >= 0 and t["device_ms"] > 0


def test_summarize_drain_blank_cells_get_empty_summaries(tmp_path):
    from agent_tpu.ops import get_op

    path = tmp_path / "blanks.csv"
    path.write_text('id,text\n0,"real document text"\n1,""\n2,"another doc"\n')
    out = get_op("map_summarize")({
        "source_uri": str(path), "shard_size": 3, "max_length": 4,
        "model_config": {"vocab_size": 260, "d_model": 32, "n_heads": 4,
                         "n_enc_layers": 2, "n_dec_layers": 2, "d_ff": 64,
                         "max_src_len": 64, "max_tgt_len": 8,
                         "dtype": "float32"},
    })
    assert out["ok"] is True
    assert out["summaries"][1] == ""          # blank cell → empty summary


def test_greedy_early_exit_equals_scan_path():
    """The while_loop early-exit decode must emit EXACTLY the fixed-trip
    scan's tokens — including rows that hit EOS at different steps and the
    pad tail after the early stop."""
    import jax.numpy as jnp
    import numpy as np

    from agent_tpu.models import decoding

    B, V, T = 4, 11, 12
    eos = 9

    # Scripted logits: row b emits token (step + b) % 7 + 1 until its EOS
    # step (2 + 2*b), then would emit garbage — EOS bookkeeping must pad.
    def step_fn(tok, step, caches):
        logits = jnp.full((B, V), -1e9, dtype=jnp.float32)
        for b in range(B):
            want = jnp.where(step == 2 + 2 * b, eos, (step + b) % 7 + 1)
            logits = logits.at[b, :].set(
                jnp.where(jnp.arange(V) == want, 0.0, -1e9)
            )
        return logits, caches

    kw = dict(batch=B, max_new_tokens=T, start_id=0, eos_id=eos, pad_id=0)
    toks_w, lens_w = decoding.greedy_scan(step_fn, None, early_exit=True, **kw)
    toks_s, lens_s = decoding.greedy_scan(step_fn, None, early_exit=False, **kw)
    np.testing.assert_array_equal(np.asarray(toks_w), np.asarray(toks_s))
    np.testing.assert_array_equal(np.asarray(lens_w), np.asarray(lens_s))
    # Longest row finishes at step 2 + 2*(B-1) = 8 < T: the early-exit tail
    # must be pad, proving the buffer semantics (not just luck).
    assert np.all(np.asarray(toks_w)[:, 9:] == 0)


def test_greedy_early_exit_under_jit_with_caches():
    """Early exit must compose with jit and a threaded KV-cache pytree
    (the real decode shape: caches in the while_loop carry)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agent_tpu.models import decoding

    B, V, T = 2, 8, 6
    eos = 7

    def step_fn(tok, step, caches):
        # Cache carries a running sum — proves the pytree threads through.
        caches = {"acc": caches["acc"] + tok.sum()}
        logits = jax.nn.one_hot(
            jnp.where(step >= 1, eos, (tok + 1) % V), V, dtype=jnp.float32
        )
        return jnp.log(logits + 1e-9), caches

    caches = {"acc": jnp.int32(0)}

    def run(early):
        return decoding.greedy_scan(
            step_fn, caches, batch=B, max_new_tokens=T,
            start_id=1, eos_id=eos, pad_id=0, early_exit=early,
        )

    toks_w, lens_w = jax.jit(lambda: run(True))()
    toks_s, lens_s = jax.jit(lambda: run(False))()
    np.testing.assert_array_equal(np.asarray(toks_w), np.asarray(toks_s))
    np.testing.assert_array_equal(np.asarray(lens_w), np.asarray(lens_s))
