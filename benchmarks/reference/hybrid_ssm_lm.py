"""Plain float32 reference of the decoder language model ``map_score_lm``
serves under ``mixer: hybrid_ssm`` (configuration ``falcon-h1-34b``): a
Mamba-2 state-space mixer and causal grouped-query attention side by side in
one block. Straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: the recurrence as a sequential
``lax.scan`` over TOKENS of the equations below (no chunks), attention as a
dense causal softmax over the whole document, the convolution as four shifted
multiply-adds; no kernel, no cache, no segments, no carried state. It imports
nothing of the program and takes nothing the program made: the weights come
from the model id by the rule the configuration's ``assumed.weights`` states,
written out again here (a layer at a time and the head by vocabulary blocks:
5.25 G parameters are 21 GB in float32), rounded once to bf16 and used in
float32. The key from the model id, the block sums and ``compare``'s first
three numbers are ``retention_lm.py``'s, as for every language-model
reference here.

It is blocked over queries, rows, layers and vocabulary ONLY so that it fits:
a block of queries sees every key up to the end of its run of blocks (the keys
after a query are masked, so leaving out those after the whole run changes
nothing), the feed-forward takes rows a block at a time. The blocks change no
arithmetic.

Equations, after the ``falcon_h1`` modelling code of the ``transformers``
library (x_t a token's residual; every ``*_multiplier`` at its published
value and place):

    x_0 = embedding_multiplier embed[id]
    h = RMSNorm(x)                                        eps rms_norm_eps
    x' = x + attention_out_multiplier Attn(attention_in_multiplier h)
           + ssm_out_multiplier SSM(ssm_in_multiplier h)
    x'' = x' + down_multiplier W_down(W_up n * silu(gate_multiplier W_gate n))
                                                          n = RMSNorm(x')
    logits = lm_head_multiplier head(RMSNorm(x_last))

Attn (Hq query heads a, Hkv key-value heads b = a // G, no bias, no head norm):

    q_a = RoPE(W_q h)_a    k_b = RoPE(key_multiplier W_k h)_b    v_b = (W_v h)_b
    o_{t,a} = sum_{s <= t} softmax_s(q_{t,a} . k_{s,b} / sqrt(D)) v_{s,b}
    Attn = W_o concat_a(o_a)           (rotary pairs (i, i + D/2), theta rope_theta)

SSM (Mamba-2: H heads j of P channels in ``ssm_n_groups`` groups g(j) that
share B and C, state N, convolution width K):

    [z | x | B | C | dt] = (W_in h) * [z, x, B, C, dt multipliers]
    [x | B | C] <- silu(conv_K([x | B | C]) + bias)       causal, depthwise
    dt_j = softplus(dt_j + dt_bias_j)      A_j = -exp(A_log_j)
    S_{t,j} = exp(dt_{t,j} A_j) S_{t-1,j} + dt_{t,j} x_{t,j} B_{t,g(j)}^T
    y_{t,j} = S_{t,j} C_{t,g(j)} + D_j x_{t,j}
    y <- GroupRMSNorm(y * silu(z)) * weight       (gate first, then the norm
                                                   over each group's channels)
    SSM = W_out y
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import manifest

_lm = manifest.load_reference("retention_lm")
seed_key = _lm.seed_key
block_sums, block_counts = _lm.block_sums, _lm.block_counts

# The family's leaves, in the order that keys them (this mixer's: wq, wk, wv,
# wo 2 .. 5, w_gate, w_up, w_down 7 .. 9, and the last four).
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
          "we_down", "w_ssm_in", "w_ssm_out", "conv_w", "conv_b")
LAYER = ("wq", "wk", "wv", "wo", "w_ssm_in", "w_ssm_out", "conv_w", "conv_b",
         "w_gate", "w_up", "w_down")
# The weight rule's two constants (the configuration's ``assumed.weights``).
QUERY_GAIN = 4.0
DT_RANGE = (0.001, 0.1)
LOSS_BLOCK = 1024
QUERY_BLOCK = 256
# Runs of query blocks, each held against its own prefix of the keys.
KEY_SPANS = 8
ROW_BLOCK = 4096
VOCAB_BLOCK = 8192


def ssm_parts(cfg: Mapping[str, Any]):
    """(columns, multiplier) of the in-projection: z, x, B, C, dt."""
    d_ssm = int(cfg["ssm_n_heads"]) * int(cfg["ssm_d_head"])
    bc = int(cfg["ssm_n_groups"]) * int(cfg["ssm_d_state"])
    return ((d_ssm, float(cfg["ssm_z_multiplier"])),
            (d_ssm, float(cfg["ssm_x_multiplier"])),
            (bc, float(cfg["ssm_b_multiplier"])),
            (bc, float(cfg["ssm_c_multiplier"])),
            (int(cfg["ssm_n_heads"]), float(cfg["ssm_dt_multiplier"])))


def leaf_shape(cfg: Mapping[str, Any], name: str):
    """(shape, fan_in) of one layer's leaf, or of a whole unlayered leaf."""
    d, f, V = int(cfg["d_model"]), int(cfg["d_ff"]), int(cfg["vocab_size"])
    hq = int(cfg["n_heads"]) * int(cfg["d_head"])
    hkv = int(cfg["n_kv_heads"]) * int(cfg["d_head"])
    d_ssm = int(cfg["ssm_n_heads"]) * int(cfg["ssm_d_head"])
    conv = d_ssm + 2 * int(cfg["ssm_n_groups"]) * int(cfg["ssm_d_state"])
    k = int(cfg["ssm_d_conv"])
    return {
        "embed": ((V, d), 1), "head": ((V, d), d),
        "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
        "wo": ((hq, d), hq),
        "w_ssm_in": ((d, sum(n for n, _ in ssm_parts(cfg))), d),
        "w_ssm_out": ((d_ssm, d), d_ssm),
        "conv_w": ((k, conv), k), "conv_b": ((conv,), k),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }[name]


def leaf_scale(cfg: Mapping[str, Any], name: str):
    """What the leaf's standard normal is multiplied by: ``1/sqrt(fan_in)``
    times the INVERSE of every multiplier the forward pass applies to the
    leaf's output (so that random weights leave every branch at the
    residual's size and the logits at unit spread, as trained ones do under
    their multipliers), and ``QUERY_GAIN`` on the queries. A float computed
    in float64, or a float32 vector along the output axis."""
    m = lambda key: float(cfg[key])  # noqa: E731
    fan_in = leaf_shape(cfg, name)[1]
    root = np.sqrt(max(1, fan_in))
    a_in, s_in = m("attention_in_multiplier"), m("ssm_in_multiplier")
    if name == "w_ssm_in":
        return np.concatenate([np.full(n, (1.0 / (s_in * mult)) / root,
                                       np.float32)
                               for n, mult in ssm_parts(cfg)])
    gain = {
        "embed": 1.0 / m("embedding_multiplier"),
        "head": 1.0 / m("lm_head_multiplier"),
        "wq": QUERY_GAIN / a_in,
        "wk": 1.0 / (a_in * m("key_multiplier")),
        "wv": 1.0 / a_in,
        "wo": 1.0 / m("attention_out_multiplier"),
        "w_ssm_out": 1.0 / m("ssm_out_multiplier"),
        "w_gate": 1.0 / m("mlp_gate_multiplier"),
        "w_down": 1.0 / m("mlp_down_multiplier"),
    }.get(name, 1.0)
    return gain / root


_DRAW: Dict[Any, Any] = {}


def draw(cfg: Mapping[str, Any], model_id: str, name: str, layer=None):
    """One leaf as the configuration defines it: normal(key) in float32 times
    its scale, rounded once to the stored dtype and kept in it. Key:
    fold_in(root, index of the leaf), then fold_in(., layer)."""
    import jax
    import jax.numpy as jnp

    shape, _ = leaf_shape(cfg, name)
    scale = leaf_scale(cfg, name)
    dtype = jnp.dtype(str(cfg.get("dtype", "bfloat16")))
    sig = (name, shape, str(dtype), np.asarray(scale).tobytes())
    if sig not in _DRAW:
        _DRAW[sig] = jax.jit(lambda key: (
            jax.random.normal(key, shape, dtype=jnp.float32) * scale
        ).astype(dtype))
    key = jax.random.fold_in(seed_key(model_id), LEAVES.index(name))
    if layer is not None:
        key = jax.random.fold_in(key, int(layer))
    return _DRAW[sig](key)


def scan_constants(n_heads: int):
    """What ``config.json`` does not state, by the Mamba-2 initialisation
    written as a rule: ``A_log = log(1 .. H)``, ``D = 1``, and ``dt_bias`` the
    inverse softplus of a step spaced log-uniformly over the heads."""
    lo, hi = DT_RANGE
    dt = np.exp(np.linspace(np.log(lo), np.log(hi), n_heads))
    return {"A_log": np.log(np.arange(1, n_heads + 1, dtype=np.float32)),
            "D": np.ones((n_heads,), np.float32),
            "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32)}


# ---- the mathematics -----------------------------------------------------

def rms_norm(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """x [L, H, D]; pairs (i, i + D/2), angle position / theta^(2i/D)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def causal_attention(q, k, v, query_block=QUERY_BLOCK, spans=KEY_SPANS):
    """q [L, Hq, D], k, v [L, Hkv, D] → [L, Hq, D]: every query against
    every key at or before it, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    L, hq, d = q.shape
    hkv = k.shape[1]
    bq = min(int(query_block), L)
    n_blocks = -(-L // bq)
    pad = n_blocks * bq - L
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, bq, hkv, hq // hkv, d)
    tp = jnp.arange(n_blocks * bq).reshape(n_blocks, bq)

    def block(args, n_keys):
        qb, t = args
        s = jnp.einsum("tbgd,sbd->tbgs", qb, k[:n_keys]) / np.sqrt(d)
        causal = jnp.arange(n_keys)[None, :] <= t[:, None]
        p = jax.nn.softmax(
            jnp.where(causal[:, None, None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("tbgs,sbd->tbgd", p, v[:n_keys])

    outs = []
    per = -(-n_blocks // max(1, min(int(spans), n_blocks)))
    for first in range(0, n_blocks, per):
        last = min(n_blocks, first + per)
        n_keys = min(L, last * bq)
        outs.append(jax.lax.map(lambda a, n=n_keys: block(a, n),
                                (qp[first:last], tp[first:last])))
    return jnp.concatenate(outs, 0).reshape(n_blocks * bq, hq, d)[:L]


def causal_conv(u, w, b):
    """u [L, C], w [K, C], b [C]: out_t = b + sum_i w[i] u_{t-(K-1)+i}, as K
    shifted multiply-adds (zeros before the document)."""
    import jax.numpy as jnp

    L = u.shape[0]
    K = w.shape[0]
    out = jnp.broadcast_to(b, u.shape)
    for i in range(K):
        shift = K - 1 - i
        out = out + w[i] * jnp.pad(u, ((shift, 0), (0, 0)))[:L]
    return out


def ssm_scan(x, dt, A, B, C, D):
    """The recurrence, one token after another. x [L, H, P], dt [L, H], A, D
    [H], B, C [L, G, N] → y [L, H, P]; the state S [H, P, N] starts at 0."""
    import jax
    import jax.numpy as jnp

    L, H, P = x.shape
    G, N = B.shape[1:]
    group = np.arange(H) // (H // G)

    def step(S, xs):
        x_t, dt_t, B_t, C_t = xs
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            dt_t[:, None, None] * x_t[:, :, None] * B_t[group][:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", S, C_t[group]) + D[:, None] * x_t
        return S, y_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C))
    return y


def mixer_forward(cfg, w, x):
    """x [L, d] → x + the two branches."""
    import jax
    import jax.numpy as jnp

    m = lambda key: float(cfg[key])  # noqa: E731
    L = x.shape[0]
    hq, hkv, dh = int(cfg["n_heads"]), int(cfg["n_kv_heads"]), int(cfg["d_head"])
    H, P = int(cfg["ssm_n_heads"]), int(cfg["ssm_d_head"])
    G, N = int(cfg["ssm_n_groups"]), int(cfg["ssm_d_state"])
    eps, theta = m("rms_norm_eps"), m("rope_theta")
    pos = jnp.arange(L)
    h = rms_norm(x, eps)

    ha = h * m("attention_in_multiplier")
    q = rope((ha @ w["wq"]).reshape(L, hq, dh), pos, theta)
    k = rope(((ha @ w["wk"]) * m("key_multiplier")).reshape(L, hkv, dh),
             pos, theta)
    v = (ha @ w["wv"]).reshape(L, hkv, dh)
    attended = causal_attention(q, k, v).reshape(L, hq * dh) @ w["wo"]

    d_ssm = H * P
    mup = np.concatenate([np.full(n, mult, np.float32)
                          for n, mult in ssm_parts(cfg)])
    proj = ((h * m("ssm_in_multiplier")) @ w["w_ssm_in"]) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * G * N], axis=1)
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
    xs, B, C = jnp.split(xbc, [d_ssm, d_ssm + G * N], axis=1)
    y = ssm_scan(xs.reshape(L, H, P), jax.nn.softplus(dt + w["dt_bias"]),
                 -jnp.exp(w["A_log"]), B.reshape(L, G, N), C.reshape(L, G, N),
                 w["D"]).reshape(L, d_ssm)
    # mamba_norm_before_gate false: the gate, then the norm a group.
    y = (y * jax.nn.silu(z)).reshape(L, G, d_ssm // G)
    y = rms_norm(y, eps).reshape(L, d_ssm)          # the norm's weight is 1
    scanned = y @ w["w_ssm_out"]

    return x + m("attention_out_multiplier") * attended + m(
        "ssm_out_multiplier") * scanned


def ffn_forward(cfg, w, x, row_block=ROW_BLOCK):
    """x [L, d] → x + the feed-forward, rows a block at a time."""
    import jax
    import jax.numpy as jnp

    m = lambda key: float(cfg[key])  # noqa: E731
    L, d = x.shape
    rows = min(int(row_block), L)
    n_blocks = -(-L // rows)
    xp = jnp.pad(x, ((0, n_blocks * rows - L), (0, 0)))

    def block(xb):
        n = rms_norm(xb, m("rms_norm_eps"))
        ff = (n @ w["w_up"]) * jax.nn.silu(
            m("mlp_gate_multiplier") * (n @ w["w_gate"]))
        return xb + m("mlp_down_multiplier") * (ff @ w["w_down"])

    return jax.lax.map(block, xp.reshape(n_blocks, rows, d)).reshape(
        n_blocks * rows, d)[:L]


def head_logprobs(h, head, targets, multiplier, vocab_block=VOCAB_BLOCK,
                  row_block=ROW_BLOCK):
    """log p(target) for every row of h [N, d] over the whole vocabulary
    head [V, d], logits = multiplier x (h . head); float32. Rows a block at a
    time and under each the vocabulary folded into a running log-sum-exp a
    block after another (a loop, so that ONE [rows, vocab_block] block of
    logits exists at a time: unrolled, 32 blocks of [65,535, 8,192] are
    scheduled side by side and ask for 64 GB)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    N, d = h.shape
    V = head.shape[0]
    vb = min(int(vocab_block), V)
    n_full, tail = divmod(V, vb)
    rows = min(int(row_block), N)
    n_blocks = -(-N // rows)
    pad = n_blocks * rows - N

    def block(args):
        hb, tb = args                                  # [rows, d], [rows]

        def fold(carry, w, offset):
            m, l, hit = carry
            logits = multiplier * (hb @ w.astype(f32).T)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            l = l * jnp.exp(m - m_new) + jnp.exp(
                logits - m_new[:, None]).sum(-1)
            inside = (tb >= offset) & (tb < offset + w.shape[0])
            picked = jnp.take_along_axis(
                logits, jnp.clip(tb - offset, 0, w.shape[0] - 1)[:, None],
                1)[:, 0]
            return m_new, l, hit + jnp.where(inside, picked, 0.0)

        carry = (jnp.full((rows,), -jnp.inf, f32), jnp.zeros((rows,), f32),
                 jnp.zeros((rows,), f32))
        carry = jax.lax.fori_loop(
            0, n_full, lambda i, c: fold(c, jax.lax.dynamic_slice_in_dim(
                head, i * vb, vb, 0), i * vb), carry)
        if tail:
            carry = fold(carry, head[n_full * vb:], n_full * vb)
        m, l, hit = carry
        return hit - (m + jnp.log(l))

    out = jax.lax.map(block, (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blocks, rows, d),
        jnp.pad(targets, (0, pad)).reshape(n_blocks, rows)))
    return out.reshape(n_blocks * rows)[:N]


_JIT: Dict[Any, Any] = {}


def _jitted(name, fn, cfg):
    import jax

    key = (name, tuple(sorted((k, str(v)) for k, v in cfg.items())))
    if key not in _JIT:
        _JIT[key] = jax.jit(fn)
    return _JIT[key]


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]], parts=("attention", "ssm")):
    """The final-normed hidden states [L, d] (float32) of each document,
    layer by layer over all the documents. ``parts``: the branches a layer
    keeps (both: the model; one: what the block would be WITHOUT the other,
    for the tests' proof that the check sees each). Call under
    ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    n_layers, eps = int(cfg["n_layers"]), float(cfg["rms_norm_eps"])
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(f32) * float(cfg["embedding_multiplier"])
          for d in docs]
    del embed
    run = dict(cfg)
    if "attention" not in parts:
        run["attention_out_multiplier"] = 0.0
    if "ssm" not in parts:
        run["ssm_out_multiplier"] = 0.0
    mixer = _jitted("mixer", lambda w, x: mixer_forward(run, w, x), run)
    ffn = _jitted("ffn", lambda w, x: ffn_forward(run, w, x), run)
    constants = {k: jnp.asarray(v) for k, v in scan_constants(
        int(cfg["ssm_n_heads"])).items()}
    for i in range(n_layers):
        w = {name: draw(cfg, model_id, name, layer=i).astype(f32)
             for name in LAYER[:8]}
        xs = [mixer({**w, **constants}, x) for x in xs]
        del w
        w = {name: draw(cfg, model_id, name, layer=i).astype(f32)
             for name in LAYER[8:]}
        xs = [ffn(w, x) for x in xs]
        del w
    return [rms_norm(x, eps) for x in xs]


def token_logprobs(cfg: Mapping[str, Any], model_id: str,
                   docs: Sequence[Sequence[int]], parts=("attention", "ssm")
                   ) -> List[np.ndarray]:
    """For each document (a sequence of token ids) the float32 array of
    log p(token_t | tokens before t), t = 1 .. L-1."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    docs = [np.asarray(d, np.int32) for d in docs]
    with jax.default_matmul_precision("highest"):
        hs = hidden_states(cfg, model_id, docs, parts)
        head = draw(cfg, model_id, "head")
        mult = float(cfg["lm_head_multiplier"])
        score = _jitted("head", lambda h, w, t: head_logprobs(h, w, t, mult),
                        cfg)
        return [np.asarray(score(h[:-1], head, jnp.asarray(d[1:])))
                if len(d) > 1 else np.zeros((0,), np.float32)
                for h, d in zip(hs, docs)]


def logits(cfg: Mapping[str, Any], model_id: str, doc: Sequence[int],
           positions: Sequence[int]) -> np.ndarray:
    """The whole-vocabulary logits [len(positions), V] (float32) that the
    given positions of one document give for their NEXT token."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, model_id, [np.asarray(doc, np.int32)])[0]
        head = draw(cfg, model_id, "head").astype(jnp.float32)
        return np.asarray(float(cfg["lm_head_multiplier"]) * (
            h[jnp.asarray(list(positions))] @ head.T))


def compare(served: Sequence[Sequence[float]],
            reference: Sequence[Sequence[float]], n_tokens: Sequence[int],
            block: int = LOSS_BLOCK) -> Dict[str, float]:
    """``retention_lm.compare``'s three numbers (bias, largest gap, slope of
    the gap e = (served - reference) / targets of a block, nats a token) and
    ``block_logprob_gap_rms``, the root mean square of e over all blocks of
    all documents: softmax attention and a linear recurrence make no discrete
    choice, so every block's gap is rounding alone, and a lower precision
    raises all of them; the mean square tells the two apart with less
    scatter than the largest single block."""
    out = _lm.compare(served, reference, n_tokens, block)
    if out:
        gaps = np.concatenate([
            (np.asarray(s, np.float64) - np.asarray(r, np.float64))
            / np.maximum(block_counts(n, block), 1.0)
            for s, r, n in zip(served, reference, n_tokens)])
        out["block_logprob_gap_rms"] = float(np.sqrt(np.mean(gaps ** 2)))
    return out
