"""Tokenizers and batch padding.

The reference's ``map_tokenize`` is not a real tokenizer — it chunks characters
into fixed windows (reference ``ops/map_tokenize.py:6-9,24``); real tokenization
happened only inside torch/transformers for summarize (reference
``ops/map_summarize.py:49``). BASELINE.json upgrades the tokenize slot to a real
tokenizer. Constraints here: zero egress (no HF hub), deterministic, fast on
host, and producing **static shapes** for pjit (padding buckets, so ragged text
doesn't retrace the compiled op — SURVEY.md §7 "hard parts").

Two tokenizers:

- :class:`ByteTokenizer` — vocab-free byte-level tokenizer (256 byte ids +
  specials). Reversible, language-agnostic, no artifacts. Default everywhere.
- :class:`WordPieceTokenizer` — greedy longest-prefix wordpiece over a loadable
  vocab (one token per line, ``##`` continuation), with a corpus-trainer for
  tests and local vocab building. API-compatible with BERT-style vocab files so
  real vocabs drop in when present on disk.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# Special token ids are shared by both tokenizers so models don't care which
# produced their input.
PAD_ID = 0
BOS_ID = 1
EOS_ID = 2
UNK_ID = 3
N_SPECIAL = 4

SPECIAL_TOKENS = ("<pad>", "<bos>", "<eos>", "<unk>")

# Default padding buckets: powers of two from 16 up. One compiled executable per
# bucket per batch size — the executable cache stays small and recompiles stop
# once the buckets are warm.
# Powers of two PLUS their midpoints: a pure pow2 ladder pads a row just
# over a bucket edge to twice its length (a ~70-byte row in the 128 bucket:
# ~44% of every matmul is padding). A ratio-1.5 ladder caps the worst-case
# pad multiplier at ~1.5× (a 65-token row pads to 96 = 1.48×) vs the pow2
# ladder's 2×. What the finer ladder gains in rows/s: not measured on the
# present tree (PERF.md §7: lengths neither 64 nor whole 128s, no cell); all entries stay multiples of 8 (TPU sublane)
# and the ≥2048 ones multiples of 512 (the flash kernel's tile
# divisibility gate).
DEFAULT_BUCKETS = (
    16, 32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536, 2048,
    3072, 4096,
)


class ByteTokenizer:
    """UTF-8 byte-level tokenizer: id = byte + N_SPECIAL. Vocab size 260."""

    vocab_size = 256 + N_SPECIAL
    pad_id, bos_id, eos_id, unk_id = PAD_ID, BOS_ID, EOS_ID, UNK_ID

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]:
        ids = [b + N_SPECIAL for b in text.encode("utf-8")]
        if add_bos:
            ids.insert(0, BOS_ID)
        if add_eos:
            ids.append(EOS_ID)
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        raw = bytes(i - N_SPECIAL for i in ids if i >= N_SPECIAL)
        return raw.decode("utf-8", errors="replace")


_WORD_RE = re.compile(r"\w+|[^\w\s]", re.UNICODE)


@dataclass
class WordPieceTokenizer:
    """Greedy longest-match wordpiece (BERT-style ``##`` continuations)."""

    vocab: Dict[str, int] = field(default_factory=dict)
    lowercase: bool = True
    max_word_chars: int = 64

    pad_id, bos_id, eos_id, unk_id = PAD_ID, BOS_ID, EOS_ID, UNK_ID

    def __post_init__(self) -> None:
        if not self.vocab:
            self.vocab = {t: i for i, t in enumerate(SPECIAL_TOKENS)}

    @property
    def vocab_size(self) -> int:
        return len(self.vocab)

    @classmethod
    def from_file(cls, path: str, lowercase: bool = True) -> "WordPieceTokenizer":
        """Load a BERT-style vocab file: one token per line, id = line number."""
        vocab: Dict[str, int] = {}
        with open(path, "r", encoding="utf-8") as f:
            for i, line in enumerate(f):
                vocab[line.rstrip("\n")] = i
        return cls(vocab=vocab, lowercase=lowercase)

    def save(self, path: str) -> None:
        inv = sorted(self.vocab.items(), key=lambda kv: kv[1])
        with open(path, "w", encoding="utf-8") as f:
            for tok, _ in inv:
                f.write(tok + "\n")

    @classmethod
    def train(
        cls,
        corpus: Iterable[str],
        vocab_size: int = 8192,
        lowercase: bool = True,
    ) -> "WordPieceTokenizer":
        """Frequency-based wordpiece trainer: whole words by count, then all
        single characters (with ``##`` variants) as the fallback alphabet.

        Not BPE-merge-optimal — it is a deterministic, dependency-free trainer
        good enough to build local vocabs for tests and demos.
        """
        counts: Dict[str, int] = {}
        chars: Dict[str, int] = {}
        for text in corpus:
            if lowercase:
                text = text.lower()
            for w in _WORD_RE.findall(text):
                counts[w] = counts.get(w, 0) + 1
                # Register both positional variants of every character so any
                # word over the seen alphabet is always encodable piece-wise.
                for c in w:
                    chars[c] = chars.get(c, 0) + 1
                    chars["##" + c] = chars.get("##" + c, 0) + 1
        vocab: Dict[str, int] = {t: i for i, t in enumerate(SPECIAL_TOKENS)}
        # Alphabet first so every word is always encodable.
        for piece in sorted(chars, key=lambda p: (-chars[p], p)):
            if len(vocab) >= vocab_size:
                break
            vocab.setdefault(piece, len(vocab))
        for w in sorted(counts, key=lambda w: (-counts[w], w)):
            if len(vocab) >= vocab_size:
                break
            vocab.setdefault(w, len(vocab))
        return cls(vocab=vocab, lowercase=lowercase)

    def _encode_word(self, word: str) -> List[int]:
        if len(word) > self.max_word_chars:
            return [self.unk_id]
        ids: List[int] = []
        start = 0
        while start < len(word):
            end = len(word)
            piece_id = None
            while end > start:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                pid = self.vocab.get(piece)
                if pid is not None:
                    piece_id = pid
                    break
                end -= 1
            if piece_id is None:
                return [self.unk_id]
            ids.append(piece_id)
            start = end
        return ids

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]:
        if self.lowercase:
            text = text.lower()
        ids: List[int] = []
        if add_bos:
            ids.append(self.bos_id)
        for w in _WORD_RE.findall(text):
            ids.extend(self._encode_word(w))
        if add_eos:
            ids.append(self.eos_id)
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        inv = {i: t for t, i in self.vocab.items()}
        out: List[str] = []
        for i in ids:
            tok = inv.get(int(i))
            if tok is None or tok in SPECIAL_TOKENS:
                continue
            if tok.startswith("##") and out:
                out[-1] += tok[2:]
            else:
                out.append(tok)
        return " ".join(out)


def byte_encode_pad(
    texts: Sequence[str],
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    batch_buckets: Optional[Sequence[int]] = None,
    max_len_cap: Optional[int] = None,
    add_bos: bool = False,
    add_eos: bool = False,
    raw_uint8: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fused byte-tokenize + pad: texts → (ids[B, L] int32, lengths[B] int32).

    The hot-path replacement for ``ByteTokenizer.encode`` + ``pad_batch`` on
    large batches: each row is one ``np.frombuffer`` over the UTF-8 bytes
    (C speed) instead of a per-byte Python list — same ids (byte + N_SPECIAL),
    same bucketed static shapes, same truncation semantics (BOS/EOS count
    toward the cap, exactly like ``encode(add_bos, add_eos)[:cap]``). Returns
    per-row lengths (not a mask): the device path rebuilds the mask on-chip.

    ``raw_uint8=True`` returns the UNSHIFTED bytes as uint8 — the minimal
    host→device wire format (1 byte/token instead of 2): the compiled
    program reconstructs ``ids = (raw + N_SPECIAL) *
    mask`` on device (see ``map_classify_tpu``), which is exact because with
    no BOS/EOS every non-pad id is ``byte + N_SPECIAL`` and the mask already
    distinguishes a body NUL byte (raw 0, masked in) from padding (raw 0,
    masked out). Incompatible with ``add_bos``/``add_eos``.
    """
    if raw_uint8 and (add_bos or add_eos):
        raise ValueError("raw_uint8 wire cannot carry BOS/EOS specials")
    cap = max_len_cap if max_len_cap is not None else buckets[-1]
    off = int(add_bos)
    bufs = [t.encode("utf-8") for t in texts]
    rows = len(bufs)
    lens = np.fromiter((len(b) for b in bufs), dtype=np.int64, count=rows)
    # Exactly encode(add_bos, add_eos)[:cap] then pad_batch: BOS/EOS join the
    # stream before truncation (a too-long text loses its EOS), and rows
    # truncate again to the top bucket when cap exceeds it (bucket_length's
    # "callers truncate to it" contract).
    totals = np.minimum(off + lens + int(add_eos), cap)
    L = bucket_length(max(1, int(totals.max()) if rows else 1), buckets)
    totals = np.minimum(totals, L)
    B = bucket_length(max(1, rows), batch_buckets) if batch_buckets else rows
    ids = np.zeros((B, L), dtype=np.uint8 if raw_uint8 else np.int32)
    lengths = np.zeros(B, dtype=np.int32)
    lengths[:rows] = totals
    nb = np.zeros(B, dtype=np.int64)
    nb[:rows] = np.maximum(totals - off, 0)
    nb[:rows] = np.minimum(nb[:rows], lens)
    if rows:
        # One vectorized scatter instead of a per-row copy loop: all texts
        # join into one flat byte view, and each row r pulls its
        # flat[start_r : start_r + nb_r] slice via a masked gather — ~3
        # array passes over [B, L] (a few ms at 8k×128) vs 8k Python
        # iterations.
        flat = np.frombuffer(b"".join(bufs), dtype=np.uint8)
        starts = np.zeros(rows, dtype=np.int64)
        if rows > 1:
            np.cumsum(lens[:-1], out=starts[1:])
        cols = np.arange(L, dtype=np.int64)[None, :]
        body = (cols >= off) & (cols < off + nb[:rows, None])
        src = starts[:, None] + (cols - off)
        if flat.size:
            ids[:rows][body] = flat[np.clip(src, 0, flat.size - 1)][body]
    if raw_uint8:
        return ids, lengths
    cols = np.arange(L)[None, :]
    body = (cols >= off) & (cols < off + nb[:, None])
    ids[body] += N_SPECIAL                     # every body byte, NULs included
    if add_bos and rows:
        ids[:rows, 0][totals > 0] = BOS_ID
    if add_eos and rows:
        fits = np.flatnonzero(off + lens + 1 <= np.minimum(cap, L))
        ids[fits, (off + nb[fits]).astype(np.int64)] = EOS_ID
    return ids, lengths


def bucket_length(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket ≥ n (or the largest bucket — callers truncate to it)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pad_batch(
    seqs: Sequence[Sequence[int]],
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    pad_id: int = PAD_ID,
    batch_buckets: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Ragged int lists → (ids[B, L], mask[B, L]) with bucketed static shapes.

    L is the smallest length bucket covering the longest sequence (longer
    sequences are truncated to the top bucket). If ``batch_buckets`` is given,
    B is also bucketed, with all-pad rows appended — both dims then come from
    small fixed sets, so the jit executable cache stays warm (SURVEY.md §7).
    """
    max_len = max((len(s) for s in seqs), default=1)
    L = bucket_length(max(1, max_len), buckets)
    rows = len(seqs)
    B = bucket_length(max(1, rows), batch_buckets) if batch_buckets else rows
    ids = np.full((B, L), pad_id, dtype=np.int32)
    mask = np.zeros((B, L), dtype=np.int32)
    for r, s in enumerate(seqs):
        s = list(s)[:L]
        ids[r, : len(s)] = s
        mask[r, : len(s)] = 1
    return ids, mask


def get_tokenizer(kind: str = "byte", vocab_path: Optional[str] = None):
    """Factory used by ops: ``byte`` (default), ``wordpiece`` (needs a
    vocab.txt path), or ``bpe`` (GPT-2/BART byte-level BPE; needs a
    directory holding vocab.json + merges.txt, e.g. an HF checkpoint dir)."""
    if kind == "byte":
        return ByteTokenizer()
    if kind == "wordpiece":
        if vocab_path:
            return WordPieceTokenizer.from_file(vocab_path)
        raise ValueError("wordpiece tokenizer requires vocab_path")
    if kind == "bpe":
        if vocab_path:
            from agent_tpu.models.bpe import ByteLevelBPE

            return ByteLevelBPE.from_dir(vocab_path)
        raise ValueError(
            "bpe tokenizer requires vocab_path (dir with vocab.json + merges.txt)"
        )
    raise ValueError(f"unknown tokenizer kind {kind!r}")
