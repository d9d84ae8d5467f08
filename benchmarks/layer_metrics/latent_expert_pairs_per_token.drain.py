"""Layer: Ops (ops/map_score_lm.py; models/moe.py). The accepted
``expert_pairs_per_token.drain`` read in the ``mistral-small-4-119b`` cell:
``moe_expert_pairs_total`` over ``moe_tokens_total``; 1.0 if the softmax
router spreads its 4 choices evenly over 128 experts of which 32 are held.
An entry of its own for the reason ``latent_expert_ffn_roofline`` gives; the
reader is the accepted one. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("expert_pairs_per_token.drain").read
