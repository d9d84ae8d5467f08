"""Layer: Ops (ops/map_score_lm.py). The accepted
``causal_attention_pair_share.drain`` read in the ``mistral-small-4-119b``
cell: 100 x causal / computed of ``causal_attention_pairs_total{kind}``,
which the ``dense_mla`` mixer ticks at the query tile one head a key head
takes (a whole 4,096-token segment: 94.1 at 65,536 tokens; 99.2 at falcon's 512). An entry of its
own for the reason ``latent_causal_attention_roofline`` gives; the reader is
the accepted one. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("causal_attention_pair_share.drain").read
