"""kernel.global_attn_roofline (%): the paged-attention kernel's share of its
roofline in the layers of kind ``full_attention`` of a model that also has
window layers. Least time (``window_work.attention_least_seconds``): the
program's own counts for THOSE layers alone (``full_attn_step_keys``, the
whole context a decode row and full layer, keys and values read once;
``full_attn_chunk_pairs``, a chunk's causal pairs at the peak rate), brought
to the traced part as ``kernel.window_attn_roofline``'s are. Time: the summed
device time of the events named ``paged_attention``, which in such a model
are the full layers' calls and no other. (``kernel.paged_attn_roofline``
multiplies the client's contexts by ALL layers' keys and values: in a model
a quarter of whose layers are full it would read four times too high, which
is why this cell is not on its list.) A program without the counters or the
kernel reads nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import window_work


def read(ctx):
    return window_work.attention_roofline_percent(ctx, "full")
