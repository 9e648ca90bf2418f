"""kernel.paged_attn_roofline.loop (%): the paged-attention kernel's share of
its roofline in a LOOPED model, where a token's keys and values lie once a
(pass, layer): ``kernel.paged_attn_roofline``'s arithmetic with a token's
bytes times the passes (``loop_work.attention_least_seconds``: ``peaks
.kv_bytes_per_token`` counts a layer once and is not edited) — for every
token delivered in the traced window its whole context read once in every
pool, for every prefill chunk of the window's new requests its queries over
its context — over the summed device time of the events named
``paged_attention`` (the kernel keeps its name inside the loop's body). A
configuration without passes, or a trace without the kernel, reads nothing.
Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import loop_work


def read(ctx):
    return loop_work.attention_roofline_percent(ctx)
