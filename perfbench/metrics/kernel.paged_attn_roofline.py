"""kernel.paged_attn_roofline (%): the paged-attention kernel's share of its
roofline. Least time: for every token delivered in the traced window the
keys and values of its whole context read once (decoding is bound by the
memory bandwidth), and for every prefill chunk of the window's new requests
its queries over its context (bound by compute at chunk 512) — computed by
``peaks.attention_least_seconds`` from the client's records, not from the
program's own count. Time: the summed device time of the kernel's events,
found by the kernel's ``name=``. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import peaks, trace

KERNEL = "paged_attention"


def read(ctx):
    t = ctx.get("trace")
    work = ctx["counters"].get("trace_window")
    hit = trace.find(t["ops"], KERNEL) if t else None
    if not work or hit is None or not hit["sum_s"]:
        return None
    sizes, kind = ctx["sizes"], ctx["device"]["kind"]
    least = (peaks.kv_bytes_per_token(sizes) * work["decode_context_tokens"]
             / peaks.peak(kind)["hbm_bytes_per_s"])
    least += sum(peaks.attention_least_seconds(sizes, q, c, kind)
                 for q, c in work["prefill_chunks"])
    return 100.0 * least / hit["sum_s"]
