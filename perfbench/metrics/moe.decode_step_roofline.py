"""moe.decode_step_roofline (%): the least time of a decode step over the
median device time of the scheduler's decode program
(``jit_paged_decode_step``) in the traced window. Least time
(``moe_work.decode_step_least_seconds``): the bytes a step must read once at
the memory's bandwidth — per layer the attention and norm weights, the
router and the experts that received a row (the mean a layer-call over the
window, from the program's counters ``moe_experts_hit`` /
``moe_layer_calls``; a prefill chunk's layer-calls, a few percent of them,
hit nearly every expert, as a full decode batch does), the output head, and
the keys and values of the live contexts (from the client's records: the
contexts of the tokens delivered in the traced window, over its decode
steps). A program without the counters or without a named decode program
reads 0. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import layers, moe_work, peaks


def read(ctx):
    d = moe_work.counters(ctx)
    hit = layers.program(ctx, "paged_decode_step") if ctx.get("trace") else None
    if d is None or hit is None or not hit["median_s"]:
        return 0.0
    work = ctx["counters"].get("trace_window") or {}
    kv_bytes = (work.get("decode_context_tokens", 0) / max(hit["count"], 1)
                * peaks.kv_bytes_per_token(ctx["sizes"]))
    least = moe_work.decode_step_least_seconds(
        ctx["config"], d["moe_experts_hit"] / d["moe_layer_calls"], kv_bytes,
        ctx["device"]["kind"])
    return 100.0 * least / hit["median_s"]
