"""client.ttft_p95_ms (ms): time from when a request was due (open loop) or
sent (closed loop) to its first streamed token, 95th percentile over the
requests due in the window, on the client's clock. Not an end-to-end metric
yet: at the parent's speed a window holds some tens of requests, and a 95th
percentile of those swings by tens of percent. Layer: handle, router and
replica. Moves serve_tokens_per_s."""


def read(ctx):
    return ctx["counters"].get("window", {}).get("ttft_p95_ms")
