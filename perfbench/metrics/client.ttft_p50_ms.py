"""client.ttft_p50_ms (ms): time from when a request was due (open loop) or
sent (closed loop) to its first streamed token, median over the requests due
in the window, on the client's clock (in the documents cell: the path of a
request whose document the prefix cache holds). Layer: handle, router and
replica. Moves serve_tokens_per_s."""


def read(ctx):
    return ctx["counters"].get("window", {}).get("ttft_p50_ms")
