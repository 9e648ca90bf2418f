"""client.tokens_per_s (tokens/s): output tokens delivered to the clients
per second of the window, in the TRACED run (so with the profiler's drag of
about 2% on it), for a cell whose rate is not held to a bound: with every
slot full the rate is ``slots`` over the mean time of a step, which the gaps
read too. Layer: handle, router and replica. Moves gap_p95_ms."""


def read(ctx):
    return ctx["counters"].get("window", {}).get("serve_tokens_per_s")
