"""sched.prefix_hit_share (%): prompt tokens the radix prefix cache served
(delta of ``prefix_hit_tokens`` over the window) over the prompt tokens of
the requests due in the window. Layer: scheduler. Moves ttft_p95_ms."""


def read(ctx):
    hit = ctx["counters"].get("delta", {}).get("prefix_hit_tokens")
    prompt = ctx["counters"].get("window", {}).get("prompt_tokens")
    if hit is None or not prompt:
        return None
    return 100.0 * hit / prompt
