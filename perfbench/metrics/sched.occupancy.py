"""sched.occupancy (%): tokens generated in the window over decode steps
times slots (scheduler counters, deltas over the whole window). Layer:
scheduler. Moves serve_tokens_per_s."""


def read(ctx):
    d = ctx["counters"].get("delta", {})
    steps, slots = d.get("decode_steps"), ctx["counters"]["end"].get("slots")
    if not steps or not slots:
        return None
    return 100.0 * d["tokens_generated"] / (steps * slots)
