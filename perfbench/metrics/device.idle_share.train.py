"""device.idle_share.train (%): 1 - busy_s / window_s of the traced steps;
busy is the union of device-op intervals on one device, the mean over
devices on four chips. Layer: device. Moves train_tokens_per_s."""


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
