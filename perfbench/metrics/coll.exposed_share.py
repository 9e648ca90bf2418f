"""coll.exposed_share (%): time in which a collective op (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) runs on a device
and no compute op does, over window_s; mean over the devices. Layer:
collectives. Moves train_tokens_per_s."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["devices"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
