"""moe.held_route_share (%): of the routes the live rows chose over ALL the
router's experts, the share that landed on the experts this chip holds:
d``moe_rows_routed`` over d``moe_routes_chosen``. 50 where two chips share
each layer and routing is even; the rest is what the absent chip would
compute, left out here and in the reference alike. ``moe_routes_chosen`` is
live rows x top-k x expert layers exactly (the dropless identity, counted on
the device). A program that holds every expert, or one without the counter,
reads nothing. Layer: experts. Moves gap_p95_ms."""

from perfbench.lib import ssm_work


def read(ctx):
    return ssm_work.held_route_share_percent(ctx)
