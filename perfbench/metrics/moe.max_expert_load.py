"""moe.max_expert_load (%): the rows of the fullest expert over the mean
rows an expert received, a layer-call (one expert layer in one program
run), over the window: the program's counters ``moe_max_expert_rows`` (the
fullest expert's rows, summed over layer-calls) over ``moe_rows_routed`` /
experts (the mean's sum). 100 is perfect balance; a decode step of 32 rows
x 8 over 64 experts (4 a expert, Poisson-spread) reads well over 200, a
prefill chunk (64 a expert) far less, and the sums weigh a layer-call by
its rows. The grouped matmuls' time follows the fullest group where they
are bound by compute. A program without the counters reads 0. Layer:
experts. Moves gap_p95_ms."""

from perfbench.lib import moe_work


def read(ctx):
    d = moe_work.counters(ctx)
    if d is None or not d.get("moe_rows_routed"):
        return 0.0
    return (100.0 * d["moe_max_expert_rows"] * ctx["config"]["num_experts"]
            / d["moe_rows_routed"])
