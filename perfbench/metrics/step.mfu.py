"""step.mfu (%): model FLOP/s utilization of the training step — the rate of
the steps outside the traced sub-window times the operations a token
requires (recompute excluded), over chips times the chip's published peak.
Layer: jitted step. Moves train_tokens_per_s."""

from perfbench.lib import peaks


def read(ctx):
    return peaks.mfu_percent(
        ctx["e2e"]["train_tokens_per_s"], ctx["sizes"],
        int(ctx["traffic"]["seq_len"]), ctx["device"]["count"],
        ctx["device"]["kind"])
