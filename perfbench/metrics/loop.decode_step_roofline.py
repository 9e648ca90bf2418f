"""loop.decode_step_roofline (%): the least time of a LOOPED model's plain
decode step over the median device time of the scheduler's decode program
(``jit_paged_decode_step``) in the traced window: THE SHARE OF THE WHOLE
STEP. Least time (``loop_work.step_least_seconds``): the stack's bytes as
many times as the configuration has passes (``total_ut_steps``), the head,
and the live rows' contexts in every (pass, layer) pool, at the memory's
bandwidth — or its operations at the peak rate, whichever is larger — from
the configuration's sizes and the client's records, never from the
program's own count. By window as ``moe.decode_step_roofline`` reads: the
plain step's median where the trace holds one, else the chunk program's if
it carried decode rows. A configuration without passes, or a trace without a
named program that carried decode rows, reads nothing. Layer: jitted step.
Moves gap_p95_ms."""

from perfbench.lib import loop_work


def read(ctx):
    return loop_work.decode_step_roofline_percent(ctx)
