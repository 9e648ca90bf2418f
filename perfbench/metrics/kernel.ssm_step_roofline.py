"""kernel.ssm_step_roofline (%): the state-space step kernel's share of its
roofline. Least time (``ssm_work.step_least_seconds``): the live rows' scan
states (64 heads of 64 x 128 float32 a layer: 2,097,152 B a row and layer)
read once and written once at the memory's bandwidth, counted by the program
(``ssm_step_rows`` = live rows x layers) over the window and brought to the
traced part by the traced runs that CARRIED decode rows over
d``decode_steps``. Time: the most the events named ``ssm_step`` can have
taken (``ssm_work.kernel_seconds_at_most``: their own column of the trace's
table plus the asynchronous ``-done`` ops of the same programs, which the
table counts apart where they fall inside a kernel's event), so the reading
is AT LEAST the kernel's share. The kernel also reads and writes the states of rows that are
not live (they come back bitwise), which lowers the reading, as it should.
A program without the counters or the kernel reads nothing. Layer: kernels.
Moves gap_p95_ms."""

from perfbench.lib import ssm_work


def read(ctx):
    return ssm_work.kernel_roofline_percent(ctx, ssm_work.STEP,
                                            ssm_work.step_least_seconds)
