"""kernel.flash_roofline (%): the flash-attention kernel's share of its
roofline in the traced training steps, first device.

Least time: the operations the calls the trace shows REQUIRE, at the chip's
published bf16 peak (197 TFLOP/s on the v5e). A call is an event named
``flash_attention_fwd``, ``flash_attention_bwd_dq`` or
``flash_attention_bwd_dkv`` (the kernels' ``name=``); the forward that full
rematerialization runs again is a call like any other. One call needs

    matmuls x (B x H / devices) x S x S x D        operations,

``2 S S D`` for a matmul over one head's [S, S] scores, halved because the
attention is causal; matmuls = 2 (forward: QK^T, PV), 3 (dQ: QK^T again,
dO V^T, dS K), 4 (dK/dV: QK^T again, P^T dO, dO V^T, dS^T Q); B the cell's
batch, S its sequence length, H and D the configuration's heads and head
size; the mesh shards batch and heads only, so a device holds B x H /
devices of them. Operations bound the kernel from S of about 1000 up (the
bytes of Q, K, V and O read and written once take S / 962 of the time of
the operations). Time: the summed device time of those events. Reads 0
where the trace shows no event by these names (a program that does not name
its kernel yet). Layer: kernels. Moves train_tokens_per_s."""

from perfbench.lib import layers


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    return layers.flash_roofline_percent(
        t["ops"], ctx["sizes"], int(ctx["cell"]["batch"]),
        int(ctx["traffic"]["seq_len"]), ctx["device"]["count"],
        ctx["device"]["kind"])
