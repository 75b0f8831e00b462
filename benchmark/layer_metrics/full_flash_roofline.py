"""The full-attention layers' flash kernels' share of their roofline, in
percent: the least time the chip could take for the causal attention the
``full_attention`` layers of one step need (forward and backward, the
causal half of the square; ``benchmark/flops_mellum.py``), over the device
time per step of the kernel events under the scope ``swa.attend_full``
(the configuration's ``trace_names.full_flash_kernel``). The arithmetic is
``window_flash_roofline``'s with the other mask's pairs."""

from benchmark.layer_metrics.window_flash_roofline import flash_roofline


def read(ctx):
    return flash_roofline(ctx, "full_attention", "full_flash_kernel")
