"""Kernels (ops/attention.py): the least time the chip could take for
the work of the flash dq kernel (dO V^T, dS K; reads q, k, v, do, writes dq) over the device time
of the ``XLA Ops`` events named ``zoo_flash_bwd_dq``, the ``name=`` of its
``pallas_call``.  The work is a third of ``costs.flash_flops`` and the
kernel's own tensors; ``program_spans.flash_kernel_roofline`` says how
the three split it."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace",
                              "train_samples_s")
KERNEL = "zoo_flash_bwd_dq"


def read(ctx):
    from benchmark import program_spans
    return program_spans.flash_kernel_roofline(ctx, KERNEL)
