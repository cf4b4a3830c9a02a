"""Kernels (ops/attention.py): the least time the chip could take for
the work of the flash forward kernel (QK^T, PV; reads q, k, v, writes o) over the device time
of the ``XLA Ops`` events named ``zoo_flash_fwd``, the ``name=`` of its
``pallas_call``.  The work is a third of ``costs.flash_flops`` and the
kernel's own tensors; ``program_spans.flash_kernel_roofline`` says how
the three split it."""

LAYER, UNIT, SOURCE, MOVES = ("Kernels", "%", "device_trace",
                              "train_samples_s")
KERNEL = "zoo_flash_fwd"


def read(ctx):
    from benchmark import program_spans
    return program_spans.flash_kernel_roofline(ctx, KERNEL)
