"""Self time of the expert layer's grouped-matmul kernel in the traced
steady steps, in % of the traced window, mean over the chips.

The kernel's ops are found in each ``trace.Reduction.op_ns`` by their
HLO names, ``gmm.<n>`` and ``tgmm.<n>``: XLA names a Pallas call's op
after the ``jax.jit`` around it, here the megablox kernels ``gmm``
(forward, and the rows' gradient) and ``tgmm`` (the weights' gradient)
that ``repro.kernels.grouped_matmul.expert_gmm`` calls.  Nothing to read
where no such op ran (a program without the kernel)."""
KERNEL_OPS = ("gmm", "tgmm")


def kernel_ns(reduction) -> float:
    return sum(t for name, t in reduction.op_ns.items()
               if name.rsplit(".", 1)[0] in KERNEL_OPS)


def read(inputs):
    reds = inputs["reductions"]
    ns = sum(kernel_ns(r) for r in reds)
    window = sum(r.window_ns for r in reds)
    if not ns or window <= 0:
        return None
    return 100.0 * ns / (window * inputs["chips"])
