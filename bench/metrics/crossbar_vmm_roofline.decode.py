"""Kernels: the ideal chip's crossbar kernel (``crossbar_vmm_pallas``)
inside the decode step, as a share of its roofline.  For every kernel call
of the ``jit_decode_step`` programs that ran whole inside the traced
window, the least time the chip could take for the product's operations
and bytes (``flops.kernel_cost``: 16-bit input codes, 4-byte outputs,
2 bytes per 16-bit weight code; the calls and their shapes from the
cell's reference, ``decode_kernels`` over the slot pool's rows), summed,
over the kernels' summed device time.  A step counts only where the trace
holds one kernel call per call the reference lists; the reader reports
nothing where no step does.  Moves itl_p95_ms."""
import flops

KERNEL = "crossbar_vmm_pallas"
PROGRAM = "jit_decode_step"
WEIGHT_BYTES = 2.0


def read(ctx):
    r = ctx.reduced
    calls = ctx.ref.decode_kernels(ctx.dims, ctx.cell.config["serving"]["max_batch"])
    kernels = sorted((o.start, o.end) for o in r.ops
                     if o.name.split(".")[0] == KERNEL and o.program == PROGRAM)
    steps, kernel_s = 0, 0.0
    for p in r.programs:
        if p.program != PROGRAM:
            continue
        inside = [b - a for a, b in kernels if p.start <= a and b <= p.end]
        if len(inside) == len(calls):
            steps += 1
            kernel_s += sum(inside) / 1e9
    if not steps:
        return None
    peak = flops.peaks(ctx.device_kind)
    least = sum(flops.roofline_s(*flops.kernel_cost(m, k, n, WEIGHT_BYTES), peak)
                for _, m, k, n in calls)
    return 100.0 * least * steps / kernel_s
