"""Kernel layer (``kernels/paged_attention.py``): the least time the
chip needs for the traced decode rounds' paged-attention calls (per
call, the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, with whole pages DMA'd, times the layers that call it), over
the kernel's device time in the trace, in percent.  The account: the
configuration's architecture module (``paged_kernel_cost``,
``paged_layers``)."""

from bench import trace

#: the kernel's ops in the device trace
KERNEL = r"paged_attention|_pa_kernel"


def read(run):
    seconds, events = trace.op_time(run.red, KERNEL)
    if seconds <= 0:
        return None
    c, arch, peaks = run.cell.config, run.cell.arch, run.peaks
    T = run.cell.mix["page_tokens"]
    layers = arch.paged_layers(c)
    least = 0.0
    for s in run.traced_steps:
        if not s.contexts:
            continue
        f, b = arch.paged_kernel_cost(c, s.contexts, T)
        least += layers * max(
            f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
