"""Kernel layer (``kernels/paged_attention.py``): the least time the
chip needs for the traced decode rounds' paged-attention calls (per
call, the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, with whole pages DMA'd), over the kernel's device time in
the trace, in percent."""

from bench import flops, trace

#: the kernel's ops in the device trace
KERNEL = r"paged_attention|_pa_kernel"


def read(run):
    seconds, events = trace.op_time(run.red, KERNEL)
    if seconds <= 0:
        return None
    c, peaks = run.cell.config, run.peaks
    T = run.cell.mix["page_tokens"]
    least = 0.0
    for s in run.traced_steps:
        if not s.contexts:
            continue
        f, b = flops.paged_kernel_cost(c, s.contexts, T)
        least += c["num_hidden_layers"] * max(
            f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
