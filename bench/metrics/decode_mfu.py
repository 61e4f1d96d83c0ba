"""Model layer (``Model.decode_step_paged``), whole step: model FLOPs of
the decode rounds inside the traced window (projections, attention over
each row's context, the head) over those rounds' wall time on the host
clock and the chip's bf16 peak, in percent.  The traced rounds alone:
the window's wall time also holds the profiler's stop, which is no
work of the program."""

from bench import flops


def read(run):
    steps = run.traced_steps
    if not steps:
        return None
    c = run.cell.config
    total = sum(flops.decode_token_flops(c, n)
                for s in steps for n in s.contexts)
    span = steps[-1].t1 - steps[0].t0
    if total == 0 or span <= 0:
        return None
    return 100.0 * total / (span * run.peaks["bf16_flops_per_s"])
