"""Kernel: the paged-decode kernel's share of its roofline. The least time
the chip could take for the work the algorithm needs (each live slot's keys
and values read once, its q read and its output written once; QK and PV
FLOPs), over the kernel's device time. The kernel is the Pallas custom call
inside the decode program; the work per call is the mean over the decode
dispatches recorded while the profiler ran, times the kernel calls the trace
holds (one per layer per dispatch)."""
from chipbench import work


def read(run):
    calls = [live for _, live in run.traced(run.decode_calls)]
    kernel = run.trace.kernels.get(run.programs["decode"])
    if run.peaks is None or not calls or not kernel or kernel[1] <= 0:
        return None
    least = [work.least_time(*work.paged_attention_call(run.shape, live),
                             run.peaks)[0] for live in calls]
    return 100.0 * (sum(least) / len(least)) * kernel[0] / kernel[1]
