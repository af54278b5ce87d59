"""Share of its roofline that the fused E-step kernel reaches.

Kernel time: the device durations of every operation in the traced window
that holds the kernel's stable name (``bench/kernels/estep_stats.py``),
whatever program it ran in. Work: the algorithm's own count over the
E-steps that the window's federations report (``estep_rows``: each row
once per E-step over it, see ``bench/kinds/fit.py``), with d and K
unpadded. Calls that no reported E-step accounts for, such as those of a
vmapped loop running on for clients that have converged, or of
FedGenGMM's server refit, count as time without work, so the share can
read low and never high. Floor: the larger of work over the chip's peak
FLOP/s and bytes over its HBM bandwidth (``bench/peaks.json``)."""
from lib import registry
from lib.trace import kernel_time


def read(layer):
    kc = registry.kernel_count("estep_stats")
    peak = registry.peak_for(layer["device_kind"])
    _, seconds = kernel_time(layer["trace"], kc.STABLE_NAME, layer["lo"],
                             layer["hi"])
    rows = layer["estep_rows"]
    if seconds <= 0.0 or rows <= 0:
        return None
    flops, bytes_ = kc.count(rows, layer["d"], layer["k"])
    floor = max(flops / peak["flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
