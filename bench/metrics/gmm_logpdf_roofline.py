"""Share of its roofline that the scoring kernel (``gmm_logpdf``) reaches.

Kernel time: the device durations of the operations that hold the
kernel's stable name (``bench/kernels/gmm_logpdf.py``) in the traced part
of the window. Work: the algorithm's count over the rows of the requests
retired in that part (the slab's padding and empty slots do not count;
rows of requests still in flight when the trace stops are left out, so
the share is if anything low), with d and K unpadded. Floor: the larger of work over peak FLOP/s
and bytes over HBM bandwidth (``bench/peaks.json``)."""
import numpy as np

from lib import registry
from lib.trace import kernel_time


def read(layer):
    kc = registry.kernel_count("gmm_logpdf")
    peak = registry.peak_for(layer["device_kind"])
    _, seconds = kernel_time(layer["trace"], kc.STABLE_NAME, layer["lo"],
                             layer["hi"])
    retired = layer["retired"]
    done = np.isfinite(retired) & (retired <= layer["traced_s"])
    rows = int(np.sum(layer["sizes"][: len(retired)][done]))
    if seconds <= 0.0 or rows == 0:
        return None
    flops, bytes_ = kc.count(rows, layer["d"], layer["k"])
    floor = max(flops / peak["flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
