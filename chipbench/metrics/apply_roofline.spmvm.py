"""Share of the HBM roofline that the window's applies reach: the
paper's least bytes of one apply over the device's peak bandwidth, times
the applies in the traced window, over the device's busy time there."""
from chipbench.reference import spmvm_min_bytes


def read(ctx):
    applies = ctx["counters"].get("applies")
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not applies or trace is None or peaks is None or trace.busy_s <= 0:
        return None
    least_s = spmvm_min_bytes(ctx["n_rows"], ctx["nnz"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s * applies / trace.busy_s
