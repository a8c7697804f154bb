"""Share of ``bitset_matmul``'s roofline: the least time its calls in the
traced window could take at the HBM peak (``bench.kernel_work``: carrier
words read and written, 8 bytes per edge of the classes swept), over their
device time in the trace.  Nothing when no call ran."""

from bench import kernel_work


def read(run):
    tr = run["trace"]
    k = tr["kernels"].get("bitset_matmul") if tr else None
    if not k or not k["calls"] or k["seconds"] <= 0:
        return None
    work = kernel_work.bitset_matmul_bytes(
        k["calls"], run["n_vertices"], run["lanes"], run["n_edges"],
        run["classes"])
    return kernel_work.roofline_share(work, k["seconds"], run["device_kind"])
