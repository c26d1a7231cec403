"""Share of its roofline that the ``xlb_admit_commit`` kernel reaches: the least
time (the larger of counted operations over the bf16 peak and counted
bytes over the HBM bandwidth; the bytes bound it) over its device time per
launch."""


def read(run):
    k = run.summary.kernels["xlb_admit_commit"]
    if not k["launches"]:
        return None
    ops, moved = run.costs["xlb_admit_commit"]
    least = max(ops / run.peaks["bf16_flops"], moved / run.peaks["hbm_bytes"])
    return 100.0 * least / (k["seconds"] / k["launches"])
