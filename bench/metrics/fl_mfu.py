"""Model FLOP/s utilization of an FL training grid, in %: the model FLOPs
that the window's rounds need (``work.fl_training``: SGD on the weighted
samples and the validation passes; the allocation game has no matmul and
is not counted) over the window, over the chips times one chip's bfloat16
peak (``bench/peaks.json``).  None where the cell counts no FLOPs."""
from bench import work


def read(run):
    c = run.counters
    if not c.get("fl_flops"):
        return None
    peak = work.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * c["fl_flops"] / c["window_s"] / (c["chips"] * peak)
