"""Share of its roofline the sampled-CE kernels (forward and backward)
reach: the least time of the window's calls (counts/sampled_ce.py over the
chip's peaks) over their summed device time in the trace."""
from chipbench import stats


def read(rec):
    return stats.roofline(rec, "sampled_ce")
