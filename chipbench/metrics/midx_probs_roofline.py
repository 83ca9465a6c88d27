"""Share of its roofline the MIDX proposal-table kernel reaches in the
decode waves: the least time of the window's calls (counts/midx_probs.py
over the chip's peaks) over its summed device time in the trace."""
from chipbench import stats


def read(rec):
    return stats.roofline(rec, "midx_probs")
