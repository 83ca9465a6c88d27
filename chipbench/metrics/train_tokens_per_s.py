"""Training tokens per second: the tokens of every step completed in the
window, over the time from the window's opening to the end of its last
step. Index refreshes in the window count in the time."""
from chipbench import stats


def read(rec):
    return stats.train_tokens_per_s(rec)
