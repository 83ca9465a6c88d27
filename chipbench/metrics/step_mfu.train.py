"""Model FLOP utilisation of training: the model FLOPs per token
(counts/<architecture>.py: forward and backward of the backbone and of the
sampled head; no recomputation, no full softmax) times the window's tokens
per second, over the chips' summed bf16 peak."""
from chipbench import stats


def read(rec):
    rate = stats.train_tokens_per_s(rec)
    if rate is None or rec.get("peaks") is None:
        return None
    per_tok = stats.model_counts(rec).train_per_token(
        rec["spec"], rec["work"]["negatives"])
    return 100.0 * per_tok * rate / (rec["peaks"]["bf16_flops"] * rec["chips"])
