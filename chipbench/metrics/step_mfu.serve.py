"""Model FLOP utilisation of serving: the model FLOPs of the prompts
prefilled and the tokens produced in the window (counts/<architecture>.py;
padding, idle slots and rejected drafts not counted), over the window and
the chips' summed bf16 peak."""
from chipbench import stats


def read(rec):
    if "all_requests" not in rec or rec.get("peaks") is None:
        return None
    counts = stats.model_counts(rec)
    spec = rec["spec"]
    flops = 0.0
    for r in rec["all_requests"]:
        for i, t in enumerate(r.times):
            if not stats.in_window(rec, t):
                continue
            flops += (counts.prompt(spec, r.plen) if i == 0
                      else counts.output_token(spec, r.plen + i - 1))
    return 100.0 * flops / stats.window_s(rec) / (
        rec["peaks"]["bf16_flops"] * rec["chips"])
