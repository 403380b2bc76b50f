"""Whole-step model FLOP utilization of grid-executed decode: model
FLOPs per token from the configuration's shapes (`work.lm_flops_per_token`:
seven projections per layer, attention over the cached context, the tied
LM head) times the traced run's decoded tokens per second, over the
chip's bf16 peak.  Percent."""


def read(run):
    f = run.facts
    peak = run.peaks.get("bf16_flops_per_s")
    if not peak or not f.get("decode_tok_s"):
        return None
    return 100.0 * f["flops_per_token"] * f["decode_tok_s"] / peak
