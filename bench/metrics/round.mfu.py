"""round.mfu: the round's model FLOP/s over the chips' bf16 peak, in %.

Model FLOPs per token come from the configuration's shapes
(``bench/lib/flops.py``: forward and backward, tied head, causal
attention, no recomputation), times the run's trained tokens per second,
over chips x the published bf16 peak of the device kind."""
from bench.lib import flops, peaks


def read(view):
    rate = view.end_to_end.get("train_tokens_per_s")
    if not rate:
        return None
    shapes = flops.lm_shapes(view.config["model"])
    per_token = flops.train_flops_per_token(shapes, view.counters["seq"])
    peak = view.chips * peaks.peaks(view.device_kind).bf16_flops
    return 100.0 * per_token * rate / peak
