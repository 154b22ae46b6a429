"""Model FLOPs of every token the fleet processed in the window (prefill
chunk tokens and decode tokens of live slots, attention at live lengths,
``bench/costs.py``) over the window's wall seconds times the chip's peak."""

from bench import costs


def read(rec):
    peaks = rec["peaks"]
    if peaks is None:
        return None
    s, w = costs.Shapes(rec["config"]), rec["window"]
    flops = 0.0
    for st in w["steps"]:
        if st[1]:
            flops += costs.mixed_step(s, st[8], st[9], st[4], st[5])[0]
        else:
            flops += costs.decode_step(s, st[4], st[5])[0]
    return 100.0 * flops / (w["window_s"] * peaks["bf16_flops_per_s"])
