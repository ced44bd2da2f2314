"""The whole step's share of the chip's peak: operations the served
model requires (``bench/work/model_step.py``) for the prefill and
decode tokens processed in the profiled part of the window, over that
part's length times the bf16 peak of ``bench/peaks.py``. Percent."""
from bench import peaks
from bench.layer_metrics import decode_work, prefill_work, traced
from bench.work import model_step


def read(ctx):
    tr = ctx["trace"]
    dec = decode_work(traced(ctx, ctx["recorder"].bursts))
    pre = prefill_work(traced(ctx, ctx["recorder"].chunks))
    ops = sum(model_step.tokens(ctx["structure"], n, c) for n, c in (dec, pre))
    if not ops or tr["window_s"] <= 0:
        return None
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * ops / (tr["window_s"] * ctx["n_devices"] * peak)
