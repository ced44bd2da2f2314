"""The paged-attention decode kernel's share of its roofline
(``kernels/paged_attention``): the least time of its work in the
profiled part of the window (the larger of operations over peak and
bytes over bandwidth, ``bench/work/paged_attention.py``, with the bytes
counted from the live context length of every decode token) over the
summed device time of its trace events. The decode steps are those the
device ran there; the tokens and contexts per step come from the
engine's record of its bursts. Percent."""
from bench import peaks
from bench.layer_metrics import decode_work, traced
from bench.work import paged_attention


def read(ctx):
    tr = ctx["trace"]
    kernel_s = tr["kernels"]["paged_attention"]
    dec = traced(ctx, ctx["recorder"].bursts)
    ticks = sum(r[3] for r in dec)
    steps = tr["steps"]["decode"]
    if kernel_s <= 0 or not ticks or not steps:
        return None
    tokens, ctx_sum = decode_work(dec)
    scale = steps / ticks               # the device's steps, the host's mix
    ops, by = paged_attention.decode(ctx["structure"], tokens, ctx_sum)
    pk = peaks.peaks(ctx["device_kind"])
    t_ops = scale * ops / pk["bf16_flops"]
    t_mem = scale * by / pk["hbm_bytes_per_s"]
    ctx.setdefault("notes", []).append(
        f"paged_attention_roofline: bound by "
        f"{'memory bandwidth' if t_mem >= t_ops else 'compute'} "
        f"({t_mem:.6f} s of bytes, {t_ops:.6f} s of operations) over kernel "
        f"time {kernel_s:.6f} s")
    return 100.0 * max(t_ops, t_mem) / kernel_s
