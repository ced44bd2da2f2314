"""The block-sparse kernel's share of its roofline
(``kernels/block_sparse``): the least time its calls in the profiled
part of the window could take (each call the larger of operations over
peak and bytes over bandwidth, ``bench/work/block_sparse.py``) over the
summed device time of its trace events. The calls are the planned
projections of every decode and prefill step the device ran there; the
rows of a call are the tokens it carries (active slots of a decode
step, prompt tokens of a prefill chunk), from the engine's record.
Percent."""
from bench import peaks
from bench.layer_metrics import decode_work, prefill_work, traced
from bench.work import block_sparse


def least_time(ctx) -> tuple:
    """(seconds, share of them bound by memory)."""
    tr, st = ctx["trace"], ctx["structure"]
    pk = peaks.peaks(ctx["device_kind"])
    rec = ctx["recorder"]
    dec = traced(ctx, rec.bursts)
    pre = traced(ctx, rec.chunks)
    ticks = sum(r[3] for r in dec)
    rows = {"decode": decode_work(dec)[0] / ticks if ticks else 0,
            "prefill": prefill_work(pre)[0] / len(pre) if pre else 0}
    total = mem = 0.0
    for kind, steps in tr["steps"].items():
        if not steps or not rows[kind]:
            continue
        for layer in st["layers"]:
            for K, N, kept in layer["proj"].values():
                if kept is None:
                    continue
                ops, by = block_sparse.call(rows[kind], K, N, kept,
                                            st["block"])
                t_ops, t_mem = ops / pk["bf16_flops"], by / pk["hbm_bytes_per_s"]
                total += steps * max(t_ops, t_mem)
                mem += steps * (t_mem if t_mem >= t_ops else 0.0)
    return total, (mem / total if total else 0.0)


def read(ctx):
    kernel_s = ctx["trace"]["kernels"]["block_sparse"]
    least, mem_share = least_time(ctx)
    if kernel_s <= 0 or least <= 0:
        return None
    ctx.setdefault("notes", []).append(
        f"block_sparse_roofline: least time {least:.6f} s over kernel time "
        f"{kernel_s:.6f} s; {100 * mem_share:.1f}% of the least time is "
        "bound by memory bandwidth")
    return 100.0 * least / kernel_s
