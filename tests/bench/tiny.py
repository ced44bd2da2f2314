"""Cell specs for CPU rehearsals: the real configuration, mix and cell
files of ``bench/``, shrunk to widths and lengths the CPU serves in
seconds (the Pallas kernels run in interpret mode there)."""
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax.numpy as jnp  # noqa: E402

from bench import model, models  # noqa: E402

WIDTHS = {
    "phi3-medium-14b": dict(hidden_size=512, num_attention_heads=8,
                            num_key_value_heads=2, head_dim=64,
                            intermediate_size=1024, vocab_size=8192),
    "qwen2-vl-2b": dict(hidden_size=512, num_attention_heads=6,
                        num_key_value_heads=1, head_dim=64,
                        intermediate_size=1024, vocab_size=8192),
}
SERVE = dict(max_slots=4, max_seq=256, block_size=16, prefill_chunk=32,
             prefill_multiple=32, n_blocks=64, paged_kernel=True)
LENGTHS = {
    "decode-long": dict(prompt=dict(median=12, min=2, max=40),
                        output=dict(median=24, min=8, max=64),
                        stratum=8, closed_pool_strata=4),
    "chat": dict(prompt=dict(median=16, min=2, max=64),
                 output=dict(median=8, min=2, max=24), stratum=8,
                 prefixes=dict(count=4, min=32, max=64)),
}
CELL = dict(rate_rps=6.0, preroll_s=0.5, drain_s=120.0, trace_offset_s=0.2,
            trace_s=1.0, check_requests=400)
# The comparison's limit at these widths, from CPU readings of sound
# runs (two dozen seeds in bfloat16: mean gap at most 5.2e-4; a dozen in
# float32: exactly 0, since the served tokens then equal the reference's
# best up to summation order). In float32 the program's int8 path reads
# 1.5e-5-2.9e-3.
LIMITS = {"bfloat16": dict(logit_gap_mean_limit=1.5e-3),
          "float32": dict(logit_gap_mean_limit=1e-6)}


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def spec(config: str, mix: str, compute: str = "bfloat16") -> dict:
    """A tiny spec of configuration ``config`` under traffic ``mix``
    (``bench/configs``, ``bench/traffic``), served with ``compute`` as
    its compute and cache dtype, reporting the metrics that
    ``BENCHMARK.json`` asks of the cells under that mix."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"] if w["traffic"] == mix}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cells & set(m["workloads"])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (cells & set(m["workloads"]) if "workloads" in m
                     else m["moves"] in names)]
    conf = model.load_json("configs", config)
    conf = _merge(conf, {
        "widths": dict(WIDTHS[config], num_hidden_layers=2),
        "num_hidden_layers": 2,
        "serve": dict(SERVE, compute_dtype=jnp.dtype(compute),
                      cache_dtype=jnp.dtype(compute)),
        "recipe": {"calibration": dict(n_samples=2, batch_size=2,
                                       seq_len=32)}})
    traffic = _merge(model.load_json("traffic", mix), LENGTHS[mix])
    return {"name": f"{config}.{mix}", "chips": 1, "conf": conf,
            "model": models.load(conf), "mix": traffic,
            "cell": dict(CELL, **LIMITS[compute]),
            "end_to_end": e2e, "per_layer": per_layer}
