"""Shared set-up for the benchmark's CPU tests: run them with

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They use the smoke sizes of both configurations and the Pallas kernels in
the interpreter (``backend="pallas_interpret"``)."""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# smoke size of the configuration (src/repro/configs/chatglm3_6b.py SMOKE)
SMOKE = {
    "chatglm3-6b-3l": {
        "family": "dense", "n_layers": 2, "d_model": 64, "vocab": 256,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "rope_theta": 10000.0, "rope_fraction": 0.5, "norm_eps": 1e-05,
        "max_seq": 128, "attn_q_chunk": 16, "attn_kv_chunk": 16},
}
TWIN_SCALE = 3e-5   # the configuration's lm_head_twin_scale


def smoke_conf(config: str) -> dict:
    """A configuration file as the harness reads it, its model cut to the
    smoke size: it names the same weights module and reference."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())
    return dict(conf, model=SMOKE[config])


def smoke_traffic(base: dict, **over) -> dict:
    """A cell's traffic file cut to what the interpreter runs in seconds."""
    t = dict(base)
    t.update(prompt_len={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             output_len={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
             slots=4, max_seq=64, preroll_s=1.0, trace_s=1.0,
             check={"max_requests_per_mode": 40, "tokens": 400})
    if t["loop"] == "open":
        t["rate_per_s"] = 4.0
    else:
        t["clients"] = 4
    t.update(over)
    return t
