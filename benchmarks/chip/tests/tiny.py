"""A cell small enough for the CPU: the qwen-7b configuration cut in every
size, with the served format and the harness's code paths unchanged."""
import copy
import json
from pathlib import Path

from bench import load_family

CHIP = Path(__file__).resolve().parents[1]

SIZES = {"n_layers": 4, "d_model": 256, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 128, "d_ff": 512, "vocab_size": 4096}


def spec(gap_limit: float, kind: str = "poisson") -> dict:
    config = json.loads((CHIP / "configs" / "qwen-7b.json").read_text())
    config = copy.deepcopy(config)
    config["overrides"] = dict(SIZES)
    config["model"].update(SIZES)
    traffic = {
        "poisson": {"kind": "poisson", "rate_per_s": 20.0, "ramp_s": 0.5},
        "closed": {"kind": "closed", "clients": 3, "start_spread_s": 0.1,
                   "ramp_s": 0.5},
    }[kind]
    traffic.update(
        prompt={"dist": "lognormal", "median": 20, "sigma": 0.6, "min": 4,
                "max": 40},
        output={"dist": "uniform", "min": 4, "max": 12})
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    return {
        "cell": {"name": "tiny", "chips": 1},
        "config": config,
        "family": load_family(config),
        "traffic": traffic,
        "sizing": {"rows": 4, "max_len": 64, "chunk": 16, "pool_blocks": 16,
                   "check_tokens": 24,
                   "check_requests": 3, "gap_limit": gap_limit},
        "end_to_end": [m for m in bench["end_to_end"]],
        "per_layer": [m for m in bench["per_layer"]],
    }
