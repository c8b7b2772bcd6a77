"""A serving or training cell cut to CPU size for the self-checks: the
configuration's widths shrunk (d 64, 4 heads of 16, 2 kv heads, d_ff 128,
vocabulary 512), the kernels' reference path, and a mix scaled to match.
It drives the same harness code as a chip run."""
from __future__ import annotations

import copy
import dataclasses
import json

from harness import cells

WIDTHS = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
          "num_key_value_heads": 2, "intermediate_size": 128,
          "vocab_size": 512}
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def small_cell(config_name: str, traffic_name: str) -> cells.Cell:
    """The configuration and mix files cut to CPU size, as a cell (whether
    or not BENCHMARK.json lists the pair)."""
    with open(cells.BENCH / "configs" / f"{config_name}.json") as f:
        config = json.load(f)
    cell = cells.Cell(name=f"{config_name}.{traffic_name}",
                      config_name=config_name, traffic_name=traffic_name,
                      chips=1, config=config,
                      traffic=cells.load_traffic(traffic_name),
                      end_to_end=[], per_layer=[])
    config.update(WIDTHS)
    dep = config["deployment"]
    dep.update(max_batch=4, prefill_chunk=16, page_size=8, use_pallas=False)
    mix = copy.deepcopy(cell.traffic)
    if mix["kind"] == "serve":
        mix["tenants"] = min(mix["tenants"], 4)
        mix["resident_slots"] = min(mix["resident_slots"], 2)
        mix["rate_per_s"] = 3.0
        p = mix["prefix"]
        p["tokens"] = ({"dist": "fixed", "value": 24} if p["scope"] == "tenant"
                       else {"dist": "loguniform", "min": 24, "max": 48})
        if "turn_gap_s" in p:
            p["turn_gap_s"] = 0.5
        mix["prompt"] = {"dist": "uniform", "min": 4, "max": 20}
        mix["output"] = {"dist": "uniform", "min": 2, "max": 6}
    else:
        mix["seq_len"] = 32
        mix["global_batch"] = 2
    return dataclasses.replace(cell, config=config, traffic=mix)


def model_config(cell: cells.Cell):
    return cells.model_config(
        cell.config, d_model=64, num_heads=4, head_dim=16, num_kv_heads=2,
        d_ff=128, vocab_size=512, attn_chunk=32)


DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
