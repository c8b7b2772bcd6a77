"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are data (``configs/<config>.json`` through the
configuration's ``file`` entry, ``traffic/<mix>.json``), and each per-layer
metric is a reader ``metrics/<metric>.py``. Adding a configuration, a mix
or a metric is adding a file and an entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic mix file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: pathlib.Path = CHECKOUT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # without a list a per-layer metric goes wherever the metric it moves is
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: pathlib.Path = CHECKOUT,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    traffic = load_traffic(w["traffic"], root)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer)


def load_traffic(name: str, root: pathlib.Path = CHECKOUT) -> Dict[str, Any]:
    with open(root / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def load_reader(metric: str, root: pathlib.Path = CHECKOUT
                ) -> Callable[[Any], Optional[float]]:
    """``read(run)`` of ``metrics/<metric>.py``: the metric's value from a
    finished run, or None where the run has nothing for it to read."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: Dict[str, Any], **overrides):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the file's depth, norm epsilon and rope base, and
    the deployment's kernel path (``overrides`` on top, for the CPU
    self-checks' small widths). Every width in the file must equal the
    program's; a mismatch is an error, never a silent override."""
    from repro.config import get_config
    dep = config["deployment"]
    fields = dict(num_layers=int(config["num_hidden_layers"]),
                  norm_eps=float(config["rms_norm_eps"]),
                  rope_theta=float(config["rope_theta"]),
                  use_pallas=bool(dep["use_pallas"]))
    fields.update(overrides)
    cfg = get_config(dep["arch"]).with_overrides(**fields)
    widths = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
              "num_attention_heads": cfg.num_heads,
              "num_key_value_heads": cfg.num_kv_heads,
              "vocab_size": cfg.vocab_size,
              "head_dim": cfg.d_head}
    for key, have in widths.items():
        if key in config and int(config[key]) != int(have):
            raise ValueError(f"{dep['arch']}: {key} is {have} in the program "
                             f"but {config[key]} in the configuration file")
    if bool(config.get("tie_word_embeddings")) != cfg.tie_embeddings:
        raise ValueError("tie_word_embeddings differs from the program")
    return cfg
