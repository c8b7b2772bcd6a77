"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files plus entries in BENCHMARK.json; the harness finds
them by name with no existing file edited."""
import json
import shutil

from harness import cells, traffic


def test_new_config_mix_and_metric(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench")
    shutil.copy(cells.CHECKOUT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}

    cfg = json.loads((root / "bench/configs/qwen2-72b.json").read_text())
    cfg["deployment"]["base_precision"] = "int8"
    (root / "bench/configs/newmodel.json").write_text(json.dumps(cfg))
    mix = cells.load_traffic("chat", root)
    mix.update(rate_per_s=2.5, tenants=16)
    (root / "bench/traffic/newmix.json").write_text(json.dumps(mix))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(w):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "newmodel", "source": "x",
                             "file": "bench/configs/newmodel.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "newmodel.newmix",
                               "config": "newmodel", "traffic": "newmix",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:          # a serving cell reports these
        if "qwen2-72b.chat" in m.get("workloads", []):
            m["workloads"].append("newmodel.newmix")
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels",
                               "moves": "output_tok_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("newmodel.newmix", root)
    assert cell.config["deployment"]["base_precision"] == "int8"
    assert cell.traffic["tenants"] == 16
    assert len(traffic.serve_requests(cell.traffic, 1, 10, 1000)) == 25
    names = [m["name"] for m in cell.per_layer]
    assert "new_metric" in names              # no workloads list: follows
    assert cells.load_reader("new_metric", root)(None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
