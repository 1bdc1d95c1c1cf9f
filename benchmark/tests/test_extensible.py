"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and ``BENCHMARK.json`` entries, and edits no file that
is here: shown by doing exactly that in a copy of the checkout."""

import json
import os
import shutil

from benchmark.tests import toy


def test_new_config_traffic_cell_and_metric_need_only_new_files(tmp_path):
    root = str(tmp_path)
    for name in ("xaynet_tpu", "native"):
        os.symlink(os.path.join(toy.ROOT, name), os.path.join(root, name))
    shutil.copytree(os.path.join(toy.ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(toy.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    # a configuration: a small MLP's vector under the M6 mask, as new data
    with open(os.path.join(root, "benchmark", "configs", "charlstm-leaf-f32m3.json")) as f:
        cfg = json.load(f)
    cfg.update(name="mlp-f32m6", model_length=20_011, updates_per_round=12, batch_size=4,
               scalar_denominator=16, bytes_per_number=7, order_bits=55,
               mask=dict(cfg["mask"], model_type="m6"))
    cfg["toml"] = {"aggregation": {"device": True, "kernel": "pallas-interpret"}}
    cfg["check"] = {"sample_positions": 0, "edge_positions": 0}
    with open(os.path.join(root, "benchmark", "configs", "mlp-f32m6.json"), "w") as f:
        json.dump(cfg, f)
    # a traffic mix: a slower Poisson stream, as new data
    with open(os.path.join(root, "benchmark", "traffic", "trickle.json"), "w") as f:
        json.dump({"arrival": "poisson", "rate_per_s": 6.0, "pattern_seed": 3, "concurrency": 16,
                   "max_shed_retries": 0, "request_timeout_s": 60.0,
                   "trace": {"start_s": 0.2, "max_s": 5.0}}, f)
    # a per-layer metric of an existing reader kind, as new data
    with open(os.path.join(root, "benchmark", "layer_metrics", "update.handle_ms.json"), "w") as f:
        json.dump({"reader": "prom_ratio", "args": {
            "num": {"name": "xaynet_request_handle_seconds_sum", "labels": {"phase": "update"}},
            "den": {"name": "xaynet_request_handle_seconds_count", "labels": {"phase": "update"}},
            "span": ["open", "close"], "scale": 1000.0}}, f)
    cell = "mlp-f32m6.trickle"
    bench["configs"].append({"name": "mlp-f32m6", "source": "made up for this test",
                             "file": "benchmark/configs/mlp-f32m6.json",
                             "reduced": cfg["reduced"], "why": "extensibility"})
    bench["workloads"].append({"name": cell, "config": "mlp-f32m6", "traffic": "trickle",
                               "chips": 1, "why": "extensibility"})
    # an end-to-end metric the harness already measures, declared for the new cell alone
    bench["end_to_end"].append({"name": "upload_p95_ms", "unit": "ms", "better": "lower",
                                "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    for m in bench["end_to_end"]:
        if m["name"] == "updates_per_s":  # a paced cell's rate is its schedule's
            m["workloads"] = [w["name"] for w in bench["workloads"] if w["name"] != cell]
    bench["per_layer"].append({"name": "update.handle_ms", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "update phase and staged aggregator",
                               "moves": "upload_p95_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, result, out, err = toy.run_cell(cell, ["--seconds", "2"], root=root, trace=0)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True and result["attempted"] == 12
    assert set(result["metrics"]) == {"upload_p95_ms", "round_tail_s", "setup_s"}
    rc, result, out, err = toy.run_cell(cell, ["--seconds", "2"], root=root, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True and result["metrics"]["update.handle_ms"]["value"] > 0
    for path, content in before.items():  # nothing that was there was edited
        with open(path, "rb") as f:
            assert f.read() == content, path
