"""A later change adds a cell, a mix and a metric as files and entries;
no file the benchmark has is edited."""
import json
import os
import shutil

from bench import harness, stats


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(harness.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
              for d, _, fs in os.walk(os.path.join(root, "bench"))
              for f in fs}
    with open(os.path.join(root, "bench", "traffic", "bursty.json"),
              "w") as f:
        json.dump({"loop": "open", "rate_rps": 3.0, "preroll_s": 2.0,
                   "prompt": {"dist": "uniform", "min": 64, "max": 128},
                   "output": {"dist": "uniform", "min": 8, "max": 16}}, f)
    with open(os.path.join(root, "bench", "metrics", "steps_run.py"),
              "w") as f:
        f.write("def read(rec, trace):\n"
                "    return float(len(rec.window_steps()))\n")
    with open(os.path.join(root, "bench", "limits",
                           "qwen2-0.5b.bursty.json"), "w") as f:
        json.dump({"served_gap": 0.5, "sample_tokens": 100}, f)
    bm_path = os.path.join(root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "qwen2-0.5b.bursty",
                            "config": "qwen2-0.5b", "traffic": "bursty",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "steps_run", "unit": "steps",
                            "better": "higher", "source": "host_clock",
                            "layer": "scheduler", "moves": "itl_p95_ms",
                            "workloads": ["qwen2-0.5b.bursty"]})
    with open(bm_path, "w") as f:
        json.dump(bm, f)

    cell = harness.load_cell("qwen2-0.5b.bursty", True, root=root)
    assert cell.mix["rate_rps"] == 3.0
    assert cell.config["name"] == "qwen2-0.5b"
    names = [m["name"] for m in cell.metrics]
    assert "steps_run" in names and "prefix_hit_pct" not in names
    rec = stats.Record(slots=1, open=0.0, close=1.0, requests=[],
                       steps=[stats.StepLog(0.1, 0.2, [], [], 0)],
                       counters={}, setup_s=0, dims={}, peaks={})
    assert harness.reader("steps_run", root=root)(rec, None) == 1.0
    after = {p: open(p, "rb").read() for p in before}
    assert after == before
