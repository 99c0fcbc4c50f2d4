"""Smoke-size cells for the benchmark's tests: a ``BENCHMARK.json`` in a
temporary root whose cells use the real drivers, metrics and limits at
sizes the CPU runs in seconds."""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")

QWEN3_SMOKE = {
    "name": "qwen3-smoke", "source": "the program's qwen3 SMOKE shapes",
    "model": {"model_type": "qwen3", "hidden_size": 256,
              "intermediate_size": 512, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 64,
              "num_hidden_layers": 2, "vocab_size": 512,
              "rope_theta": 1000000, "rms_norm_eps": 1e-06,
              "tie_word_embeddings": True}}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _traffic(name, **override):
    return dict(_load("traffic", name + ".json"), **override)


def make_root(tmp_path, extra_metrics=()) -> str:
    """A root with smoke cells ``round.smoke``, ``sweep.smoke`` and
    ``serve.smoke``; -> its path.  Drivers and metrics are the real ones."""
    root = str(tmp_path)
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, d))
    os.symlink(os.path.join(BENCH, "drivers"), os.path.join(bench, "drivers"))
    os.symlink(os.path.join(BENCH, "metrics"), os.path.join(bench, "metrics"))
    configs = {"qwen3-smoke": QWEN3_SMOKE,
               "secvii-n10": _load("configs", "secvii-n10.json")}
    traffic = {
        # a smoke round averages its loss over 128 tokens, not 16,384, so
        # bfloat16's loss gap reads ~1.8e-4 there: the loss limit is the
        # smoke size's own, between that and float8's 1.1e-3
        "round.smoke": _traffic("round.int8", batch=2, seq=64,
                                rounds_per_call=2,
                                limits=dict(_load("traffic", "round.int8.json")
                                            ["limits"], loss_gap=6e-4)),
        "sweep.smoke": _traffic("sweep.genC-256", points=4, check_sample=4),
        "serve.smoke": _traffic("serve.poisson", rules=["genC"],
                                hot={"n": 3, "cmax": [0.22, 0.5]}, rate=3,
                                check_sample=4),
    }
    for name, c in configs.items():
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(c, f)
    for name, t in traffic.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)
    real = _load("..", "BENCHMARK.json")
    cells = [("round.smoke", "qwen3-smoke"), ("sweep.smoke", "secvii-n10"),
             ("serve.smoke", "secvii-n10")]
    bench_json = {
        **{k: real[k] for k in ("command", "paths", "run_seconds",
                                "end_to_end")},
        "configs": [{"name": n, "source": c["source"],
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "smoke"} for n, c in configs.items()],
        "workloads": [{"name": w, "config": c, "traffic": w, "chips": 1,
                       "why": "smoke"} for w, c in cells],
        "per_layer": list(extra_metrics),
    }
    # end-to-end metrics keep their cells' names; point them at the smoke
    # cells of the same driver
    rename = {}
    for w in real["workloads"]:
        drv = _load("traffic", w["traffic"] + ".json")["driver"]
        rename[w["name"]] = drv + ".smoke"
    for m in bench_json["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_json, f)
    return root


def execute(root, cell, seed=7, seconds=1.0):
    """Run a smoke cell on the CPU; -> the result line as a dict."""
    from bench.lib import harness

    return harness.execute(cell, seed, seconds, False, on_chip=False,
                           root=root, bench_dir=os.path.join(root, "bench"))


def run_driver(root, cell, seed=7, seconds=1.0, **kw):
    """Run a smoke cell's driver on the CPU with keyword arguments of the
    test's own (faults planted underneath); -> its Outcome."""
    import time

    from bench.lib import harness

    spec = harness.Spec.load(cell, root, os.path.join(root, "bench"))
    run = harness.Run(spec, seed, seconds, False, time.perf_counter(),
                      on_chip=False)
    return spec.driver().run(run, **kw)
