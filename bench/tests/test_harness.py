"""The runner: found by name, refuses a machine without the chip, and each
driver runs a whole smoke-size cell on the CPU with no device metric."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402
from bench.tests import smoke  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_added_by_files_alone(tmp_path):
    """A new configuration, mix, cell and per-layer metric, as new files
    and entries only, are found by name and run."""
    metric = {"name": "sweep.points_per_sweep", "unit": "plans",
              "better": "higher", "source": "program_counter",
              "layer": "api.sweep", "moves": "plans_per_s",
              "workloads": ["sweep.small"]}
    root = smoke.make_root(tmp_path, extra_metrics=[metric])
    bench = os.path.join(root, "bench")
    # the metric's reader lives beside the real ones only for this test
    mdir = os.path.join(root, "extra_metrics")
    os.makedirs(mdir)
    for f in os.listdir(os.path.join(bench, "metrics")):
        os.symlink(os.path.join(bench, "metrics", f), os.path.join(mdir, f))
    with open(os.path.join(mdir, metric["name"] + ".py"), "w") as f:
        f.write("def read(view):\n"
                "    return view.counters['points'] / "
                "view.counters['sweeps']\n")
    os.unlink(os.path.join(bench, "metrics"))
    os.symlink(mdir, os.path.join(bench, "metrics"))
    with open(os.path.join(bench, "configs", "secvii-n10.json")) as f:
        cfg = json.load(f)
    cfg.update(name="secvii-n10-small", T_max=50000.0)
    with open(os.path.join(bench, "configs", "secvii-n10-small.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "sweep.smoke.json")) as f:
        mix = dict(json.load(f), points=3, cmax=[0.3, 0.5])
    with open(os.path.join(bench, "traffic", "sweep.small.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "secvii-n10-small", "source": "test",
                         "file": "bench/configs/secvii-n10-small.json",
                         "reduced": ["T_max"], "why": "test"})
    b["workloads"].append({"name": "sweep.small",
                           "config": "secvii-n10-small",
                           "traffic": "sweep.small", "chips": 1,
                           "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    spec = harness.Spec.load("sweep.small", root, bench)
    assert spec.config["T_max"] == 50000.0
    assert [m["name"] for m in spec.per_layer()] == [metric["name"]]
    out = smoke.run_driver(root, "sweep.small")
    assert out.correct and out.counters["points"] > 0
    view = harness.View(trace=None, end_to_end=out.end_to_end,
                        counters=out.counters, config=spec.config,
                        traffic=spec.traffic, device_kind="cpu", chips=1)
    assert spec.reader(spec.per_layer()[0])(view) == 3


def _smoke_ok(line, attempted_min=1):
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= attempted_min
    assert line["metrics"] == {}             # no device metric off the chip
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def test_sweep_and_serve_drivers_end_to_end(tmp_path):
    root = smoke.make_root(tmp_path)
    _smoke_ok(smoke.execute(root, "sweep.smoke"))
    line = smoke.execute(root, "serve.smoke", seconds=2.0)
    _smoke_ok(line)
    assert set(line["checks"]) == {"failed_requests", "plan_mismatches",
                                   "z_gap"}


def test_compile_counter_sees_a_compile():
    import jax
    import jax.numpy as jnp

    with harness._CompileCounter() as c:
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)(jnp.ones(7)) \
            .block_until_ready()
    assert c.count >= 1
    with harness._CompileCounter() as c:
        pass
    assert c.count == 0
