"""The traffic generator, the harness's lookup by file name, and the
refusal to run without a chip."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.kinds import serve

ROOT = harness.ROOT


def test_serve_schedule_is_deterministic_under_seed():
    tr = harness.traffic_of("serve_steady")
    a = serve.schedule(tr, 2 ** 31 + 12345, 10.0)
    b = serve.schedule(tr, 2 ** 31 + 12345, 10.0)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = serve.schedule(tr, 7, 10.0)
    # another seed orders the same work: same sizes, temperatures, clients
    for k in ("sizes", "temps", "clients"):
        np.testing.assert_array_equal(np.sort(a[k]), np.sort(c[k]))
    assert not np.array_equal(a["sizes"], c["sizes"])
    assert not np.array_equal(a["times"], c["times"])


def test_serve_schedule_takes_the_cell_parameters():
    tr = harness.traffic_of("serve_steady")
    s = serve.schedule(tr, 3, 10.0)
    n = int(round(tr["rate_per_s"] * 10.0))
    assert len(s["times"]) == n and np.all(np.diff(s["times"]) > 0)
    assert 0 < s["times"][0] and s["times"][-1] < 10.0
    assert s["sizes"].min() >= tr["samples_min"]
    assert s["sizes"].max() <= tr["samples_max"]
    tempered = np.mean(s["temps"] != 1.0)
    assert abs(tempered - tr["temp_share"]) < 0.05
    assert set(s["clients"]) <= set(range(tr["clients"]))
    seeds = np.concatenate([s["seeds"], s["warm_seeds"]])
    assert len(set(seeds.tolist())) == len(seeds)   # dedup never hits
    assert len(s["warm_seeds"]) == len(tr["warmup"])
    fast = serve.schedule(dict(tr, rate_per_s=2 * tr["rate_per_s"],
                               samples_max=4), 3, 10.0)
    assert len(fast["times"]) == 2 * n and fast["sizes"].max() <= 4


def test_every_name_resolves_to_its_own_file():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg = harness.config_of(cell["config"])
        assert cfg["name"] == cell["config"]
        ref = harness.reference_of(cell["config"])
        assert callable(ref.param_shapes) and callable(ref.loss_fn)
        traffic = harness.traffic_of(cell["traffic"])
        assert callable(harness.kind_driver(traffic["kind"]).run)
        assert set(harness.limits_of(cell["name"]))
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    # no list of names in the harness's code
    names = [c["name"] for c in bench["workloads"]] + \
        [m["name"] for m in bench["per_layer"] + bench["end_to_end"]
         if m["name"] not in ("setup_s",)]
    for f in ("harness.py", "run.py", "trace.py", "compare.py"):
        text = (ROOT / "bench" / f).read_text()
        assert not [n for n in names if n in text], f


def test_a_new_metric_file_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.layer_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    assert harness.metric_reader("new.layer_ms").read(None) == 1.5
    with pytest.raises(harness.BenchError):
        harness.metric_reader("not.there")


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "bitseq120.train_tb_b16", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert re.search("no TPU", p.stderr)
