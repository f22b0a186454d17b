"""The traffic generator and the lookup of cell files by name."""
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import files  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402

MIX = {"kind": "open_loop", "rate_per_s": 5.0,
       "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                  "min": 32, "max": 2048},
       "output": {"dist": "uniform", "min": 16, "max": 64},
       "cycle": 64, "sizes_seed": 0}


def _take(seed, n=64, mix=MIX):
    t = Traffic(mix, 151936, seed)
    return [t.next() for _ in range(n)]


def test_generator_is_deterministic_per_seed():
    a, b = _take(2**31 + 17), _take(2**31 + 17)
    assert [(r.prompt_len, r.max_new, r.due) for r in a] == \
        [(r.prompt_len, r.max_new, r.due) for r in b]
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, b))
    # another seed: the same schedule of sizes and due times, other content
    c = _take(5)
    assert [(r.prompt_len, r.max_new, r.due) for r in a] == \
        [(r.prompt_len, r.max_new, r.due) for r in c]
    assert not any((x.tokens[:8] == y.tokens[:8]).all() for x, y in zip(a, c))


def test_seeds_share_one_multiset_of_sizes_and_gaps():
    n = MIX["cycle"]
    span = n / MIX["rate_per_s"]
    a, c = _take(1, n=3 * n + 1), _take(2**31 + 3, n=3 * n + 1)
    for k in range(3):
        ca, cc = a[k * n:(k + 1) * n], c[k * n:(k + 1) * n]
        # every cycle holds the same (prompt, output) pairs for every seed
        assert sorted((r.prompt_len, r.max_new) for r in ca) == \
            sorted((r.prompt_len, r.max_new) for r in cc)
        # ... starts on its boundary, and spans it with the same gaps
        assert ca[0].due == cc[0].due == __import__("pytest").approx(k * span)
        ga = np.diff([r.due for r in a[k * n:(k + 1) * n + 1]])
        gc = np.diff([r.due for r in c[k * n:(k + 1) * n + 1]])
        assert np.allclose(np.sort(ga), np.sort(gc))
    assert [r.prompt_len for r in a[:n]] != [r.prompt_len for r in a[n:2 * n]]
    dues = [r.due for r in a]
    assert dues == sorted(dues)
    assert all(32 <= r.prompt_len <= 2048 and 16 <= r.max_new <= 64
               for r in a)


def test_stream_never_repeats_content():
    reqs = _take(3, n=3 * MIX["cycle"])
    heads = {tuple(r.tokens[:16]) for r in reqs}
    assert len(heads) == len(reqs)


def test_backlog_has_no_due_times():
    mix = dict(MIX, kind="backlog")
    mix.pop("rate_per_s")
    reqs = _take(4, n=8, mix=mix)
    assert all(r.due is None for r in reqs)


def test_new_files_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"hidden_size": 7}))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"kind": "backlog"}))
    (tmp_path / "metrics" / "new_metric.batch.py").write_text(
        "def read(run):\n    return run * 2\n")
    assert files.config_of("new-model", tmp_path)["hidden_size"] == 7
    assert files.traffic_of("new-mix", tmp_path)["kind"] == "backlog"
    assert files.metric_reader("new_metric.batch", tmp_path)(21) == 42


def test_shipped_cells_resolve():
    bench = files.load_benchmark()
    for w in bench["workloads"]:
        assert files.config_of(w["config"])["program"]
        assert files.traffic_of(w["traffic"])["engine"]
        for section in ("end_to_end", "per_layer"):
            for m in files.metrics_for(bench, w["name"], section):
                assert callable(files.metric_reader(m["name"]))
    names = [m["name"] for m in bench["per_layer"] + bench["end_to_end"]]
    assert len(names) == len(set(names))


def test_metrics_for_filters_by_workloads():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in files.metrics_for(bench, "x", "end_to_end")] \
        == ["a", "b"]
    assert [m["name"] for m in files.metrics_for(bench, "y", "end_to_end")] \
        == ["a"]


def test_lengths_clip():
    from chipbench.traffic import quantile_lengths
    x = quantile_lengths({"dist": "lognormal", "median": 100, "sigma": 3.0,
                          "min": 10, "max": 200}, 1000)
    assert x.min() == 10 and x.max() == 200
    assert np.median(x) == 100
    u = quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert sorted(u.tolist()) == list(range(16, 65))
