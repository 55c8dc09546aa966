"""``BENCHMARK.json`` and the files it names: every entry resolves by
name, names and units keep to their characters, every per-layer metric
moves an end-to-end metric its cells report, a new cell (with a new
loop and a new end-to-end metric) needs only new files, and the graph
generator is deterministic in the seed."""
import json
import re
import shutil

import numpy as np
import pytest

from bench import harness, synth
from bench.manifest import ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_every_entry_resolves_to_its_file(man):
    data = man.data
    for path in data["paths"]:
        assert (ROOT / path).is_dir(), path
    assert data["command"][1] == "bench/run.py"
    assert (ROOT / data["command"][1]).is_file()
    for c in data["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = man.config(c["name"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert hasattr(man.reference(cfg["model"]), "train")
    for w in data["workloads"]:
        assert w["config"] in man.configs
        mix = man.mix(w["traffic"])
        man.loop(mix["loop"]).validate(mix)
        assert set(man.limits(w["name"])) >= {"loss_gap", "grad_gap",
                                             "delta_gap"}
        assert w["chips"] in (1, 4)
    for m in data["end_to_end"] + data["per_layer"]:
        assert callable(man.metric_reader(m["name"]).read)


def test_a_loop_refuses_keys_it_does_not_implement(man):
    loop = man.loop("closed_train")
    for extra in ({"negatives": "popularity"}, {"arrivals": "open"}):
        with pytest.raises(ValueError, match="closed_train"):
            loop.validate({**man.mix("fullgraph-bpr"), **extra})


def test_names_units_and_sources(man):
    data = man.data
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in data[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in data["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200
    for c in data["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in data["end_to_end"] + data["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in data["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in data["end_to_end"])


def test_every_per_layer_metric_moves_what_its_cells_report(man):
    e2e = {m["name"] for m in man.data["end_to_end"]}
    for m in man.data["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", man.cells):
            assert m["moves"] in {x["name"] for x in man.end_to_end(cell)}
            assert m in man.per_layer(cell)
    for cell in man.cells:
        assert len(man.end_to_end(cell)) >= 2 and man.per_layer(cell)


def test_run_seconds_fit_a_full_check(man):
    rs = man.data["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


SERVE_LOOP = """
import time

import jax.numpy as jnp


def validate(mix):
    assert set(mix) == {"loop", "requests", "why"}, mix


class Loop:
    def __init__(self, mix, cfg, ref, seed, *, phases, cache_dir,
                 require_tpu, log=print):
        validate(mix)
        self.table = jnp.arange(cfg["n_items"], dtype=jnp.float32)
        self.want, self.lat, self.answers = mix["requests"], [], []

    def window(self, seconds, annotate):
        t0 = time.perf_counter()
        while len(self.lat) < self.want:
            t = time.perf_counter()
            self.answers.append(float(self.table[len(self.lat)]))
            self.lat.append(time.perf_counter() - t)
        return {"latencies_s": self.lat,
                "seconds": time.perf_counter() - t0}

    attempted = property(lambda self: len(self.lat))
    failed = 0

    def release(self):
        del self.table

    def counts(self):
        return {}

    def check(self, log=print):
        wrong = sum(a != i for i, a in enumerate(self.answers))
        return {"wrong_answers": wrong}, {"wrong_answers": 0}
"""

P95 = """
import numpy as np


def read(ctx):
    lat = ctx["window"].get("latencies_s")
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
"""


def test_a_new_cell_needs_only_new_files(man, tmp_path):
    """A new configuration, a mix with a loop of its own that is not a
    training loop, a new end-to-end metric, a new per-layer metric and
    two new cells, all by new files and new entries; the serving-like
    cell then runs through the harness as it stands."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    data = json.loads(json.dumps(man.data))
    new = lambda path, text: (tmp_path / path).write_text(text)
    cfg = man.config("lightgcn-m25")
    cfg["name"] = "lightgcn-m25-2e24"
    cfg["n_edges"] = 1 << 24
    new("bench/configs/lightgcn-m25-2e24.json", json.dumps(cfg))
    new("bench/mixes/fullgraph-bpr-5.json",
        json.dumps({**man.mix("fullgraph-bpr"), "compared_steps": 5}))
    new("bench/limits/new-cell.json",
        json.dumps(man.limits("lightgcn-m25-train")))
    new("bench/metrics/new.metric.py", "def read(ctx):\n    return 1.5\n")
    new("bench/loops/lookup_serve.py", SERVE_LOOP)
    new("bench/mixes/serve-lookup.json", json.dumps(
        {"loop": "lookup_serve", "requests": 40, "why": "x"}))
    new("bench/metrics/serve_p95_ms.py", P95)
    new("bench/limits/new-serve.json", "{}")
    data["configs"].append({"name": "lightgcn-m25-2e24", "source": "x",
                            "file": "bench/configs/lightgcn-m25-2e24.json",
                            "reduced": ["n_edges"], "why": "x"})
    data["workloads"] += [
        {"name": "new-cell", "config": "lightgcn-m25-2e24",
         "traffic": "fullgraph-bpr-5", "chips": 1, "why": "x"},
        {"name": "new-serve", "config": "lightgcn-m25",
         "traffic": "serve-lookup", "chips": 1, "why": "x"}]
    # an end-to-end metric lists the cells that report it
    [step] = [m for m in data["end_to_end"] if m["name"] == "train_step_s"]
    step["workloads"].append("new-cell")
    data["end_to_end"].append({"name": "serve_p95_ms", "unit": "ms",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["new-serve"]})
    data["per_layer"].append({"name": "new.metric", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "engine", "moves": "train_step_s",
                              "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    new_man = Manifest(root=tmp_path)
    w = new_man.cell("new-cell")
    assert new_man.config(w["config"])["n_edges"] == 1 << 24
    assert new_man.mix(w["traffic"])["compared_steps"] == 5
    assert "new.metric" in [m["name"] for m in new_man.per_layer("new-cell")]
    assert new_man.metric_reader("new.metric").read({}) == 1.5
    assert "new.metric" not in [m["name"] for m in
                                new_man.per_layer("lightgcn-m25-train")]
    assert [m["name"] for m in new_man.end_to_end("new-serve")] == \
        ["setup_s", "serve_p95_ms"]

    cfg = {**man.config("lightgcn-m25"), "n_items": 64}
    res = harness.run_cell("new-serve", 5, 0.01, False, cfg=cfg,
                           require_tpu=False, root=tmp_path)
    assert res["correct"] and res["attempted"] == 40
    assert set(res["metrics"]) == {"setup_s", "serve_p95_ms"}
    assert res["checks"] == {"wrong_answers": {"value": 0, "limit": 0}}


def test_generator_is_deterministic_in_the_seed():
    cfg = dict(n_users=500, n_items=300, n_edges=4000, graph_seed=0,
               zipf_alpha=1.05)
    base = synth.base_graph(cfg)
    again = synth.base_graph(cfg)
    np.testing.assert_array_equal(base.user, again.user)
    np.testing.assert_array_equal(base.item, again.item)
    a, b = synth.relabel(base, 2**31 + 7, 4), synth.relabel(base, 2**31 + 7, 4)
    np.testing.assert_array_equal(a.user, b.user)
    np.testing.assert_array_equal(a.item, b.item)
    c = synth.relabel(base, 12, 4)
    assert not np.array_equal(a.user, c.user)
    # every seed: the same degrees, and the same edges between blocks
    block = -(-(500 + 300) // 4)
    for g in (a, c):
        assert sorted(np.bincount(g.user, minlength=500)) == \
            sorted(np.bincount(base.user, minlength=500))
        pairs = lambda g: np.unique(np.stack([g.user // block,
                                              (g.item + 500) // block]),
                                    axis=1, return_counts=True)[1]
        np.testing.assert_array_equal(pairs(g), pairs(base))


def test_generator_output_is_pinned():
    """The yardstick's graph may not move: a few numbers of one small
    graph, as the copy of the generator made them when it was added."""
    g = synth.generate_bipartite(400, 200, 3000, seed=3)
    assert len(g.user) == 3000
    assert int(g.user.astype(np.int64).sum()) == 568783
    assert int(g.item.astype(np.int64).sum()) == 295999
    assert g.user[:5].tolist() == [1, 370, 353, 10, 70]
    assert g.item[:5].tolist() == [134, 124, 124, 70, 138]
