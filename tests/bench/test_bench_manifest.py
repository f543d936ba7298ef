"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, driver and metric resolves by name; metrics move what their
cells report; a run without a chip prints no result; a cell is added by
new files alone."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness

from bench_tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = harness.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir()
    assert MAN["command"][1] == "bench/run.py"


def test_names_units_and_texts_use_the_allowed_characters():
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    names = [x["name"] for x in MAN["configs"] + MAN["workloads"] + metrics]
    names += [w[k] for w in MAN["workloads"] for k in ("config", "traffic")]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([x["why"] for x in MAN["configs"] + MAN["workloads"]]
                 + [m["layer"] for m in MAN["per_layer"]]
                 + [c["source"] for c in MAN["configs"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for group in (MAN["configs"], MAN["workloads"], metrics):
        assert len({x["name"] for x in group}) == len(group)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_resolves_by_name(cell):
    entry = harness.cell_entry(MAN, cell)
    conf = next(c for c in MAN["configs"] if c["name"] == entry["config"])
    assert conf["file"] == f"bench/configs/{entry['config']}.json"
    config = harness.load_json("configs", entry["config"])
    assert config["name"] == entry["config"]
    assert config["reduced"] == conf["reduced"]
    traffic = harness.load_json("traffic", entry["traffic"])
    gen = harness.load_module("traffic", traffic["generator"])
    driver = harness.load_module("drivers", config["driver"])
    assert hasattr(driver, "Driver") and gen.__doc__
    assert harness.limits(cell)
    for trace in (False, True):
        for m in harness.cell_metrics(MAN, cell, trace):
            assert callable(harness.load_module("metrics", m["name"]).read)
    assert any(m["name"] == "setup_s"
               for m in harness.cell_metrics(MAN, cell, False))
    assert len(harness.cell_metrics(MAN, cell, False)) >= 2
    assert harness.cell_metrics(MAN, cell, True)


def test_each_metric_moves_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    layers = {m["layer"] for m in MAN["per_layer"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), \
                (m["name"], cell)
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"**{layer}**" in perf, layer


def test_a_run_without_a_chip_prints_no_result(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    only = tmp_path / "only"
    only.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", only)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, only / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for where in (ROOT, only):
        r = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", CELLS[0],
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=where, env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode != 0
        assert '"metrics"' not in r.stdout


SERVE_MIXES = sorted({w["traffic"] for w in MAN["workloads"]
                      if w["config"] == "stablelm-1.6b"})


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_serve_traffic_is_deterministic_and_fits_the_arena(mix):
    params = harness.load_json("traffic", mix)
    gen = harness.load_module("traffic", params["generator"])
    cfg = harness.load_json("configs", "stablelm-1.6b")
    max_seq, vocab = cfg["deployment"]["max_seq"], cfg["vocab_size"]
    a = gen.Backlog(params, 2 ** 31 + 11, vocab, max_seq)
    b = gen.Backlog(params, 2 ** 31 + 11, vocab, max_seq)
    c = gen.Backlog(params, 3, vocab, max_seq)
    for _ in range(2 * params["schedule_requests"] + 3):
        (pa, oa), (pb, ob), (pc, oc) = a.next(), b.next(), c.next()
        assert oa == ob == oc and len(pa) == len(pc)
        np.testing.assert_array_equal(pa, pb)
        assert len(pa) + oa <= max_seq
        assert pa.min() >= 0 and pa.max() < vocab
    prompts, outputs = gen.schedule(params, max_seq)
    for lens, mean in ((prompts, params["prompt_mean"]),
                       (outputs, params["output_mean"])):
        assert lens.min() >= 1
        assert abs(lens.mean() / mean - 1) < 0.15


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_serve_traffic_is_cut_only_to_fit_the_arena(mix):
    params = harness.load_json("traffic", mix)
    gen = harness.load_module("traffic", params["generator"])
    wide_p, wide_o = gen.schedule(params, 10 ** 9)
    prompts, outputs = gen.schedule(params, 256)
    assert prompts.max() <= 255 and (prompts + outputs).max() <= 256
    assert outputs.min() >= 1
    fits = wide_p + wide_o <= 256
    np.testing.assert_array_equal(prompts[fits], wide_p[fits])
    np.testing.assert_array_equal(outputs[fits], wide_o[fits])
    assert (prompts + outputs)[~fits].tolist() == [256] * int((~fits).sum())


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A copy of the benchmark gains a cell from new data files, a new
    metric reader and a manifest entry, and runs it on the CPU."""
    work = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", work / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (work / "src").symlink_to(ROOT / "src")
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "microhh-32-f32", "config": "microhh",
                             "traffic": "steps-32-f32", "chips": 1,
                             "why": "a tiny domain"})
    man["end_to_end"][0]["workloads"].append("microhh-32-f32")
    man["per_layer"].append({"name": "steps_seen", "unit": "steps",
                             "better": "higher", "source": "host_clock",
                             "layer": "whole step", "moves": "step_ms",
                             "workloads": ["microhh-32-f32"]})
    (work / "BENCHMARK.json").write_text(json.dumps(man))
    (work / "bench/traffic/steps-32-f32.json").write_text(json.dumps(
        {"generator": "stencil_steps", "grid": 32, "dtype": "float32",
         "dt": 0.001}))
    (work / "bench/workloads/microhh-32-f32.json").write_text(json.dumps(
        {"limits": {"advec_u_err": 1e-5, "diff_uvw_err": 1e-5,
                    "update_err": 1e-5}}))
    (work / "bench/metrics/steps_seen.py").write_text(
        "def read(rec):\n    return rec['window']['steps']\n")
    script = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(work)!r})\n"
        "from bench import harness, work\n"
        "harness.prepare_environment()\n"
        "cpu = lambda chips: (jax.devices(), work.Peaks('cpu', 1e12, 1e11))\n"
        "for trace in (False, True):\n"
        "    harness.run('microhh-32-f32', 3, 0.3, trace, 0.0, require_chip=cpu)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", script], cwd=work, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    # per-layer metrics that list their cells stay out of the new one
    assert [set(x["metrics"]) for x in lines] == [{"step_ms", "setup_s"},
                                                   {"steps_seen"}]
    assert all(x["correct"] for x in lines)
