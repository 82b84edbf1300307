"""The harness on the CPU: schedules, cells found by name, refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import _paths
import harness
import stats
import traffic

MIX = {"arrivals": {"process": "gamma", "cv": 2.0, "pattern_seed": 3},
       "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                         "min": 32, "max": 1536},
       "output_tokens": {"dist": "fixed", "value": 128}}


def test_same_seed_same_schedule():
    big = 2 ** 31 + 12345
    a = traffic.schedule(MIX, 0.5, 51, big, 49155)
    b = traffic.schedule(MIX, 0.5, 51, big, 49155)
    assert a == b
    assert len(a) == 26 and all(0 <= d.due_s < 51 for d in a)
    assert all(3 <= x < 49155 for d in a for x in d.prompt)


def test_seeds_share_the_work_and_differ_in_tokens():
    a = traffic.schedule(MIX, 0.5, 51, 1, 49155)
    b = traffic.schedule(MIX, 0.5, 51, 2, 49155)
    assert [(d.due_s, len(d.prompt), d.max_new_tokens) for d in a] == \
        [(d.due_s, len(d.prompt), d.max_new_tokens) for d in b]
    assert [d.prompt for d in a] != [d.prompt for d in b]
    lens = sorted(len(d.prompt) for d in a)
    assert lens[0] >= 32 and lens[-1] <= 1536
    assert lens[len(lens) // 2] == pytest.approx(512, rel=0.1)


def test_gamma_arrivals_are_bursty():
    import statistics
    due = [d.due_s for d in traffic.schedule(MIX, 4.0, 50, 1, 100)]
    gaps = [b - a for a, b in zip(due, due[1:])]
    cv = statistics.pstdev(gaps) / statistics.mean(gaps)
    assert 1.4 < cv < 2.6


def test_a_new_cell_is_found_with_no_code_edit(tmp_path):
    root = tmp_path
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), root)
    for sub in ("cells", "configs", "mixes"):
        shutil.copytree(os.path.join(_paths.BENCH, sub),
                        root / "chipbench" / sub)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    base = bench["workloads"][0]
    bench["workloads"].append(dict(base, name="x.new", traffic="newmix"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = json.loads((root / "chipbench" / "cells" /
                       f"{base['name']}.json").read_text())
    cell["rate_per_s"] = 9.0
    (root / "chipbench" / "cells" / "x.new.json").write_text(
        json.dumps(cell))
    (root / "chipbench" / "mixes" / "newmix.json").write_text(
        json.dumps(MIX))
    spec = harness.load_spec("x.new", root=str(root))
    assert spec.cell["rate_per_s"] == 9.0 and spec.mix == MIX
    assert len(harness.schedule(spec, 1, 10)) == 90
    with pytest.raises(KeyError):
        harness.load_spec("no.such.cell", root=str(root))


def test_every_configured_cell_resolves():
    bench = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        spec = harness.load_spec(w["name"])
        cfg = harness.program_config(spec)
        assert cfg.n_layers == spec.model["n_layers"]
        assert cfg.d_model == spec.model["d_model"]
        assert harness.reference_module(spec) is not None


def test_warmup_covers_every_table_width():
    lens = [40, 300, 700, 1536]
    groups = harness.warmup_groups(lens, {n: 1 for n in lens}, 128, 4, 16,
                                   2048)
    firsts = {harness.table_width(g[0] + 1, 16, 2048) for g in groups}
    need = {harness.table_width(c, 16, 2048)
            for c in range(41, 1536 + 128 + 1)}
    assert need <= firsts
    assert [1536, 1536] in groups and [1536] * 4 in groups


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_with_no_result(tmp_path):
    out = _run(_paths.ROOT,
               {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode == 3, out.stderr[-2000:]
    assert "TPU" in out.stderr
    assert '"metrics"' not in out.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout


@pytest.mark.parametrize("q,want", [(50, 2.5), (90, 3.7), (0, 1.0),
                                    (100, 4.0)])
def test_exact_percentile(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_reaching_an_unfinished_request_is_infinite():
    import math
    vals = [1.0, 2.0, 3.0, math.inf]
    assert stats.percentile(vals, 50) == 2.5
    assert math.isinf(stats.percentile(vals, 90))
