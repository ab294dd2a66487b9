"""The traffic generator and the registry of configurations, mixes and
metrics that the harness finds by name."""
import collections
import json
import shutil
import subprocess
import sys

import _bench_path  # noqa: F401
import numpy as np
import pytest

import harness
import loadgen

SEED = 2**33 + 12345  # wider than 32 bits, as the driver's are


def _sizes(reqs):
    return sorted((len(r.prompt), r.max_new, r.system) for r in reqs)


@pytest.mark.parametrize("mix", ["chat", "decode"])
def test_same_seed_same_schedule(mix):
    m = loadgen.load_mix(mix)
    a = loadgen.generate(m, SEED, 30, 1000)
    b = loadgen.generate(m, SEED, 30, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


@pytest.mark.parametrize("mix", ["chat", "decode"])
def test_seeds_share_sizes_and_differ_in_order(mix):
    m = loadgen.load_mix(mix)
    a = loadgen.generate(m, SEED, 30, 1000)
    b = loadgen.generate(m, SEED + 1, 30, 1000)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not all((x.prompt[:16] == y.prompt[:16]).all()
                   for x, y in zip(a, b))


def test_chat_follows_its_parameters():
    m = loadgen.load_mix("chat")
    seconds = 51
    reqs = loadgen.generate(m, SEED, seconds, 1000)
    span = m["preroll_s"] + seconds
    assert len(reqs) == round(m["rate_per_s"] * span)
    due = np.array([r.due for r in reqs])
    assert (np.diff(due) > 0).all()
    assert due[0] > -m["preroll_s"] and due[-1] == pytest.approx(seconds)
    sp = m["system_prompts"]
    heads = {}
    for r in reqs:
        assert 0 <= r.system < sp["count"]
        heads.setdefault(r.system, r.prompt[:sp["min"]])
        assert (r.prompt[:sp["min"]] == heads[r.system]).all()
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
    # Zipf(1) over 8 prompts: the first takes about 37% of the requests
    counts = collections.Counter(r.system for r in reqs)
    assert counts[0] == max(counts.values())
    big = loadgen.generate(dict(m, rate_per_s=50.0), SEED, 200, 1000)
    share = collections.Counter(r.system for r in big)
    want = loadgen.zipf_weights(sp["count"], sp["zipf_s"])
    got = np.array([share[i] for i in range(sp["count"])]) / len(big)
    assert np.abs(got - want).max() < 0.02
    outs = np.array([r.max_new for r in big])
    assert abs(np.median(outs) - m["output"]["median"]) < 10


def test_system_prompts_are_page_aligned_and_in_range():
    m = loadgen.load_mix("chat")
    sp = m["system_prompts"]
    reqs = loadgen.generate(dict(m, rate_per_s=20.0), SEED, 60, 10**6)
    groups = collections.defaultdict(list)
    for r in reqs:
        groups[r.system].append(r.prompt)
    assert len(groups) == sp["count"]
    for prompts in groups.values():
        a, b = prompts[0], prompts[1]
        n = min(len(a), len(b))
        head = int(np.argmin(a[:n] == b[:n])) if (a[:n] != b[:n]).any() \
            else n
        assert head % sp["align"] == 0
        assert sp["min"] - sp["align"] < head <= sp["max"]


def test_decode_follows_its_parameters():
    m = loadgen.load_mix("decode")
    reqs = loadgen.generate(m, SEED, 30, 1000)
    assert len(reqs) == m["requests"]
    assert all(r.due == 0 and r.system == -1 for r in reqs)
    assert all(m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
               and m["output"]["min"] <= r.max_new <= m["output"]["max"]
               for r in reqs)
    first = lambda s: sorted(len(r.prompt) for r in
                             loadgen.generate(m, s, 30, 1000)[:32])
    assert first(SEED) == first(SEED + 7)


def test_every_cell_resolves_by_name():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        spec = harness.cell_spec(w["name"], bench)
        assert spec["conf"]["family"]
        for m in spec["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
        program, ref = harness.family(spec["conf"])
        assert program.__name__ == f"models.{spec['conf']['family']}"


def test_new_files_become_available_without_edits(tmp_path):
    """A copy of the benchmark gains a configuration, a mix and a metric
    by new files and new BENCHMARK.json entries alone."""
    root = tmp_path / "repo"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/phi4-mini-3.8b.json")
                      .read_text())
    (root / "bench/configs/new-model.json").write_text(json.dumps(conf))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        dict(loadgen.load_mix("decode"), concurrency=4, requests=8)))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "itl_p95_ms",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys; sys.path.insert(0, 'bench'); import harness, loadgen\n"
        "s = harness.cell_spec('new-cell')\n"
        "r = loadgen.generate(s['mix'], 1, 10, 100)\n"
        "print(s['conf']['hidden_size'], len(r),"
        " [m['name'] for m in s['per_layer']],"
        " harness.metric_reader('new_metric')(None))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[:2] == ["3072", "8"]
    assert "'new_metric'" in out.stdout and out.stdout.strip().endswith(
        "42.0")
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == b for p, b in before.items())
