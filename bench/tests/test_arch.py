"""A configuration finds its architecture file by its own ``model_type``,
and the dense GQA decoder's file reads what the benchmark read before the
architecture became a file.

``bench/testdata/dense_readings.json`` holds readings recorded before the
dense equations moved into ``bench/arch/dense.py``: for each configuration
of BENCHMARK.json its sizes, the program's ``ModelConfig``, the shapes of
the parameter tree and the work counts at fixed arguments; for a tiny
float32 configuration the weights' bytes (sha256) and the reference's gaps
and the fp8 control's on one fixed request."""
import dataclasses
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench import harness, spec
from bench.spec import BENCH, ROOT

with open(BENCH / "testdata" / "dense_readings.json") as f:
    READ = json.load(f)
CONFIGS = sorted(READ["configs"])
DENSE = BENCH / "arch" / "dense.py"


def _config(name):
    conf = {c["name"]: c for c in spec.load_benchmark()["configs"]}[name]
    with open(ROOT / conf["file"]) as f:
        return json.load(f)


def _plain(x):
    """``x`` as JSON gives it back: tuples become lists."""
    return json.loads(json.dumps(x))


def test_recorded_configurations_are_the_benchmarks():
    assert CONFIGS == sorted(c["name"]
                             for c in spec.load_benchmark()["configs"])


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_resolves_to_dense(name):
    arch = spec.architecture(_config(name)["model_type"])
    assert arch.__file__ == str(DENSE)
    assert spec.load_cell(next(
        w["name"] for w in spec.load_benchmark()["workloads"]
        if w["config"] == name)).arch is arch


@pytest.mark.parametrize("name", CONFIGS)
def test_shapes_and_model_config_unchanged(name):
    c = _config(name)
    arch = spec.architecture(c["model_type"])
    want = READ["configs"][name]
    assert dataclasses.asdict(arch.shapes(c)) == want["shapes"]
    cfg = arch.model_config(name, c)
    got = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    assert _plain(got) == want["model_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_tree_unchanged(name):
    c = _config(name)
    arch = spec.architecture(c["model_type"])
    s = arch.shapes(c)
    tree = jax.eval_shape(lambda: arch.make_params(2**31 + 77, s))
    got = {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype)]
           for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == READ["configs"][name]["params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_unchanged(name):
    c = _config(name)
    arch = spec.architecture(c["model_type"])
    s = arch.shapes(c)
    want = READ["configs"][name]
    for args, flops in want["prefill_flops"].items():
        start, end = map(int, args.split(","))
        assert arch.prefill_flops(s, start, end) == flops
    for args, work in want["decode_work"].items():
        steps, ctx, tokens = map(int, args.split(","))
        assert list(arch.decode_work(s, steps, ctx, tokens)) == work


@pytest.fixture(scope="module")
def tiny():
    t = READ["tiny"]
    arch = spec.architecture(t["config"]["model_type"])
    return t, arch, arch.shapes(t["config"])


def test_tiny_weights_unchanged(tiny):
    t, arch, s = tiny
    params = arch.make_params(t["seed"], s)
    got = {jax.tree_util.keystr(p):
           hashlib.sha256(np.asarray(a).tobytes()).hexdigest()
           for p, a in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == t["params_sha256"]


@pytest.mark.parametrize("control", [False, True])
def test_tiny_reference_gaps_unchanged(tiny, control):
    t, arch, s = tiny
    req = (np.asarray(t["prompt"], np.int32), t["served"])
    gaps = arch.served_gaps(t["seed"], s, [req], control=control)
    want = t["control_gaps" if control else "gaps"]
    np.testing.assert_allclose(gaps, want, rtol=0, atol=1e-6)


def _tree(tmp_path, model_type="llama", **config):
    """A copy of the benchmark whose BENCHMARK.json has one more
    configuration, ``probe`` (smollm-360m's file with ``model_type`` and
    ``config`` set), and one more cell, ``probe.chat``."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    c = dict(_config("smollm-360m"), model_type=model_type, **config)
    (root / "bench" / "configs" / "probe.json").write_text(json.dumps(c))
    bench = spec.load_benchmark()
    bench["configs"].append(dict(bench["configs"][0], name="probe",
                                 file="bench/configs/probe.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="probe.chat",
                                   config="probe"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_architecture_is_a_new_file(tmp_path):
    """An architecture file that exists only in another tree is found there
    by its ``model_type`` and drives a whole run of the harness; the files
    of ``bench/`` are the committed ones."""
    root = _tree(tmp_path, model_type="toy")
    toy = root / "bench" / "arch" / "toy.py"
    toy.write_text(DENSE.read_text().replace(
        'MODEL_TYPES = ("llama", "mistral")', 'MODEL_TYPES = ("toy",)'))
    cell = spec.load_cell("probe.chat", root)
    assert cell.arch.__file__ == str(toy)
    assert spec.load_cell("smollm-360m.chat-prefix-sat", root).arch.__file__ \
        == str(root / "bench" / "arch" / "dense.py")

    cell.config.update(num_hidden_layers=2, hidden_size=64,
                       intermediate_size=128, num_attention_heads=4,
                       num_key_value_heads=2, vocab_size=512)
    cell.traffic.update(max_context=256, lead_in_s=1.0)
    cell.traffic["system"].update(count=4, min=20, max=60)
    cell.traffic["message"].update(median=16, min=4, max=64)
    cell.traffic["budget"].update(median=8, min=2, max=24)
    cell.traffic["arrival"]["rate_per_s"] = 4.0
    cell.config["serve"].update(max_batch=4, max_seq_len=384, num_pages=256,
                                prefill_buckets=[16, 32, 64, 128, 192, 256])
    cell.e2e = cell.e2e + [spec.Metric("mfu", "%")]
    out = harness.run_cell(cell, 2**32 + 21, 2.0, False, time.perf_counter(),
                           require_tpu=False,
                           limits=spec.load_limits(
                               "smollm-360m.chat-prefix-sat"))
    assert out["correct"], out["check"]
    assert out["metrics"]["mfu"]["value"] > 0


@pytest.mark.parametrize("case,names", [
    ("unknown", ["dense.py"]),
    ("twice", ["dense.py", "twin.py"]),
    ("sliding_window", ["sliding_window"]),
])
def test_refused_before_anything_is_built(tmp_path, monkeypatch, case, names):
    if case == "unknown":
        root = _tree(tmp_path, model_type="nope")
    elif case == "twice":
        root = _tree(tmp_path)
        (root / "bench" / "arch" / "twin.py").write_text(
            'MODEL_TYPES = ("llama",)\n')
    else:
        root = _tree(tmp_path, sliding_window=4096)
    with pytest.raises(ValueError) as e:
        cell = spec.load_cell("probe.chat", root)
        monkeypatch.setattr(cell.arch, "make_params", None)
        harness.run_cell(cell, 1, 1.0, False, time.perf_counter(),
                         require_tpu=False)
    for n in names:
        assert n in str(e.value)
