"""The benchmark's own tests: run with ``python -m pytest bench/tests`` from
the root of the checkout, on the CPU (``JAX_PLATFORMS=cpu``)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


# Cells whose files the benchmark keeps for a later benchmark PR but whose
# entries are not in BENCHMARK.json: workload -> (configuration, traffic).
KEPT = {"smollm-360m.chat-prefix": ("smollm-360m", "chat-prefix")}


def _cells():
    from bench.spec import load_benchmark
    return [w["name"] for w in load_benchmark()["workloads"]]


# The cells of BENCHMARK.json, which the CPU tests run at a tiny size.
CELLS = _cells()


def any_cell(name: str):
    """The workload ``name`` from BENCHMARK.json, or a kept one."""
    import json
    from bench.spec import BENCH, Cell, architecture, load_cell
    try:
        return load_cell(name)
    except KeyError:
        conf, mix = KEPT[name]
        with open(BENCH / "configs" / f"{conf}.json") as f:
            config = json.load(f)
        with open(BENCH / "traffic" / f"{mix}.json") as f:
            traffic = json.load(f)
        return Cell(name, conf, mix, 1, config,
                    architecture(config["model_type"]), traffic, [], [])


def tiny_cell(name: str, dtype: str = "bfloat16", widths: bool = False):
    """The cell ``name`` at a size the CPU runs in seconds: the same code
    paths (prefix reuse, buckets, backlog), two layers, short lengths, and
    tiny widths unless ``widths`` keeps the configuration's own."""
    cell = any_cell(name)
    c, t = cell.config, cell.traffic
    c.update(num_hidden_layers=2, dtype=dtype)
    if not widths:
        c.update(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2,
                 vocab_size=512)
    if "system" in t:
        t["system"].update(count=4, min=20, max=60)
        t["message"].update(median=16, min=4, max=64)
        t["budget"].update(median=8, min=2, max=24)
        t["max_context"] = 256
        t["lead_in_s"] = 1.0
        t["arrival"]["rate_per_s"] = 4.0
        c["serve"].update(max_batch=4, max_seq_len=384, num_pages=256,
                          prefill_buckets=[16, 32, 64, 128, 192, 256])
    else:
        t["message"].update(median=64, min=32, max=192)
        t["budget"].update(median=16, min=4, max=48)
        t["max_context"] = 256
        t["arrival"]["count"] = 4000
        c["serve"].update(max_batch=4, max_seq_len=256, num_pages=128,
                          prefill_buckets=[32, 64, 128, 192])
    return cell
