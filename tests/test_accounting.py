"""Validates the roofline accounting methodology (launch/analytic.py).

Ground truth: a fully-unrolled, unchunked compile of a small model — its
cost_analysis is exact (zero while loops).  The corrected numbers for the
chunked / layer-scanned variants of the SAME program must agree within 5%.
"""
import dataclasses
import subprocess
import sys
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
from repro.config import get_config, TrainConfig
from repro.train.steps import abstract_train_state, make_train_step
from repro.sharding import batch_shardings
from repro.models.transformer import ExecPolicy
from repro.launch.mesh import make_mesh_for
from repro.launch.dryrun import _train_shardings
from repro.launch import analytic
from repro.config.shapes import ShapeSpec

cfg = dataclasses.replace(get_config("smollm-360m"), num_layers=4)
spec = ShapeSpec("t", "train", 1024, 8)
tcfg = TrainConfig(global_batch=8, seq_len=1024)
mesh = make_mesh_for((2, 4), ("data", "model"))
mesh_shape = {"data": 2, "model": 4}
state = abstract_train_state(cfg, tcfg)
s_sh = _train_shardings(state, mesh)
batch = {k: jax.ShapeDtypeStruct((8, 1024), jnp.int32 if k != "loss_mask"
         else jnp.float32) for k in ("tokens", "targets", "loss_mask")}
b_sh = batch_shardings(batch, mesh)

def flops_for(pol, reps):
    fn = make_train_step(cfg, tcfg, pol)
    with mesh:
        comp = jax.jit(fn, in_shardings=(s_sh, b_sh),
                       donate_argnums=0).lower(state, batch).compile()
    raw = comp.cost_analysis().get("flops")
    corr = analytic.scan_corrections(cfg, spec, pol.q_chunk or 0,
                                     pol.kv_chunk or 0, mesh_shape, reps)
    return raw + corr.flops

# flops_for already restores the xent scan (the only scan when q_chunk=0)
gt = flops_for(ExecPolicy(scan_layers=False, q_chunk=0, kv_chunk=0), 0)
chunked = flops_for(ExecPolicy(scan_layers=False, q_chunk=512, kv_chunk=512), 0)
scanned = flops_for(ExecPolicy(scan_layers=True, q_chunk=512, kv_chunk=512), 4)
print("RESULT:" + json.dumps({"gt": gt, "chunked": chunked, "scanned": scanned}))
"""


def test_scan_corrections_match_unrolled_ground_truth():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][0]
    r = json.loads(line[len("RESULT:"):])
    assert abs(r["chunked"] / r["gt"] - 1) < 0.05, r
    assert abs(r["scanned"] / r["gt"] - 1) < 0.05, r


def test_model_flops_formula():
    from repro.config import SHAPES, get_config
    from repro.launch.analytic import model_flops
    cfg = get_config("gemma-7b")
    mf = model_flops(cfg, SHAPES["train_4k"])
    assert abs(mf - 6 * cfg.param_count() * 4096 * 256) / mf < 1e-9
    moe = get_config("olmoe-1b-7b")
    mfm = model_flops(moe, SHAPES["train_4k"])
    assert mfm == 6 * moe.active_param_count() * 4096 * 256
