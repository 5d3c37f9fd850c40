import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any other import (jax locks the device
count at first backend init).  512 virtual CPU devices stand in for
2 pods x 256 chips; the single-pod mesh uses the first 256.

For every cell this records, as JSON in --out:
  * compile success + memory_analysis (bytes per device -> "it fits"),
  * cost_analysis flops/bytes + the scan-trip-count corrections
    (launch/analytic.py — XLA counts while bodies once),
  * the collective inventory with wire bytes (launch/hlo_analysis.py),
  * MODEL_FLOPS and the analytic step flops.

Usage:
  python -m repro.launch.dryrun                       # all cells, both meshes
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import (SHAPES, ShapeSpec, TrainConfig, get_config,
                          input_specs, shape_applicable)
from repro.configs import ASSIGNED_ARCHS
from repro.launch import analytic
from repro.launch.hlo_analysis import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import ExecPolicy, init_params
from repro.sharding import (batch_shardings, named, opt_state_shardings,
                            param_shardings, state_shardings)
from repro.train.steps import (abstract_decode_state, abstract_train_state,
                               make_decode_step, make_prefill_step,
                               make_train_step)

DEFAULT_POLICY = ExecPolicy(scan_layers=False, q_chunk=512, kv_chunk=512,
                            remat="block")


def _train_shardings(state, mesh, drop_logical=()):
    ps = param_shardings(state["params"], mesh, drop_logical)
    sh: Dict[str, Any] = {
        "params": ps,
        "opt": {"m": opt_state_shardings(ps, state["params"], mesh),
                "count": named(mesh, (), ())},
        "step": named(mesh, (), ()),
    }
    if "v" in state["opt"]:
        sh["opt"]["v"] = opt_state_shardings(ps, state["params"], mesh)
    if "ef" in state:
        sh["ef"] = ps
    return sh


def build_cell(arch: str, shape_name: str, mesh, policy: ExecPolicy,
               scan_layers: Optional[bool] = None,
               cfg_patch: Optional[Dict[str, Any]] = None):
    """Returns (fn, args, in_shardings, donate_argnums) for the cell."""
    cfg = get_config(arch)
    if cfg_patch:
        cfg = dataclasses.replace(cfg, **cfg_patch)
    spec = SHAPES[shape_name]
    if scan_layers is not None:
        policy = dataclasses.replace(policy, scan_layers=scan_layers)
    batch = input_specs(cfg, spec)
    b_sh = batch_shardings(batch, mesh)
    drop = ("experts",) if cfg.moe_expert_sharding == "replicate" else ()

    if spec.kind == "train":
        tcfg = TrainConfig(global_batch=spec.global_batch,
                           seq_len=spec.seq_len, remat=policy.remat)
        state = abstract_train_state(cfg, tcfg)
        s_sh = _train_shardings(state, mesh, drop)
        fn = make_train_step(cfg, tcfg, policy)
        return fn, (state, batch), (s_sh, b_sh), (0,)

    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    p_sh = param_shardings(params, mesh, drop)
    states = abstract_decode_state(cfg, spec.global_batch, spec.seq_len)
    st_sh = state_shardings(states, mesh)
    if spec.kind == "prefill":
        fn = make_prefill_step(cfg, policy)
    else:
        fn = make_decode_step(cfg, policy)
    return fn, (params, states, batch), (p_sh, st_sh, b_sh), (1,)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             policy: ExecPolicy = DEFAULT_POLICY,
             scan_layers: Optional[bool] = None,
             with_hlo: bool = True,
             cfg_patch: Optional[Dict[str, Any]] = None,
             mesh_shape: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
    """One cell.  ``cfg_patch`` / ``mesh_shape`` support §Perf variants
    (e.g. {"moe_dispatch": "batched"} / {"data": 32, "model": 8})."""
    cfg = get_config(arch)
    if cfg_patch:
        cfg = dataclasses.replace(cfg, **cfg_patch)
    spec = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "policy": {"scan_layers": policy.scan_layers
                   if scan_layers is None else scan_layers,
                   "q_chunk": policy.q_chunk, "kv_chunk": policy.kv_chunk,
                   "remat": policy.remat,
                   "constrain_recurrence": policy.constrain_recurrence},
        "cfg_patch": cfg_patch or {}, "mesh_shape": mesh_shape or {},
    }
    if not shape_applicable(cfg, spec):
        rec["status"] = "skip"
        rec["reason"] = ("long_500k needs sub-quadratic attention; "
                         f"{arch} attends globally")
        return rec
    try:
        if mesh_shape:
            from repro.launch.mesh import make_mesh_for
            mesh = make_mesh_for(tuple(mesh_shape.values()),
                                 tuple(mesh_shape.keys()))
        else:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        fn, args, in_sh, donate = build_cell(arch, shape_name, mesh, policy,
                                             scan_layers, cfg_patch)
        t0 = time.time()
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        ca = compiled.cost_analysis()
        # NOTE: the compiled module is the per-device SPMD program, so
        # cost_analysis flops/bytes are PER DEVICE (verified empirically);
        # corrections are computed per-device via sharding degrees.
        flops_hlo = float(ca.get("flops", 0.0))
        bytes_hlo = float(ca.get("bytes accessed", 0.0))
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        use_scan = policy.scan_layers if scan_layers is None else scan_layers
        reps = (cfg.num_layers // len(cfg.pattern)) if use_scan else 0
        corr = analytic.scan_corrections(cfg, spec, policy.q_chunk,
                                         policy.kv_chunk,
                                         mesh_shape=mesh_shape,
                                         layer_scan_reps=reps)
        rec["flops_hlo_perdev"] = flops_hlo
        rec["bytes_hlo_perdev"] = bytes_hlo
        rec["scan_correction"] = {"flops": corr.flops, "bytes": corr.bytes,
                                  **corr.detail}
        rec["flops_perdev"] = flops_hlo + corr.flops
        rec["bytes_perdev"] = bytes_hlo + corr.bytes
        rec["model_flops"] = analytic.model_flops(cfg, spec)
        rec["analytic_step_flops"] = analytic.step_flops(cfg, spec)

        if with_hlo:
            hlo = compiled.as_text()
            st = collective_stats(hlo)
            rec["collectives"] = {
                "wire_bytes": st.total_wire_bytes,
                "by_kind": st.by_kind,
                "count": st.count,
            }
            del hlo
        rec["num_devices"] = mesh.devices.size
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def cell_path(outdir: str, arch: str, shape: str, mesh_kind: str) -> str:
    return os.path.join(outdir, f"{arch}__{shape}__{mesh_kind}.json")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default=None, choices=["single", "multi"],
                    help="default: both")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--scan-layers", action="store_true",
                    help="scan over layers (fast compile; multi-pod proof)")
    ap.add_argument("--force", action="store_true", help="redo existing cells")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    if args.list:
        for a in archs:
            for s in shapes:
                ok = shape_applicable(get_config(a), SHAPES[s])
                print(f"{a:28s} {s:12s} {'run' if ok else 'SKIP'}")
        return

    os.makedirs(args.out, exist_ok=True)
    for a in archs:
        for s in shapes:
            for m in meshes:
                path = cell_path(args.out, a, s, m)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {a} {s} {m}")
                    continue
                t0 = time.time()
                rec = run_cell(a, s, m,
                               scan_layers=True if args.scan_layers else None)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                jax.clear_caches()  # bound compile-cache growth across cells
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compile={rec['compile_s']}s "
                             f"flops/dev={rec['flops_perdev']:.3e} "
                             f"coll={rec.get('collectives', {}).get('wire_bytes', 0):.3e}B")
                elif status == "error":
                    extra = rec["error"][:120]
                print(f"[{status}] {a} {s} {m} ({time.time()-t0:.0f}s) {extra}",
                      flush=True)


if __name__ == "__main__":
    main()
