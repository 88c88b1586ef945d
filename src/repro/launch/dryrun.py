import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment: MULTI-POD DRY-RUN steps 0-4).

A CPU-only tool: importing this module forces 512 virtual CPU devices
through ``XLA_FLAGS`` (above), so no path that runs on a chip — the
training and serving entry points, ``chip_smoke.py`` — ever imports it.

Lowers + compiles train_step / serve_step / prefill for every
(architecture x input shape) on the single-pod 16x16 mesh and the 2x16x16
multi-pod mesh, records memory_analysis() + cost_analysis() + collective
bytes parsed from the optimized HLO, and writes one JSON per cell to
benchmarks/results/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --sweep [--mesh both]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.registry import ARCH_IDS, get_config, get_shape
from ..distributed import sharding as shd
from ..models.config import SHAPES, cell_is_runnable
from . import specs as spec_mod
from . import steps as steps_mod
from .mesh import make_production_mesh

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand sizes of every collective op in optimized HLO.
    (cost_analysis has no collective term — assignment §ROOFLINE.)"""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # ops appear as:  %name = <shape> all-reduce(...)
        m = re.match(r"%?[\w\.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)", s)
        if not m:
            continue
        shape_txt, op = m.groups()
        # ignore -start/-done duplicates by only counting 'start' or plain
        if f"{op}-done" in s:
            continue
        out[op] += _shape_bytes(shape_txt)
        count[op] += 1
    return {"bytes": out, "counts": count,
            "total_bytes": int(sum(out.values()))}


# --- perf-iteration variants (EXPERIMENTS.md §Perf) ------------------------
# Each maps to ModelConfig overrides (+ 'serve_tp_only' handled separately).
VARIANTS = {
    "baseline": {},
    # train-cell iterations
    "sp": {"seq_shard_activations": True},
    "sp_dots": {"seq_shard_activations": True, "remat": "dots"},
    "sp_dots_padheads": {"seq_shard_activations": True, "remat": "dots",
                         "q_head_pad": 8},
    "dots": {"remat": "dots"},
    "padheads": {"q_head_pad": 8},
    # decode-cell iterations
    "tponly": {"serve_tp_only": True},
    "tponly_int8kv": {"serve_tp_only": True, "kv_cache_dtype": "int8"},
    "int8kv": {"kv_cache_dtype": "int8"},
    "int8kv_multistep4": {"kv_cache_dtype": "int8", "decode_steps": 4},
    "multistep4": {"decode_steps": 4},
    # moe iterations
    "sp_group128": {"seq_shard_activations": True, "moe_group_size": 128},
    "sp_dots_group128": {"seq_shard_activations": True, "remat": "dots",
                         "moe_group_size": 128},
}


def apply_variant(cfg, variant: str):
    import dataclasses as _dc
    over = dict(VARIANTS[variant])
    serve_tp_only = over.pop("serve_tp_only", False)
    return _dc.replace(cfg, **over) if over else cfg, serve_tp_only


def lower_cell(arch: str, shape_id: str, mesh, *, smoke: bool = False,
               cfg_override=None, variant: str = "baseline"):
    """Returns (lowered, meta) for one (arch x shape) cell."""
    cfg = cfg_override if cfg_override is not None \
        else get_config(arch, smoke=smoke)
    cfg, serve_tp_only = apply_variant(cfg, variant)
    if cfg.seq_shard_activations and "pod" in mesh.axis_names:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, mesh_batch_axes=("pod", "data"))
    shape = get_shape(shape_id)
    specs = spec_mod.input_specs(cfg, shape)

    if shape.kind in ("train",):
        tcfg = steps_mod.LMTrainConfig()
        train_step, tx = steps_mod.make_train_step(cfg, tcfg)
        params_shape = jax.eval_shape(
            lambda: steps_mod.init_lm_params(jax.random.PRNGKey(0), cfg))
        opt_shape = jax.eval_shape(tx.init, params_shape)
        p_specs, o_specs, b_specs = steps_mod.train_shardings(
            mesh, cfg, params_shape, opt_shape, specs)
        jitted = jax.jit(
            train_step,
            in_shardings=(shd.to_named(mesh, p_specs),
                          shd.to_named(mesh, o_specs),
                          shd.to_named(mesh, b_specs)),
            donate_argnums=(0, 1))
        with mesh:
            lowered = jitted.lower(params_shape, opt_shape, specs)
        return lowered, {"step": "train_step"}

    if shape.kind == "prefill":
        prefill = steps_mod.make_prefill_step(cfg)
        params_shape = jax.eval_shape(
            lambda: steps_mod.init_lm_params(jax.random.PRNGKey(0), cfg))
        p_specs = shd.param_specs(mesh, params_shape)
        b_specs = shd.input_sharding_specs(mesh, specs, cfg)
        jitted = jax.jit(prefill,
                         in_shardings=(shd.to_named(mesh, p_specs),
                                       shd.to_named(mesh, b_specs)))
        with mesh:
            lowered = jitted.lower(params_shape, specs)
        return lowered, {"step": "prefill_step"}

    # decode
    serve = steps_mod.make_serve_step(cfg)
    params_shape = jax.eval_shape(
        lambda: steps_mod.init_lm_params(jax.random.PRNGKey(0), cfg))
    p_specs = shd.param_specs(mesh, params_shape, fsdp=not serve_tp_only)
    cache_shape = specs["cache"]
    c_specs = shd.cache_specs(mesh, cache_shape, cfg)
    tok_spec = P(shd._batch_ok(mesh, specs["tokens"].shape[0]), None)
    extra = {}
    extra_specs = {}
    if "embeds" in specs:
        extra["embeds"] = specs["embeds"]
        extra["position_ids"] = specs["position_ids"]
        extra_specs = {
            "embeds": P(shd._batch_ok(mesh, specs["embeds"].shape[0]),
                        None, None),
            "position_ids": P(None, None, None)}
    jitted = jax.jit(
        serve,
        in_shardings=(shd.to_named(mesh, p_specs),
                      NamedSharding(mesh, tok_spec),
                      shd.to_named(mesh, c_specs),
                      shd.to_named(mesh, extra_specs)),
        donate_argnums=(2,))
    with mesh:
        lowered = jitted.lower(params_shape, specs["tokens"], cache_shape,
                               extra)
    return lowered, {"step": "serve_step"}


def _cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (None when the backend
    records no cost metadata)."""
    return compiled.cost_analysis() or {}


def run_cell(arch: str, shape_id: str, mesh_kind: str, *,
             smoke: bool = False, save: bool = True,
             calibrate: bool = True, variant: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = 512 if mesh_kind == "multi" else 256
    cfg = get_config(arch, smoke=smoke)
    shape = get_shape(shape_id)
    ok, why = cell_is_runnable(cfg, shape)
    rec = {"arch": arch, "shape": shape_id, "mesh": mesh_kind,
           "chips": n_chips, "family": cfg.family, "smoke": smoke,
           "variant": variant,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "status": "skipped", "skip_reason": why}
    if not ok:
        return _save(rec, save)
    t0 = time.time()
    try:
        lowered, meta = lower_cell(arch, shape_id, mesh, smoke=smoke,
                                   variant=variant)
        rec.update(meta)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(mem, "peak_memory_in_bytes",
                        getattr(mem, "temp_size_in_bytes", 0))),
        }
        ca = _cost_analysis(compiled)
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
        rec["collectives"] = collective_bytes(compiled.as_text())
        # --- calibration: XLA cost_analysis counts a while-loop body ONCE,
        # so the scanned-layer program under-reports per-step cost by ~L.
        # Lower unrolled L=1 and L=2 programs; per-layer cost = c2 - c1 and
        # corrected total = c1 + (L-1)*(c2-c1).  (See EXPERIMENTS.md §Dry-run
        # methodology.)
        if calibrate:
            rec["calibration"] = _calibrate(arch, shape_id, mesh, cfg,
                                            smoke=smoke, variant=variant)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _save(rec, save)


def _calibrate(arch: str, shape_id: str, mesh, cfg, *, smoke: bool,
               variant: str = "baseline") -> dict:
    import dataclasses
    out = {}
    L_full = cfg.num_layers
    for L in (1, 2):
        cal_cfg = dataclasses.replace(
            cfg, num_layers=L,
            encoder_layers=min(cfg.encoder_layers, L),
            scan_layers=False)
        lowered, _ = lower_cell(arch, shape_id, mesh, smoke=smoke,
                                cfg_override=cal_cfg, variant=variant)
        compiled = lowered.compile()
        ca = _cost_analysis(compiled)
        out[f"L{L}"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": collective_bytes(
                compiled.as_text())["total_bytes"],
        }
    c1, c2 = out["L1"], out["L2"]
    out["corrected"] = {
        k: c1[k] + (L_full - 1) * max(c2[k] - c1[k], 0.0)
        for k in ("flops", "bytes_accessed", "collective_bytes")
    }
    out["num_layers"] = L_full
    return out


def _save(rec: dict, save: bool) -> dict:
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        smoke = "_smoke" if rec.get("smoke") else ""
        var = rec.get("variant", "baseline")
        vtag = f"_{var}" if var != "baseline" else ""
        name = (f"dryrun_{rec['mesh']}_{rec['arch']}_{rec['shape']}"
                f"{vtag}{smoke}.json")
        (RESULTS_DIR / name).write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI sanity)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-calibration", action="store_true",
                    help="skip the L1/L2 roofline calibration lowerings")
    ap.add_argument("--variant", default="baseline",
                    choices=list(VARIANTS),
                    help="perf-iteration variant (EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.sweep:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --sweep"
        cells = [(args.arch, args.shape)]

    failures = 0
    for mesh_kind in meshes:
        for arch, shape_id in cells:
            out = (RESULTS_DIR /
                   f"dryrun_{mesh_kind}_{arch}_{shape_id}.json")
            if args.skip_existing and out.exists():
                prev = json.loads(out.read_text())
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[cached ] {mesh_kind:6s} {arch:24s} "
                          f"{shape_id:12s}", flush=True)
                    continue
            # multi-pod pass proves the pod axis shards; the roofline table
            # is single-pod only, so calibration runs on 'single' only.
            calibrate = (mesh_kind == "single") and not args.no_calibration
            rec = run_cell(arch, shape_id, mesh_kind, smoke=args.smoke,
                           calibrate=calibrate, variant=args.variant)
            line = (f"[{rec['status']:7s}] {mesh_kind:6s} {arch:24s} "
                    f"{shape_id:12s}")
            if rec["status"] == "ok":
                line += (f" compile={rec['compile_s']:.0f}s "
                         f"flops={rec['cost']['flops']:.3e} "
                         f"coll={rec['collectives']['total_bytes']:.3e}B")
            elif rec["status"] == "error":
                line += " " + rec["error"][:120]
                failures += 1
            print(line, flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
