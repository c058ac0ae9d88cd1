import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
derive roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-7b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Outputs one JSON per cell under experiments/dryrun/.
"""

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from ..analysis.roofline import build_roofline, model_flops
from ..configs.base import ShapeSpec, shape_by_name, shapes_for
from ..configs.registry import all_arch_names, get
from ..dist import sharding as shd
from ..dist.steps import make_decode_step, make_prefill_step, make_train_step, opt_config_for
from ..models.api import active_params, family_for
from ..optim import adamw
from .mesh import make_production_mesh

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _memory_dict(mem) -> dict:
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {}
    for k in keys:
        try:
            out[k] = float(getattr(mem, k))
        except Exception:
            pass
    return out


def lower_cell(cfg, shape: ShapeSpec, mesh):
    """Build the jitted step for this cell and lower it (abstract only).

    The activation mesh is installed only for the duration of the trace
    (restored on exit) so repeated dry-run cells — or anything jitted later
    in the same process — never see a stale mesh."""
    prev_mesh = shd._ACTIVATION_MESH
    try:
        return _lower_cell(cfg, shape, mesh)
    finally:
        shd.set_activation_mesh(prev_mesh)


def _lower_cell(cfg, shape: ShapeSpec, mesh):
    shd.set_activation_mesh(mesh)
    fam = family_for(cfg)
    p_specs = fam.param_specs(cfg)
    p_sh = shd.param_shardings(cfg, mesh, p_specs)
    in_specs = fam.input_specs(cfg, shape)
    in_sh = shd.input_shardings(cfg, mesh, shape, in_specs)
    rep = shd.replicated(mesh)

    if shape.kind == "train":
        opt_cfg = opt_config_for(cfg)
        o_specs = adamw.init_specs(opt_cfg, p_specs)
        o_sh = shd.opt_shardings(cfg, mesh, o_specs, p_sh)
        step = make_train_step(cfg, opt_cfg, microbatches=cfg.train_microbatches)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, in_sh),
            out_shardings=(p_sh, o_sh, {"loss": rep, "grad_norm": rep}),
            donate_argnums=(0, 1),
        )
        with mesh:
            return jitted.lower(p_specs, o_specs, in_specs)
    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_sh, in_sh))
        with mesh:
            return jitted.lower(p_specs, in_specs)
    # decode
    c_specs = fam.cache_specs(cfg, shape)
    c_sh = shd.cache_shardings(cfg, mesh, shape, c_specs)
    step = make_decode_step(cfg)
    bx = shd.batch_axes(mesh, shape.global_batch)
    from jax.sharding import NamedSharding, PartitionSpec as P

    tok_sh = NamedSharding(mesh, P(bx))
    jitted = jax.jit(
        step,
        in_shardings=(p_sh, c_sh, in_sh),
        out_shardings=(tok_sh, c_sh),
        donate_argnums=(1,),
    )
    with mesh:
        return jitted.lower(p_specs, c_specs, in_specs)


def _unit_count(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    if cfg.family == "ssm_xlstm":
        return cfg.n_layers // 2
    return cfg.n_layers


def _unit_variant(cfg, u: int):
    """Depth-u analysis variant with Python-unrolled layer loops so XLA's
    cost analysis counts every layer (while-loop bodies are counted once
    regardless of trip count — verified empirically)."""
    import dataclasses

    if cfg.family == "hybrid":
        return dataclasses.replace(
            cfg, n_layers=u * cfg.attn_every, analysis_unroll=True
        )
    if cfg.family == "ssm_xlstm":
        return dataclasses.replace(cfg, n_layers=2 * u, analysis_unroll=True)
    if cfg.family == "encdec":
        return dataclasses.replace(
            cfg, n_layers=u, n_encoder_layers=u, analysis_unroll=True
        )
    return dataclasses.replace(cfg, n_layers=u, analysis_unroll=True)


def _cell_metrics(cfg, shape, mesh) -> dict:
    compiled = lower_cell(cfg, shape, mesh).compile()
    cost = compiled.cost_analysis()
    from ..analysis.roofline import collective_bytes

    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in coll.items()},
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True) -> dict:
    """Compile the full cell (deliverable) + u=1/u=2 variants whose linear
    extrapolation recovers while-loop trip counts in the cost metrics (see
    analysis/corrections.py for the methodology)."""
    cfg = get(arch)
    shape = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size

    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()

    # layer-count extrapolation (u=1, u=2)
    units = _unit_count(cfg)
    m1 = _cell_metrics(_unit_variant(cfg, 1), shape, mesh)
    m2 = _cell_metrics(_unit_variant(cfg, 2), shape, mesh)

    def extrap(a, b):
        return a + (units - 1) * (b - a)

    from ..analysis.corrections import scan_correction_flops

    corr = scan_correction_flops(cfg, shape) / chips
    flops_x = extrap(m1["flops"], m2["flops"]) + corr
    bytes_x = extrap(m1["bytes"], m2["bytes"])
    coll_kinds = {
        k: extrap(m1["coll"].get(k, 0.0), m2["coll"].get(k, 0.0))
        for k in set(m1["coll"]) | set(m2["coll"])
    }
    cost_corrected = {"flops": flops_x, "bytes accessed": bytes_x}

    mf = model_flops(cfg, shape, active_params(cfg))
    rl = build_roofline(
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        chips=chips,
        cost=cost_corrected,
        hlo_text="",  # collectives supplied pre-extrapolated below
        model_flops_global=mf,
        memory_analysis=_memory_dict(mem),
    )
    # patch in extrapolated collectives
    coll_total = float(sum(coll_kinds.values()))
    rl.collective_bytes_per_device = coll_total
    rl.collective_by_kind = {k: int(v) for k, v in coll_kinds.items() if v}
    rl.t_collective = coll_total / 50e9
    terms = {
        "compute": rl.t_compute,
        "memory": rl.t_memory,
        "collective": rl.t_collective,
    }
    rl.bottleneck = max(terms, key=terms.get)
    t_bound = max(terms.values())
    rl.peak_fraction = mf / (chips * 197e12 * t_bound) if t_bound > 0 else 0.0
    rl.useful_flops_ratio = (
        mf / (flops_x * chips) if flops_x > 0 else 0.0
    )

    rec = json.loads(rl.to_json())
    rec["raw_full_cost"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
    }
    rec["scan_correction_flops_per_device"] = corr
    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out = OUT_DIR / f"{arch}_{shape_name}_{mesh_name}.json"
    out.write_text(json.dumps(rec, indent=1))
    if verbose:
        ma = rec["memory_analysis"]
        print(
            f"[OK] {arch} x {shape_name} x {mesh_name}: "
            f"compile {rec['compile_s']}s  "
            f"args/device {ma.get('argument_size_in_bytes', 0)/1e9:.2f} GB  "
            f"temp/device {ma.get('temp_size_in_bytes', 0)/1e9:.2f} GB  "
            f"t_comp {rl.t_compute*1e3:.2f}ms t_mem {rl.t_memory*1e3:.2f}ms "
            f"t_coll {rl.t_collective*1e3:.2f}ms -> {rl.bottleneck}",
            flush=True,
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-tm", action="store_true",
                    help="also dry-run the TM (paper) sharded configs")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for name in all_arch_names():
            cfg = get(name)
            for s in shapes_for(cfg):
                cells.append((name, s.name))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    failures = []
    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    for arch, sname in cells:
        if args.skip_existing and (OUT_DIR / f"{arch}_{sname}_{mesh_name}.json").exists():
            print(f"[SKIP] {arch} x {sname} (exists)", flush=True)
            continue
        try:
            run_cell(arch, sname, args.multi_pod)
        except Exception as e:
            failures.append((arch, sname, repr(e)))
            print(f"[FAIL] {arch} x {sname}: {e!r}", flush=True)
            traceback.print_exc()

    if args.include_tm:
        from ..dist.tm_sharded import dryrun_tm

        for tm_name in ("tm-paper", "tm-xl"):
            try:
                rec = dryrun_tm(tm_name, multi_pod=args.multi_pod, out_dir=OUT_DIR)
                print(f"[OK] {tm_name}: {rec['bottleneck']}", flush=True)
            except Exception as e:
                failures.append((tm_name, "-", repr(e)))
                print(f"[FAIL] {tm_name}: {e!r}", flush=True)

    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        sys.exit(1)
    print(f"\nall {len(cells)} cells OK")


if __name__ == "__main__":
    main()
