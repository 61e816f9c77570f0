import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: for each cell we build the production mesh from placeholder
host devices, jit the right step function with full NamedShardings,
``.lower().compile()`` it, and record

  - ``compiled.memory_analysis()``  (fits-per-chip evidence)
  - ``compiled.cost_analysis()``    (per-device FLOPs / bytes)
  - the collective schedule parsed from the compiled HLO

into a JSON artifact under experiments/dryrun/. EXPERIMENTS.md §Dry-run
and §Roofline are generated from these artifacts (benchmarks/roofline).

NOTE the XLA_FLAGS line above must execute before ANY other import —
jax locks the device count at first init. Do not set that flag globally:
smoke tests and benches must see 1 device.
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from ..analysis.roofline import parse_collectives, roofline_from_artifact
from ..config import SHAPES, RunConfig
from ..configs import REGISTRY, cells, get_config
from ..models import build
from ..models.params import ParamDef, tree_size
from ..optim import OptConfig
from ..parallel.axes import ShardingRules, use_rules
from ..parallel.plan import make_plan
from .mesh import make_production_mesh
from .steps import make_prefill_step, make_serve_step, make_train_step

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def model_flops_for(model, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference), N = active
    params minus the embedding gather table, D = tokens processed."""
    cfg = model.cfg
    n = model.n_params
    if cfg.family == "moe":
        routed = tree_size(
            {
                k: v
                for k, v in model.defs["layers"]["ffn"].items()
                if k in ("wi_gate", "wi_up", "wo")
            }
        )
        n -= routed * (1.0 - cfg.top_k / cfg.n_experts)
    n -= cfg.vocab * cfg.d_model  # embedding gather does no matmul flops
    if shape.mode == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.mode == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def microbatch_policy(cfg, shape) -> int:
    """Gradient-accumulation factor for train cells: activation
    transients shrink by this factor so the biggest models fit HBM."""
    if shape.mode != "train":
        return 1
    n = cfg.n_params
    if n > 40e9:
        return 8
    if n > 5e9:
        return 4
    return 1


def variant_cfg(cfg, k: int):
    """A k-unit fully-unrolled copy of the arch for exact cost
    accounting (cost_analysis counts loop bodies once; the unrolled
    1-unit and 2-unit variants give base + per-unit costs exactly)."""
    kw = dict(scan_layers=False, unroll_inner=True)
    fam = cfg.family
    if fam in ("dense", "moe"):
        kw["n_layers"] = k
    elif fam == "vlm":
        kw["n_layers"] = k * cfg.cross_every
    elif fam == "hybrid":
        kw["n_layers"] = k * cfg.attn_every
    elif fam == "ssm":
        kw["n_layers"] = k
        kw["slstm_at"] = ()  # sLSTM counted as mLSTM-equivalent (noted)
    elif fam == "encdec":
        kw["n_layers"] = k
        kw["n_enc_layers"] = k
    return dataclasses.replace(cfg, **kw)


def n_units(cfg) -> int:
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.cross_every
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers  # dense/moe/ssm layers; encdec (enc, dec) pairs


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    strategy: str = "dos",
    fsdp: bool = True,
    remat: bool = True,
    donate: bool = True,
    cfg_override=None,
    microbatches: int | None = None,
    unroll_mb: bool = False,
):
    """Lower + compile one cell; returns (artifact dict, compiled)."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_chips = mesh.size
    model = build(cfg)
    rules = ShardingRules(
        mesh, strategy=strategy, fsdp=fsdp and shape.mode == "train"
    )
    plan = make_plan(model, shape, rules)
    mb = microbatches if microbatches is not None else microbatch_policy(cfg, shape)

    if shape.mode == "train":
        step = make_train_step(model, OptConfig(), remat=remat,
                               microbatches=mb, unroll_mb=unroll_mb)
        donate_argnums = (0, 1) if donate else ()
    elif shape.mode == "prefill":
        step = make_prefill_step(model, max_len=shape.seq_len)
        donate_argnums = ()
    else:
        step = make_serve_step(model)
        donate_argnums = (1,) if donate else ()

    t0 = time.time()
    with use_rules(rules), mesh:
        lowered = jax.jit(
            step,
            in_shardings=plan.in_shardings,
            out_shardings=plan.out_shardings,
            donate_argnums=donate_argnums,
        ).lower(*plan.abstract)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    rf = roofline_from_artifact(
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        n_chips=n_chips,
        cost=cost,
        coll=coll,
        model_flops=model_flops_for(model, shape),
    )

    artifact = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "strategy": strategy,
        "fsdp": bool(fsdp and shape.mode == "train"),
        "n_chips": n_chips,
        "mode": shape.mode,
        "microbatches": mb,
        "n_params": model.n_params,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                / 2**30, 3,
            ),
        },
        "cost": {k: v for k, v in cost.items() if k in ("flops", "bytes accessed")},
        "collectives": {
            "counts": coll.counts,
            "wire_bytes": coll.wire_bytes,
            "by_op_bytes": coll.by_op_bytes,
        },
        "roofline": rf.to_dict(),
    }
    return artifact, compiled


def measure_cost_corrected(arch, shape_name, *, multi_pod, strategy, fsdp,
                           remat, microbatches=None):
    """Exact per-step cost via unrolled 1-unit / 2-unit variants:
    total(metric) = cost(1) + (units - 1) * (cost(2) - cost(1))."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mb = microbatches if microbatches is not None else microbatch_policy(cfg, shape)
    outs = []
    for k in (1, 2):
        vcfg = variant_cfg(cfg, k)
        art, compiled = lower_cell(
            arch, shape_name, multi_pod=multi_pod, strategy=strategy,
            fsdp=fsdp, remat=remat, cfg_override=vcfg,
            microbatches=mb, unroll_mb=True,
        )
        coll = parse_collectives(compiled.as_text())
        outs.append((art["cost"], coll))
    (c1, coll1), (c2, coll2) = outs
    units = n_units(cfg)

    def comb(a, b):
        return a + (units - 1) * (b - a)

    cost = {
        "flops": comb(c1.get("flops", 0.0), c2.get("flops", 0.0)),
        "bytes accessed": comb(
            c1.get("bytes accessed", 0.0), c2.get("bytes accessed", 0.0)
        ),
    }
    wire = comb(coll1.wire_bytes, coll2.wire_bytes)
    by_op = {
        op: comb(coll1.by_op_bytes.get(op, 0.0), coll2.by_op_bytes.get(op, 0.0))
        for op in set(coll1.by_op_bytes) | set(coll2.by_op_bytes)
    }
    counts = {
        op: int(comb(coll1.counts.get(op, 0), coll2.counts.get(op, 0)))
        for op in set(coll1.counts) | set(coll2.counts)
    }
    from ..analysis.roofline import CollectiveStats

    coll = CollectiveStats(
        wire_bytes=wire, result_bytes=0.0, counts=counts, by_op_bytes=by_op
    )
    return cost, coll


def cell_key(arch, shape, mesh_name, strategy):
    return f"{arch}__{shape}__{mesh_name}__{strategy}"


def run_and_save(arch, shape_name, *, multi_pod, strategy="dos", force=False,
                 verbose=True, **kw):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ART_DIR.mkdir(parents=True, exist_ok=True)
    out = ART_DIR / (cell_key(arch, shape_name, mesh_name, strategy) + ".json")
    if out.exists() and not force:
        if verbose:
            print(f"[skip] {out.name} (cached)")
        return json.loads(out.read_text())
    try:
        artifact, compiled = lower_cell(
            arch, shape_name, multi_pod=multi_pod, strategy=strategy, **kw
        )
        # Exact cost accounting (single-pod roofline only — the
        # multi-pod pass proves compilation/sharding).
        if not multi_pod:
            cfg = get_config(arch)
            shape = SHAPES[shape_name]
            model = build(cfg)
            cost_c, coll_c = measure_cost_corrected(
                arch, shape_name, multi_pod=multi_pod, strategy=strategy,
                fsdp=kw.get("fsdp", True), remat=kw.get("remat", True),
            )
            from ..analysis.traffic import traffic_bytes_per_device

            kbytes = traffic_bytes_per_device(
                cfg, shape, model.n_params,
                n_chips=artifact["n_chips"],
                microbatches=artifact.get("microbatches", 1),
            )
            rf = roofline_from_artifact(
                arch=arch, shape=shape_name,
                mesh_name=artifact["mesh"], n_chips=artifact["n_chips"],
                cost=cost_c, coll=coll_c,
                model_flops=model_flops_for(model, shape),
                kernel_bytes=kbytes,
            )
            artifact["cost_corrected"] = cost_c
            artifact["collectives_corrected"] = {
                "counts": coll_c.counts,
                "wire_bytes": coll_c.wire_bytes,
                "by_op_bytes": coll_c.by_op_bytes,
            }
            artifact["roofline"] = rf.to_dict()
    except Exception as e:  # record failures — they are bugs to fix
        artifact = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "strategy": strategy, "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        out.write_text(json.dumps(artifact, indent=1))
        if verbose:
            print(f"[FAIL] {out.name}: {artifact['error']}")
        return artifact
    out.write_text(json.dumps(artifact, indent=1))
    if verbose:
        r = artifact["roofline"]
        print(
            f"[ok] {out.name}: mem/dev={artifact['memory']['peak_per_device_gb']}GB "
            f"flops/dev={artifact['cost'].get('flops', 0):.3e} "
            f"dominant={r['dominant']} step~{r['step_s']*1e3:.2f}ms "
            f"(compile {artifact['compile_s']}s)"
        )
    return artifact


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--strategy", default="dos", choices=["dos", "megatron", "zero", "auto"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default=None,
                    help="'save_gathered' keeps FSDP gathers across bwd")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    live, skipped = cells()
    if args.list:
        for a, s in live:
            print(f"{a} {s}")
        for a, s, why in skipped:
            print(f"# SKIP {a} {s}: {why}")
        return

    todo = [
        (a, s)
        for a, s in live
        if (args.arch is None or a == args.arch)
        and (args.shape is None or s == args.shape)
    ]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_fail = 0
    for a, s in todo:
        for mp in meshes:
            remat = (args.remat_policy or True) if not args.no_remat else False
            art = run_and_save(
                a, s, multi_pod=mp, strategy=args.strategy,
                fsdp=not args.no_fsdp, remat=remat,
                force=args.force,
            )
            n_fail += 1 if "error" in art else 0
    print(f"done: {len(todo) * len(meshes)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
