"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, lower + compile the real step
function (train_step for train shapes, serve_step for prefill/decode) on the
single-pod 16x16 mesh AND the 2x16x16 multi-pod mesh, print
memory_analysis()/cost_analysis(), and record the roofline terms
(EXPERIMENTS.md §Dry-run / §Roofline read from the JSON this writes).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro import compat
from repro.analysis import roofline
from repro.comm.chunnels import make_transport
from repro.configs import ARCH_IDS, SHAPES, get_config, get_shape, shape_applicable
from repro.configs.base import ShardingConfig, TrainConfig
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build
from repro.models.sharding import kv_partition_mode
from repro.serving import steps as serve_steps
from repro.train import step as train_step_mod

# The chip whose peaks the roofline applies (repro.launch.mesh.PEAKS).
MODELED_KIND = "TPU v5 lite"


def use_fake_host_devices() -> None:
    """Lower against 512 fake CPU devices — the dry run models whole pods,
    never the chip this process might find. Call before JAX touches a device."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build(cfg)
    specs = model.batch_specs(shape)
    if shape.kind == "decode":
        specs = {"batch": specs, "cache": model.cache_specs(shape)}
    return specs


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               transport: str = "xla", moe_dispatch: str | None = None,
               attn_chunk: int | None = None, remat: str | None = None,
               kv_partition: str = "auto"):
    """Lower + compile one cell; returns the result record."""
    cfg = get_config(arch)
    if moe_dispatch and cfg.moe:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch=moe_dispatch))
    if attn_chunk:
        cfg = cfg.replace(attn_chunk=attn_chunk)
    if remat:
        cfg = cfg.replace(remat=remat)
    shape = get_shape(shape_name)
    ok, skip_reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": True, "reason": skip_reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    compat.set_mesh(mesh)  # enables trace-time activation sharding constraints
    sh = ShardingConfig(pod_transport=transport, kv_partition=kv_partition)
    t0 = time.time()

    if shape.kind == "train":
        chunnels = () if transport == "xla" or not multi_pod else (
            make_transport(transport, **(
                {"fast_axis": "data", "slow_axis": "pod"}
                if transport in ("hierarchical", "hier_compressed") else {"axis": "pod"})),
        )
        model = build(cfg, mesh=mesh)
        tcfg = TrainConfig()
        # donation: the production configuration — the output state aliases
        # the input state buffers, so memory_analysis reflects the real step
        jitted = train_step_mod.jit_train_step(
            model, tcfg, chunnels, mesh, sh, model.batch_specs(shape),
            donate=True)
        state = train_step_mod.state_shapes(model, chunnels, tcfg)
        lowered = jitted.lower(state, model.batch_specs(shape))
    elif shape.kind == "prefill":
        model = build(cfg, mesh=mesh)
        jitted = serve_steps.jit_prefill(model, mesh, sh, model.batch_specs(shape))
        lowered = jitted.lower(model.param_shapes(), model.batch_specs(shape))
    else:  # decode
        attn_fn = None
        if kv_partition_mode(cfg, mesh, sh) == "sequence" and cfg.family not in ("ssm",):
            from repro.comm.kvshard import make_seq_sharded_decode
            attn_fn = make_seq_sharded_decode(mesh, "model")
        model = build(cfg, mesh=mesh, decode_attn_fn=attn_fn)
        cache = model.cache_specs(shape)
        jitted = serve_steps.jit_decode(model, mesh, sh, model.batch_specs(shape),
                                        cache, donate_cache=False)
        lowered = jitted.lower(model.param_shapes(), cache, model.batch_specs(shape))

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compat.cost_analysis(compiled)
    hlo = compiled.as_text()
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    rf = roofline.analyze(hlo, cfg, shape, mesh_shape, device_kind=MODELED_KIND)

    per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.generated_code_size_in_bytes
                     + max(0, mem.output_size_in_bytes - mem.alias_size_in_bytes))
    rec = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "multi_pod": multi_pod,
        "mesh": mesh_shape,
        "device_kind": MODELED_KIND,
        "transport": transport,
        "kv_partition": (kv_partition_mode(cfg, mesh, sh)
                         if shape.kind == "decode" else None),
        "moe_dispatch": cfg.moe.dispatch if cfg.moe else None,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total": per_dev_bytes,
            "fits_16GB": bool(per_dev_bytes < 16e9),
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if k in ("flops", "bytes accessed")},
        "roofline": rf.to_dict(),
        "skipped": False,
    }
    return rec


def cell_id(rec) -> str:
    pod = "2pod" if rec["multi_pod"] else "1pod"
    return f"{rec['arch']}__{rec['shape']}__{pod}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh only")
    ap.add_argument("--single-pod", action="store_true", help="16x16 mesh only")
    ap.add_argument("--transport", default="xla")
    ap.add_argument("--moe-dispatch", default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--kv-partition", default="auto")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    use_fake_host_devices()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = [False, True]
    if args.multi_pod:
        pods = [True]
    if args.single_pod:
        pods = [False]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                t0 = time.time()
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp,
                                     transport=args.transport,
                                     moe_dispatch=args.moe_dispatch,
                                     attn_chunk=args.attn_chunk,
                                     remat=args.remat,
                                     kv_partition=args.kv_partition)
                except Exception as e:  # a failure here is a bug in the system
                    n_fail += 1
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "skipped": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"FAIL {arch} {shape} mp={mp}: {e}")
                tag = f"__{args.tag}" if args.tag else ""
                fn = out / f"{cell_id(rec) if 'mesh' in rec or 'reason' in rec or True else ''}{tag}.json"
                fn = out / (cell_id(rec) + tag + ".json")
                fn.write_text(json.dumps(rec, indent=1))
                status = ("SKIP" if rec.get("skipped") else
                          ("ERR " if "error" in rec else "OK  "))
                extra = ""
                if not rec.get("skipped") and "roofline" in rec:
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']} comp={r['compute_s']:.3e}s "
                             f"mem={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s "
                             f"fits={rec['memory']['fits_16GB']}")
                    print(f"{status} {arch:24s} {shape:12s} {'2pod' if mp else '1pod'} "
                          f"({time.time()-t0:5.1f}s){extra}")
                    if not rec.get("skipped") and "memory" in rec:
                        print(f"     memory_analysis: {rec['memory']}")
                        print(f"     cost_analysis:   {rec['cost_analysis']}")
                else:
                    print(f"{status} {arch:24s} {shape:12s} {'2pod' if mp else '1pod'} "
                          f"({time.time()-t0:5.1f}s) {rec.get('reason', rec.get('error', ''))[:90]}")
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
