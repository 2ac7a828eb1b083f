import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (DESIGN §6/§7).

Lowers + compiles every (architecture x input-shape) cell against the
production meshes — (16,16) single pod and (2,16,16) multi-pod — with
ShapeDtypeStruct stand-ins (no allocation), printing memory_analysis()
and cost_analysis(), parsing the collective schedule out of the compiled
HLO, and appending everything to a JSON results file consumed by
EXPERIMENTS.md and the roofline/perf loop.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""
import argparse
import json
import pathlib
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_kind: str, verbose=True) -> dict:
    import jax
    from repro.configs.registry import get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import (model_flops, parse_collective_bytes,
                                       roofline_terms)
    from repro.launch.steps import build_plan

    bundle, spec = get_shape(arch, shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
               n_devices=mesh.size, ok=False)
    try:
        t0 = time.time()
        plan = build_plan(bundle, spec, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(plan.step, in_shardings=plan.in_shardings,
                             donate_argnums=plan.donate)
            lowered = jitted.lower(*plan.args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
        mem = compiled.memory_analysis()
        print(mem)
        cost = compiled.cost_analysis()
        print({k: v for k, v in cost.items()
               if k in ("flops", "bytes accessed")})
        rec["mem"] = dict(
            argument_gb=getattr(mem, "argument_size_in_bytes", 0) / 1e9,
            output_gb=getattr(mem, "output_size_in_bytes", 0) / 1e9,
            temp_gb=getattr(mem, "temp_size_in_bytes", 0) / 1e9,
            alias_gb=getattr(mem, "alias_size_in_bytes", 0) / 1e9)
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        coll = parse_collective_bytes(compiled.as_text())
        rec["flops_per_dev"] = flops
        rec["bytes_per_dev"] = bytes_acc
        rec["collectives"] = coll
        # XLA's cost_analysis counts loop bodies ONCE (verified by probe):
        # for LM cells, recover exact totals by lowering L=0 and L=1
        # variants with unchunked attention and extrapolating linearly.
        if bundle.family == "lm":
            L = bundle.config.n_layers
            Tk = spec.dim("seq_len")
            c = {}
            for nl in (0, 1):
                ov = dict(n_layers=nl, attn_chunk=Tk)
                p2 = build_plan(bundle, spec, mesh, lm_overrides=ov)
                with jax.set_mesh(mesh):
                    comp2 = jax.jit(
                        p2.step, in_shardings=p2.in_shardings,
                        donate_argnums=p2.donate).lower(*p2.args).compile()
                cost2 = comp2.cost_analysis()
                coll2 = parse_collective_bytes(comp2.as_text())
                c[nl] = dict(
                    flops=float(cost2.get("flops", 0.0)),
                    bytes=float(cost2.get("bytes accessed", 0.0)),
                    coll={k: coll2[k] for k in coll2 if k != "counts"})
            flops = c[0]["flops"] + L * (c[1]["flops"] - c[0]["flops"])
            bytes_acc = c[0]["bytes"] + L * (c[1]["bytes"] - c[0]["bytes"])
            coll = {k: c[0]["coll"].get(k, 0)
                    + L * (c[1]["coll"].get(k, 0) - c[0]["coll"].get(k, 0))
                    for k in c[0]["coll"]}
            rec["flops_per_dev_true"] = flops
            rec["bytes_per_dev_true"] = bytes_acc
            rec["collectives_true"] = coll
        elif bundle.family == "cca":
            rec["note"] = ("costs are per simulated cycle x chunk counted "
                           "once = exactly one cycle per device")
        rec["roofline"] = roofline_terms(flops, bytes_acc, coll)
        mf = model_flops(bundle, spec)
        rec["model_flops_global"] = mf
        if mf == mf and flops > 0:  # not NaN
            rec["useful_ratio"] = mf / (flops * mesh.size)
        rec["desc"] = plan.static_desc
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(rec["error"])
    return rec


def merge_out(path: str, recs: list) -> None:
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    data = {}
    if p.exists():
        data = json.loads(p.read_text())
    for r in recs:
        data[f'{r["arch"]}|{r["shape"]}|{r["mesh"]}'] = r
    p.write_text(json.dumps(data, indent=1, default=str))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--family", help="run all archs of one family")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    from repro.configs.registry import ARCHS, cells
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.all:
        todo = cells()
    elif args.family:
        todo = [(a, s.name) for a, b in ARCHS.items()
                if b.family == args.family for s in b.shapes]
    else:
        todo = [(args.arch, args.shape)]

    done = set()
    p = pathlib.Path(args.out)
    if args.skip_done and p.exists():
        data = json.loads(p.read_text())
        done = {k for k, v in data.items() if v.get("ok")}

    for arch, shape_name in todo:
        for mk in meshes:
            if f"{arch}|{shape_name}|{mk}" in done:
                print(f"=== skip {arch} / {shape_name} / {mk} (done)")
                continue
            print(f"=== dry-run {arch} / {shape_name} / mesh={mk}")
            rec = run_cell(arch, shape_name, mk)
            merge_out(args.out, [rec])
            status = "OK" if rec["ok"] else f'FAIL {rec.get("error")}'
            print(f"=== {arch}/{shape_name}/{mk}: {status}")


if __name__ == "__main__":
    main()
