import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""HLO collective audit: the §Perf loop's profiler substitute.

Prints per-collective byte totals and the top ops with op_name metadata
(which jaxpr op emitted them) — this is how the perf iterations localize
collective/memory waste without real-TPU traces.

  PYTHONPATH=src python -m repro.launch.audit --arch arctic-480b \
      --shape train_4k [--layers 1] [--mesh single]
"""
import argparse
import collections
import re


def audit(arch, shape_name, mesh_kind="single", layers=None, top=20):
    import jax
    from repro.configs.registry import get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_plan

    bundle, spec = get_shape(arch, shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    ov = None
    if layers is not None and bundle.family == "lm":
        ov = dict(n_layers=layers, attn_chunk=spec.dim("seq_len"))
    plan = build_plan(bundle, spec, mesh, lm_overrides=ov)
    with jax.set_mesh(mesh):
        comp = jax.jit(plan.step, in_shardings=plan.in_shardings,
                       donate_argnums=plan.donate).lower(*plan.args).compile()
    txt = comp.as_text()
    pat = re.compile(
        r"= (\w+)\[([\d,]*)\][^ ]* "
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)\(")
    dt = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
          "s8": 1, "u8": 1, "f64": 8, "s64": 8}
    tot = collections.Counter()
    rows = []
    for line in txt.splitlines():
        m = pat.search(line)
        if not m:
            continue
        d, dims, kind = m.groups()
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        b = n * dt.get(d, 4)
        tot[kind] += b
        op = re.search(r'op_name="([^"]*)"', line)
        rows.append((b, kind, f"{d}[{dims}]",
                     (op.group(1) if op else "?")[-90:]))
    print(f"=== {arch}/{shape_name}/{mesh_kind} per-device collective "
          f"bytes ===")
    for k, v in sorted(tot.items()):
        print(f"  {k:20s} {v/1e9:8.2f} GB")
    rows.sort(reverse=True)
    print(f"=== top {top} ===")
    for b, kind, shp, op in rows[:top]:
        print(f"  {b/1e6:9.1f}MB {kind:18s} {shp:28s} {op}")
    return tot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()
    audit(args.arch, args.shape, args.mesh, args.layers)


if __name__ == "__main__":
    main()
