import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import — jax locks the device count
on first init, and the dry-run needs 512 placeholder host devices to build
the production meshes ((16,16) single-pod, (2,16,16) multi-pod).

Per cell this driver records, to ``experiments/dryrun/<arch>__<shape>__<mesh>.json``:

  * ``memory_analysis``  — per-device argument/output/temp/peak bytes
    (proves the cell fits 16 GiB HBM),
  * ``cost_analysis``    — per-device HLO FLOPs + bytes accessed,
  * collective breakdown — parsed from the post-SPMD HLO
    (``compiled.as_text()``): per-op-kind payload bytes using ring-traffic
    factors (all-reduce 2(g-1)/g, all-gather/all-to-all (g-1)/g,
    reduce-scatter (g-1), permute 1) with the group size ``g`` parsed from
    ``replica_groups``,
  * roofline terms       — compute / memory / collective seconds per step on
    TPU v5e constants (launch.mesh), dominant term, MODEL_FLOPS ratio.

Usage:
  python -m repro.launch.dryrun --all                      # full 40-cell x 2-mesh matrix
  python -m repro.launch.dryrun --arch qwen3-moe-235b-a22b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --list
"""

import argparse
import json
import re
import time
import traceback


def _parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--list", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--variant", default="base",
                   choices=["base", "opt", "opt-beam"],
                   help="'opt' lowers the beyond-paper-optimised step where "
                        "one exists (suffixes the JSON)")
    return p.parse_args()


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 0


def _traffic_factor(kind: str, g: int) -> float:
    """Per-device ring-traffic bytes as a multiple of the op's output bytes."""
    if g <= 1:
        g = 2  # unknown group -> conservative small-group factors
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g
    if kind == "all-gather":
        return (g - 1) / g
    if kind == "reduce-scatter":
        return float(g - 1)
    if kind == "all-to-all":
        return (g - 1) / g
    return 1.0  # collective-permute


def parse_collectives(hlo_text: str) -> dict:
    """Per-kind payload/traffic bytes from a post-SPMD (per-device) HLO."""
    out = {k: dict(count=0, out_bytes=0, traffic_bytes=0.0)
           for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for kind in _COLLECTIVES:
            # match ` = <shape> <kind>(` and `<kind>-start(`; skip -done (no
            # new traffic) and convert-fusions mentioning the name.
            if f" {kind}(" in s or f" {kind}-start(" in s:
                lhs = s.split("=", 1)[1]
                op_pos = lhs.find(kind)
                shape_txt = lhs[:op_pos]
                b = _shape_bytes(shape_txt)
                g = _group_size(s)
                out[kind]["count"] += 1
                out[kind]["out_bytes"] += b
                out[kind]["traffic_bytes"] += b * _traffic_factor(kind, g)
                break
    out["total_traffic_bytes"] = sum(
        v["traffic_bytes"] for k, v in out.items() if isinstance(v, dict)
    )
    return out


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes", "peak_memory_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out and "peak_memory_in_bytes" not in out:
        out["peak_memory_in_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
        )
    return out


def _compile_cell(cell, mesh):
    import jax

    jitted = jax.jit(
        cell.step,
        in_shardings=cell.in_shardings(mesh),
        out_shardings=cell.out_shardings(mesh),
        donate_argnums=cell.donate,
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return compiled


def _measure(compiled):
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax < 0.6 returns [dict]
        cost = cost[0] if cost else {}
    cost = dict(cost)
    cost = {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals", "optimal_seconds")}
    coll = parse_collectives(compiled.as_text())
    return cost, coll


def run_cell(arch: str, shape: str, mesh_kind: str,
             variant: str = "base") -> dict:
    import jax

    from repro.launch import mesh as mesh_lib
    from repro.launch.steps import build_cell, needs_probe, probe_trip_count

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, variant=variant)
    t_build = time.time() - t0
    compiled = _compile_cell(cell, mesh)
    t_compile = time.time() - t0 - t_build
    t_lower = t_build

    cost, coll = _measure(compiled)
    mem = _memory_dict(compiled)

    flops_dev = cost.get("flops", 0.0)
    bytes_dev = cost.get("bytes accessed", 0.0)
    coll_dev = coll["total_traffic_bytes"]
    probe = None

    if needs_probe(arch):
        # XLA cost analysis counts the layer-scan body once; probe with 1 and
        # 2 UNROLLED layers and extrapolate: F(L) = F1 + (L-1) * (F2 - F1).
        L = probe_trip_count(arch)
        c1, k1 = _measure(_compile_cell(build_cell(arch, shape, mesh, 1), mesh))
        c2, k2 = _measure(_compile_cell(build_cell(arch, shape, mesh, 2), mesh))

        def extr(a1, a2):
            return max(a1, a1 + (L - 1) * (a2 - a1))

        flops_dev = extr(c1.get("flops", 0.0), c2.get("flops", 0.0))
        bytes_dev = extr(c1.get("bytes accessed", 0.0),
                         c2.get("bytes accessed", 0.0))
        coll_dev = extr(k1["total_traffic_bytes"], k2["total_traffic_bytes"])
        probe = dict(
            n_layers=L,
            probe1=dict(flops=c1.get("flops"), bytes=c1.get("bytes accessed"),
                        coll=k1["total_traffic_bytes"]),
            probe2=dict(flops=c2.get("flops"), bytes=c2.get("bytes accessed"),
                        coll=k2["total_traffic_bytes"]),
            corrected=dict(flops=flops_dev, bytes=bytes_dev, coll=coll_dev),
        )
    elif arch == "pdasc" and shape.startswith("build"):
        # MSA build runs PAM inside fori/while loops (bodies counted once);
        # use the analytic distance-matrix count (meta) as the compute term.
        flops_dev = float(cell.meta["model_flops"]) / n_chips
        probe = dict(analytic=True)
    compute_s = flops_dev / mesh_lib.PEAK_FLOPS_BF16
    memory_s = bytes_dev / mesh_lib.HBM_BW
    collective_s = coll_dev / mesh_lib.ICI_BW
    terms = dict(compute_s=compute_s, memory_s=memory_s,
                 collective_s=collective_s)
    bottleneck = max(terms, key=terms.get)

    model_flops = float(cell.meta.get("model_flops", 0.0))
    hlo_flops_total = flops_dev * n_chips
    result = dict(
        arch=arch, shape=shape, mesh=mesh_kind, kind=cell.kind,
        n_chips=int(n_chips),
        ok=True,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        cost_analysis=cost,
        memory_analysis=mem,
        collectives=coll,
        probe=probe,
        roofline=dict(
            **{k: float(v) for k, v in terms.items()},
            bottleneck=bottleneck,
            model_flops=model_flops,
            hlo_flops_per_device=flops_dev,
            hlo_flops_total=hlo_flops_total,
            useful_flops_ratio=(model_flops / hlo_flops_total
                                if hlo_flops_total else None),
            step_time_lower_bound_s=max(terms.values()),
        ),
        meta={k: (float(v) if isinstance(v, (int, float)) else v)
              for k, v in cell.meta.items()},
    )
    return result


def main():
    args = _parse_args()
    import jax  # after XLA_FLAGS

    from repro.configs import all_cells

    if args.list:
        for a, s in all_cells():
            print(f"{a:24s} {s}")
        return

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    if not cells:
        raise SystemExit("no matching cells")
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_fail = 0
    suffix = "" if args.variant == "base" else f"__{args.variant}"
    for arch, shape in cells:
        for mk in meshes:
            path = os.path.join(args.out, f"{arch}__{shape}__{mk}{suffix}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {arch} x {shape} x {mk}")
                continue
            print(f"[dryrun] {arch} x {shape} x {mk} ...", flush=True)
            try:
                res = run_cell(arch, shape, mk, variant=args.variant)
                n_ok += 1
                r = res["roofline"]
                print(
                    f"  ok: compile={res['compile_s']:.1f}s "
                    f"flops/dev={res['cost_analysis'].get('flops', 0):.3e} "
                    f"bottleneck={r['bottleneck']} "
                    f"lb={r['step_time_lower_bound_s']*1e3:.2f}ms",
                    flush=True,
                )
                if res["memory_analysis"]:
                    print("  memory:", json.dumps(res["memory_analysis"]))
            except Exception as e:
                n_fail += 1
                res = dict(arch=arch, shape=shape, mesh=mk, ok=False,
                           error=f"{type(e).__name__}: {e}",
                           traceback=traceback.format_exc()[-4000:])
                print(f"  FAIL: {type(e).__name__}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
