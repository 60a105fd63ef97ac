import os
# 512 forced host devices stand in for the production meshes; pin the CPU so
# neither this process nor its --all children (which inherit the env) try to
# take an attached accelerator that another process holds
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes (16x16 single-pod, 2x16x16 multi-pod) with
ShapeDtypeStruct inputs — no allocation.  Proves the distribution config is
coherent: sharding mismatches, compile-time OOM or unsupported collectives
fail here.

Per cell it records: memory_analysis (bytes/device), cost_analysis (FLOPs /
bytes for §Roofline), and the collective-op byte census parsed from the
post-SPMD HLO.  Results cached as JSON under --out (incremental; --force to
redo).  ``--all`` drives every cell in subprocesses (one compile per process
keeps 512-device XLA memory bounded).

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod both] --out experiments/dryrun
"""

import argparse
import json
import re
import subprocess
import sys
import time


# regex over post-SPMD HLO: "<shape> <collective>(" — result shape precedes op
_COLL_RE = re.compile(
    r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

# per-chip wire-byte factor per result byte (ring algorithms)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def parse_collectives(hlo_text: str) -> dict:
    per_kind_bytes = {}
    per_kind_count = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES.get(dt, 4)
        per_kind_bytes[kind] = per_kind_bytes.get(kind, 0) + b
        per_kind_count[kind] = per_kind_count.get(kind, 0) + 1
    wire = sum(_WIRE_FACTOR[k] * v for k, v in per_kind_bytes.items())
    return {"per_kind_bytes": per_kind_bytes, "per_kind_count": per_kind_count,
            "wire_bytes_per_chip": wire}


def cell_path(out_dir: str, arch: str, shape: str, multi_pod: bool) -> str:
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    import jax
    from repro import configs
    from repro.configs.base import STEP_FNS
    from repro.distributed import sharding as shlib
    from repro.launch.mesh import make_production_mesh
    from repro.optim import adamw_init

    spec = configs.get(arch_id)
    cell = spec.shapes[shape_name]
    record = {
        "arch": arch_id, "shape": shape_name,
        "mesh": [2, 16, 16] if multi_pod else [16, 16],
        "kind": cell.kind, "dims": {k: v for k, v in cell.dims.items()
                                    if isinstance(v, (int, float, str))},
    }
    if cell.skip_reason:
        record["status"] = "skipped"
        record["skip_reason"] = cell.skip_reason
        return record

    cfg = spec.config_for_cell(spec.make_config(), cell)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = spec.plan_for(cfg, cell)
    record["plan"] = plan.name

    from repro.models import egnn, recsys, transformer
    mod = {"lm": transformer, "gnn": egnn, "recsys": recsys}[spec.family]

    t0 = time.time()
    with shlib.activate(mesh, plan):
        params_abs = mod.abstract(cfg)
        axes = mod.axes(cfg)
        p_shard = shlib.sharding_for_axes_tree(axes, params_abs)
        inputs = spec.input_specs(cfg, cell)
        b_axes = spec.batch_axes(cfg, cell)
        b_shard = shlib.sharding_for_axes_tree(b_axes, inputs)
        step_fn, is_train = STEP_FNS[spec.family](cfg, cell)
        if is_train:
            opt_abs = jax.eval_shape(adamw_init, params_abs)
            o_shard = {
                "m": p_shard, "v": p_shard,
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            }
            if "master" in opt_abs:
                o_shard["master"] = p_shard
            lowered = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard)) \
                .lower(params_abs, opt_abs, inputs)
        else:
            lowered = jax.jit(step_fn, in_shardings=(p_shard, b_shard)) \
                .lower(params_abs, inputs)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        if mem is not None:
            for key in ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes",
                        "alias_size_in_bytes"):
                v = getattr(mem, key, None)
                if v is not None:
                    record.setdefault("memory", {})[key] = int(v)
            print("memory_analysis:", record.get("memory"))
        cost = compiled.cost_analysis()
        if cost:
            c = cost[0] if isinstance(cost, (list, tuple)) else cost
            record["cost"] = {k: float(v) for k, v in c.items()
                              if isinstance(v, (int, float)) and (
                                  k in ("flops", "bytes accessed")
                                  or k.startswith("bytes accessed"))}
            print("cost_analysis: flops=%.3e bytes=%.3e" % (
                record["cost"].get("flops", 0), record["cost"].get("bytes accessed", 0)))
        try:
            hlo = compiled.as_text()
            record["collectives"] = parse_collectives(hlo)
            record["hlo_lines"] = hlo.count("\n")
            from repro.launch.hlo_census import census
            record["census"] = census(hlo)   # trip-count-aware roofline terms
            print("census: flops/chip=%.3e mem/chip=%.3e wire/chip=%.3e" % (
                record["census"]["flops_per_chip"],
                record["census"]["mem_bytes_per_chip"],
                record["census"]["wire_bytes_per_chip"]))
        except Exception as e:  # pragma: no cover
            record["collectives_error"] = str(e)
        # parameter/input footprint per device (from shardings; exact)
        def sharded_bytes(tree_abs, tree_shard):
            tot = 0
            for a, s in zip(jax.tree.leaves(tree_abs), jax.tree.leaves(
                    tree_shard, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding))):
                n = 1
                for d in a.shape:
                    n *= d
                shards = 1
                spec_ = s.spec
                for i, pp in enumerate(spec_):
                    if pp is None:
                        continue
                    ax = (pp,) if isinstance(pp, str) else pp
                    k = 1
                    for aa in ax:
                        k *= mesh.shape[aa]
                    if a.shape[i] % k == 0:
                        shards *= k
                tot += n * a.dtype.itemsize // shards
            return tot
        record["param_bytes_per_device"] = sharded_bytes(params_abs, p_shard)
        record["status"] = "ok"
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", dest="multi_pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both",
                    help="which meshes to run with --all")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        from repro import configs
        meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
        todo = []
        for aid, sname, cell in configs.all_cells():
            for mp in meshes:
                path = cell_path(args.out, aid, sname, mp)
                if os.path.exists(path) and not args.force:
                    continue
                todo.append((aid, sname, mp))
        print(f"[dryrun] {len(todo)} cells to run")
        fails = []
        for i, (aid, sname, mp) in enumerate(todo):
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", aid,
                   "--shape", sname, "--out", args.out] + (["--multi-pod"] if mp else [])
            print(f"[{i+1}/{len(todo)}] {aid} x {sname} x {'2x16x16' if mp else '16x16'}",
                  flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True, timeout=args.timeout)
            if r.returncode != 0:
                fails.append((aid, sname, mp))
                err_path = cell_path(args.out, aid, sname, mp) + ".err"
                with open(err_path, "w") as f:
                    f.write(r.stdout[-5000:] + "\n" + r.stderr[-10000:])
                print(f"  FAILED ({time.time()-t0:.0f}s) -> {err_path}")
            else:
                print(f"  ok ({time.time()-t0:.0f}s)")
        print(f"[dryrun] done; {len(fails)} failures: {fails}")
        sys.exit(1 if fails else 0)

    record = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    path = cell_path(args.out, args.arch, args.shape, args.multi_pod)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps({k: v for k, v in record.items() if k != "collectives"}, indent=2))
    print("->", path)


if __name__ == "__main__":
    main()
